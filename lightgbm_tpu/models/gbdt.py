"""GBDT boosting engine and the `Booster` class.

TPU-native replacement for LightGBM's ``GBDT::TrainOneIter`` driver
(SURVEY.md §3.1): one boosting round = one jitted device program
(grad/hess -> bagging-masked stats -> best-first tree growth -> train-score
update), driven by a host loop that only syncs for early stopping / logging.

Compilation strategy: the round step is cached per *static* configuration
(objective, num_leaves, num_bins, ...) at module level, while every
continuous hyper-parameter (learning_rate, lambda_l1/l2, min_data_in_leaf,
fractions, max_depth) is a traced scalar.  A 108-config sweep with three
distinct ``num_leaves`` values therefore compiles exactly three programs
(SURVEY.md §3.3 TPU mapping), and configs can later be vmapped.
"""

from __future__ import annotations

import functools
from collections.abc import MutableSequence as _MutableSequence
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..config import Params, default_metric_for_objective, parse_params
from ..dataset import Dataset
from ..metrics import get_metric
from ..objectives import Objective, create_objective
from ..ops.lookup import lookup_values
from ..ops.members import member_view, members_of
from ..ops.predict import predict_forest_binned, predict_tree_binned
from ..ops.split import CatColumns, SplitContext
from ..utils import profiling
from .spec import (GrowSpec, categorical_key,
                   check_int8_row_limit, resolve_grow_spec)
from .tree import (Tree, build_cat_info,
                   grower_from_spec, pad_tree, renew_leaf_values, wave_extent)


class HyperScalars(NamedTuple):
    """Traced per-config scalars fed to the jitted round step."""

    learning_rate: jnp.ndarray
    lambda_l1: jnp.ndarray
    lambda_l2: jnp.ndarray
    min_data_in_leaf: jnp.ndarray
    min_sum_hessian: jnp.ndarray
    min_gain_to_split: jnp.ndarray
    max_depth: jnp.ndarray
    feature_fraction_bynode: jnp.ndarray
    top_rate: jnp.ndarray        # GOSS a (used only when boosting="goss")
    other_rate: jnp.ndarray      # GOSS b
    max_delta_step: jnp.ndarray = 0.0   # |leaf output| cap (<=0 = off)
    path_smooth: jnp.ndarray = 0.0      # child-output smoothing (0 = off)
    linear_lambda: jnp.ndarray = 0.0    # linear-leaf ridge (linear_tree)

    @staticmethod
    def from_params(p: Params) -> "HyperScalars":
        return HyperScalars(
            learning_rate=jnp.float32(p.learning_rate),
            lambda_l1=jnp.float32(p.lambda_l1),
            lambda_l2=jnp.float32(p.lambda_l2),
            min_data_in_leaf=jnp.float32(p.min_data_in_leaf),
            min_sum_hessian=jnp.float32(p.min_sum_hessian_in_leaf),
            min_gain_to_split=jnp.float32(p.min_gain_to_split),
            max_depth=jnp.int32(p.max_depth),
            feature_fraction_bynode=jnp.float32(p.feature_fraction_bynode),
            top_rate=jnp.float32(p.top_rate),
            other_rate=jnp.float32(p.other_rate),
            max_delta_step=jnp.float32(p.max_delta_step),
            path_smooth=jnp.float32(p.path_smooth),
            linear_lambda=jnp.float32(p.linear_lambda),
        )

    def ctx(self) -> SplitContext:
        return SplitContext(
            lambda_l1=self.lambda_l1,
            lambda_l2=self.lambda_l2,
            min_data_in_leaf=self.min_data_in_leaf,
            min_sum_hessian=self.min_sum_hessian,
            min_gain_to_split=self.min_gain_to_split,
            max_delta_step=self.max_delta_step,
            path_smooth=self.path_smooth,
        )


def _objective_static_key(obj: Objective, p: Params) -> tuple:
    """Hashable key identifying the objective for the jit-compile cache.

    The custom-loss callable rides in the key itself (callables hash by
    identity), so user fobj objectives get their own cached program instead
    of crashing the rebuild path.

    Group-based objectives (lambdarank) add the STATIC part of their
    packed layout (``obj.layout``: the shapes of the query blocks); the
    layout's tensors are operands of the round program (``obj.groups``,
    handed in by every caller), so two trainings on equal shapes share one
    compiled program and nothing is keyed by identity.
    """
    key = (
        obj.name,
        p.sigmoid,
        getattr(obj, "pos_weight", 1.0),
        p.alpha,
        p.fair_c,
        p.poisson_max_delta_step,
        p.lambdarank_truncation_level,
        p.lambdarank_norm,
        p.num_class,
        p.extra.get("fobj"),
        p.tweedie_variance_power,
    )
    if getattr(obj, "needs_group", False):
        key += (obj.layout,)
    return key


def _rebuild_objective(key: tuple) -> Objective:
    (name, sigmoid, pos_weight, alpha, fair_c, pmd, trunc, norm, num_class,
     fobj, tvp) = (key + (None, 1.5))[:11]
    p = Params(
        objective="none" if fobj is not None else name,
        sigmoid=sigmoid, alpha=alpha, fair_c=fair_c,
        poisson_max_delta_step=pmd, lambdarank_truncation_level=trunc,
        lambdarank_norm=norm, num_class=max(num_class, 1),
        tweedie_variance_power=tvp,
    )
    if fobj is not None:
        p.extra["fobj"] = fobj
    obj = create_objective(p)
    if hasattr(obj, "pos_weight"):
        obj.pos_weight = pos_weight
    if getattr(obj, "needs_group", False):
        obj.layout = key[11]
    return obj


def _grad_hess(obj: Objective, pred, y, w, groups):
    """The objective's gradients; a group objective also takes its packed
    layout's tensors (``groups``, an operand of the caller's program)."""
    if groups is None:
        return obj.grad_hess(pred, y, w)
    return obj.grad_hess(pred, y, w, groups)


@functools.lru_cache(maxsize=None)
def _member_scan_fn():
    """The jitted member view + split scan of one node's bundle planes,
    ``(planes, members, ctx, feature_mask) -> BestSplit``."""
    from ..ops.split import find_best_split

    @jax.jit
    def scan(planes, members, ctx, feature_mask):
        return find_best_split(member_view(planes, members), ctx,
                               feature_mask, jnp.bool_(True),
                               bins_minor=True)

    return scan


@functools.lru_cache(maxsize=None)
def _cat_scan_fn(cat_key: tuple):
    """The jitted categorical split scan of one node's planes over its
    categorical columns, ``(planes, ctx, cats) -> BestSplit``
    (``cat_key``: ``spec.categorical_key``)."""
    from ..ops.split import find_best_split

    @jax.jit
    def scan(planes, ctx, cats):
        num_features = planes.shape[1]
        return find_best_split(
            planes, ctx, jnp.ones(num_features, jnp.float32),
            jnp.bool_(True), build_cat_info(cat_key, cats),
            bins_minor=True)

    return scan


@functools.lru_cache(maxsize=None)
def _group_grad_fn(obj_key: tuple):
    """The jitted lambda pass alone, ``(pred, y, w, groups) -> (g, h)``:
    the replicated pass of the data-parallel learner."""
    return jax.jit(_rebuild_objective(obj_key).grad_hess)


def _goss_compact_round(grow, bins, y, w, bag, pred, fmask,
                        hyper: HyperScalars, key, g, h, goss_k,
                        renew_alpha, sample_key=None, renew_scale=None,
                        members=None, col_order=None, cats=None):
    """One compacted GOSS round (shared by the per-round and scanned paths
    — the two MUST stay in RNG lockstep for fused == host training).

    Unlike CPU LightGBM (where skipping rows is free), a TPU histogram pass
    costs the same for masked rows as for live ones — so the sampled subset
    is GATHERED into a dense [k_top + k_other, F] matrix and the tree grown
    on that, cutting histogram cost by ~(top_rate + other_rate).  Train
    scores for ALL rows then come from one traversal pass.  ``grow`` is
    the caller's ``tree.grower_from_spec`` closure (the dp learner's
    carries its mesh placement)."""
    from ..ops.sampling import approx_top_mask

    k_top, k_other = goss_k
    n = bins.shape[0]
    if sample_key is None:
        sample_key = key  # sampling and growth share one stream (serial)
    valid = bag > 0
    # sort-free selection (a 1M-row lax.top_k is a ~7 s device sort and
    # long fused GOSS programs crashed the runtime watchdog): histogram-
    # threshold masks, then prefix-sum compaction into the static buffers
    is_top = approx_top_mask(jnp.where(valid, jnp.abs(g), 0.0), valid,
                             k_top)
    rest = valid & ~is_top
    u = jax.random.uniform(jax.random.fold_in(sample_key, 0x7FFFFFFF), (n,))
    sampled = approx_top_mask(jnp.where(rest, 1.0 - u, 0.0), rest, k_other)

    def compact_idx(mask, k):
        pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
        idx = jnp.zeros(k, jnp.int32).at[
            jnp.where(mask, pos, k)].set(lax.iota(jnp.int32, n),
                                         mode="drop")
        filled = lax.iota(jnp.int32, k) < jnp.sum(mask.astype(jnp.int32))
        return idx, filled.astype(jnp.float32)

    top_idx, top_fill = compact_idx(is_top, k_top)
    other_idx, other_fill = compact_idx(sampled, k_other)
    idx = jnp.concatenate([top_idx, other_idx])         # [k]
    amp = (1.0 - hyper.top_rate) / jnp.maximum(hyper.other_rate, 1e-12)
    wt = jnp.concatenate([top_fill, other_fill * amp])
    # when live rows < the static k (small or heavily padded shards), the
    # unfilled buffer slots point at row 0 with weight 0 — mask their count
    # (their g/h are already zero via the sample weights) so they cannot
    # pollute min_data_in_leaf gating
    live = (bag[idx] > 0).astype(jnp.float32) * (wt > 0)
    wt = wt * live
    bins_c = jnp.take(bins, idx, axis=0)
    stats = jnp.stack([g[idx] * wt, h[idx] * wt, live], axis=-1)
    tree, rl_c, passes = grow(bins_c, stats, fmask, hyper.ctx(),
                              hyper.max_depth, hyper.feature_fraction_bynode,
                              key, members=members, col_order=col_order,
                              cats=cats)
    if renew_alpha is not None:
        rw = w[idx] * wt
        if renew_scale is not None:
            rw = rw * renew_scale(y[idx])
        tree = renew_leaf_values(tree, rl_c, y[idx] - pred[idx],
                                 rw, renew_alpha)
    # convergence-checked traversal (depth_cap=None): iterates the tree's
    # ACTUAL depth — the num_leaves-deep static scan was 3.7 s/round at
    # 500k rows (r5 trace), ~10x the whole histogram work, and any
    # optimistic static bound is unsound under stalled waves
    new_pred = pred + hyper.learning_rate * predict_tree_binned(
        tree, bins, None, members)
    return tree, new_pred, passes



def mc_round_update(grow_one, g, h, keys, pred, learning_rate):
    """Shared multiclass round: one tree per class via a vmapped grower.

    The class axis vmaps over ``grow_one`` (per-class histogram psums /
    split-exchange all_gathers batch into one collective under mesh
    learners), and the prediction update is one batched
    ``leaf_value[row_leaf]`` lookup.  Callers own their RNG chain: the
    ``keys`` argument must already match the host loop's fold/split
    sequence, or fused/mesh training would diverge from serial."""
    trees, row_leafs = jax.vmap(grow_one, in_axes=(1, 1, 0))(g, h, keys)
    deltas = jax.vmap(lambda t, rl: lookup_values(
        rl, t.leaf_value))(trees, row_leafs)            # [K, n]
    return trees, pred + learning_rate * deltas.T


@functools.lru_cache(maxsize=None)
def _round_fn(obj_key: tuple, spec: GrowSpec, is_rf: bool, num_class: int,
              goss_k: Optional[Tuple[int, int]], linear_k: Optional[int]):
    """goss_k: static (k_top, k_other) row counts enabling the compacted
    GOSS path; None = plain gbdt/rf.  No defaults: ``lru_cache`` keys on
    the call as written, so every caller spells all six."""
    obj = _rebuild_objective(obj_key)
    is_goss = goss_k is not None
    renew_alpha = getattr(obj, "renew_alpha", None)
    renew_scale = getattr(obj, "renew_scale", None)
    # the class axis vmaps over the grower: no in-kernel partition there
    grow = grower_from_spec(spec, fuse_partition=num_class == 1)

    def goss_bag(key, g, bag, hyper):
        """GOSS as row re-weighting (multiclass path): top-|g| keep +
        amplified sample of the rest (SURVEY.md §2C; VERDICT r1 item 5)."""
        from ..ops.sampling import goss_weights
        g_abs = jnp.abs(g) if g.ndim == 1 else jnp.sum(jnp.abs(g), axis=-1)
        return goss_weights(key, g_abs, bag, hyper.top_rate,
                            hyper.other_rate, jnp.sum(bag))

    if num_class > 1:
        # one tree per class per round, grown simultaneously: the class axis
        # is a vmapped batch over the grower (SURVEY.md §7 batching design)
        @jax.jit
        def round_fn_mc(bins, y, w, bag, pred, feature_mask,
                        hyper: HyperScalars, key, groups=None, members=None,
                        col_order=None, cats=None):
            g, h = _grad_hess(obj, pred, y, w, groups)    # [n, K]
            if is_goss:
                bag = goss_bag(jax.random.fold_in(key, 0x7FFFFFFF), g, bag, hyper)

            def grow_one(gc, hc, kc):
                stats = jnp.stack([gc * bag, hc * bag,
                                   (bag > 0).astype(jnp.float32)], axis=-1)
                return grow(bins, stats, feature_mask, hyper.ctx(),
                            hyper.max_depth, hyper.feature_fraction_bynode,
                            kc, members=members, col_order=col_order,
                            cats=cats)[:2]

            return mc_round_update(grow_one, g, h,
                                   jax.random.split(key, num_class), pred,
                                   hyper.learning_rate)

        return round_fn_mc

    if is_goss:  # single-class: compacted GOSS (mc handled above, masked)

        @jax.jit
        def round_fn_goss(bins, y, w, bag, pred, feature_mask,
                          hyper: HyperScalars, key, groups=None,
                          members=None, col_order=None, cats=None):
            g, h = _grad_hess(obj, pred, y, w, groups)
            return _goss_compact_round(
                grow, bins, y, w, bag, pred, feature_mask, hyper, key, g, h,
                goss_k, renew_alpha, renew_scale=renew_scale,
                members=members, col_order=col_order, cats=cats)[:2]

        return round_fn_goss

    if linear_k is not None:
        from .tree import fit_linear_leaves

        @jax.jit
        def round_fn_linear(bins, y, w, bag, pred, feature_mask,
                            hyper: HyperScalars, key, xraw, groups=None,
                            cats=None):
            """linear_tree round: constant-leaf growth on binned codes,
            then every leaf refits a ridge model over its path features on
            the RAW values (tree.fit_linear_leaves) — the Newton constant
            remains the fallback for degenerate leaves."""
            g, h = _grad_hess(obj, pred, y, w, groups)
            stats = jnp.stack(
                [g * bag, h * bag, (bag > 0).astype(jnp.float32)], axis=-1)
            tree, row_leaf, _ = grow(bins, stats, feature_mask, hyper.ctx(),
                                     hyper.max_depth,
                                     hyper.feature_fraction_bynode, key,
                                     cats=cats)
            tree, delta = fit_linear_leaves(
                tree, row_leaf, xraw, g, h, bag, hyper.linear_lambda,
                linear_k, spec.row_chunk)
            new_pred = pred + hyper.learning_rate * delta
            return tree, new_pred

        return round_fn_linear

    @jax.jit
    def round_fn(bins, y, w, bag, pred, feature_mask, hyper: HyperScalars,
                 key, groups=None, members=None, col_order=None, cats=None):
        with jax.named_scope("lgbtpu.grad"):
            g, h = _grad_hess(obj, pred, y, w, groups)
            stats = jnp.stack(
                [g * bag, h * bag, (bag > 0).astype(jnp.float32)], axis=-1)
        tree, row_leaf, _ = grow(bins, stats, feature_mask, hyper.ctx(),
                                 hyper.max_depth,
                                 hyper.feature_fraction_bynode, key,
                                 members=members, col_order=col_order,
                                 cats=cats)
        if renew_alpha is not None:
            rw = w * bag if renew_scale is None else w * bag * renew_scale(y)
            tree = renew_leaf_values(tree, row_leaf, y - pred, rw,
                                     renew_alpha)
        with jax.named_scope("lgbtpu.pred_update"):
            shrink = jnp.where(is_rf, 1.0, hyper.learning_rate)
            new_pred = pred + shrink * lookup_values(row_leaf,
                                                     tree.leaf_value)
        return tree, new_pred

    return round_fn


@functools.lru_cache(maxsize=None)
def _multi_round_fn(obj_key: tuple, spec: GrowSpec, is_rf: bool,
                    n_rounds: int, bagging_freq: int, use_ff: bool,
                    goss_k: Optional[Tuple[int, int]] = None):
    """``n_rounds`` boosting rounds as ONE device program (`lax.scan`).

    The host round loop pays a dispatch round-trip per boosting round,
    which dominates wall time on reference-sized data (the diamonds
    bench spends 30 strict histogram trips of microseconds each per
    round).  Scanning rounds on device
    removes that entirely; trees come back stacked with a leading
    [n_rounds] axis, and so do the rounds' pass logs (``passes``: f32
    ``[n_rounds, 5, grow_leaves - 1]``, models.tree._PASS), which are not
    part of the model.  RNG streams match the host loop exactly (same
    fold_in(key, round_index) chain), so fused and host training produce
    identical models.  ``members`` (``ops.members.Members``) grows on an
    EFB table's bundle columns; the feature mask is over the original
    features.  ``cats`` (``ops.split.CatColumns``) are the table's
    categorical columns (``None``: none).
    """
    obj = _rebuild_objective(obj_key)
    renew_alpha = getattr(obj, "renew_alpha", None)
    renew_scale = getattr(obj, "renew_scale", None)
    grow = grower_from_spec(spec, fuse_partition=True)

    @jax.jit
    def multi(bins, y, w, bag0, pred0, hyper: HyperScalars, round_key,
              bag_key, ff_key, row_mask, num_data, start_iter, bag_frac, ff,
              groups=None, members=None, col_order=None, cats=None):
        num_features = (bins.shape[1] if members is None
                        else members.num_features)

        def body(carry, i):
            pred, bag = carry
            if bagging_freq > 0:
                from ..ops.sampling import sample_bag

                bag = lax.cond(
                    i % bagging_freq == 0,
                    lambda _: sample_bag(
                        jax.random.fold_in(bag_key, i), row_mask,
                        bag_frac, num_data),
                    lambda _: bag, None)
            if use_ff:
                from .feature_mask import compose_tree_mask

                fmask = compose_tree_mask(
                    jax.random.fold_in(ff_key, i), ff, num_features)
            else:
                fmask = jnp.ones(num_features, jnp.float32)
            rkey = jax.random.fold_in(round_key, i)
            with jax.named_scope("lgbtpu.grad"):
                g, h = _grad_hess(obj, pred, y, w, groups)
            if goss_k is not None:
                tree, new_pred, passes = _goss_compact_round(
                    grow, bins, y, w, bag, pred, fmask, hyper, rkey, g, h,
                    goss_k, renew_alpha, renew_scale=renew_scale,
                    members=members, col_order=col_order, cats=cats)
                return (new_pred, bag), (tree, passes)
            with jax.named_scope("lgbtpu.grad"):
                stats = jnp.stack(
                    [g * bag, h * bag, (bag > 0).astype(jnp.float32)],
                    axis=-1)
            tree, row_leaf, passes = grow(bins, stats, fmask, hyper.ctx(),
                                          hyper.max_depth,
                                          hyper.feature_fraction_bynode, rkey,
                                          members=members, col_order=col_order,
                                          cats=cats)
            if renew_alpha is not None:
                rw = (w * bag if renew_scale is None
                      else w * bag * renew_scale(y))
                tree = renew_leaf_values(tree, row_leaf, y - pred, rw,
                                         renew_alpha)
            if is_rf:
                new_pred = pred
            else:
                with jax.named_scope("lgbtpu.pred_update"):
                    new_pred = pred + hyper.learning_rate * \
                        lookup_values(row_leaf, tree.leaf_value)
            return (new_pred, bag), (tree, passes)

        (pred, bag), (trees, passes) = lax.scan(
            body, (pred0, bag0), start_iter + jnp.arange(n_rounds))
        return pred, bag, trees, passes

    return multi


@functools.lru_cache(maxsize=None)
def _tree_pred_fn(depth_cap: int, num_class: int = 1):
    """``pred + shrink * tree(bins)``; ``members`` walks an EFB table's
    bundle columns (a training or validation set's codes)."""
    if num_class > 1:
        @jax.jit
        def add_tree_mc(pred, tree, bins, shrink, members=None):
            vals = jax.vmap(lambda t: predict_tree_binned(
                t, bins, depth_cap, members))(tree)      # pred [n, K]
            return pred + shrink * vals.T

        return add_tree_mc

    @jax.jit
    def add_tree(pred, tree, bins, shrink, members=None):
        return pred + shrink * predict_tree_binned(tree, bins, depth_cap,
                                                   members)

    return add_tree


def _predict_forest_mc(forest, bins, shrink, inits, n_trees, depth_cap,
                       start_iteration=0, members=None):
    """Per-class forest replay for multiclass tree stacks ([T, K, M]
    fields) -> raw scores [n, K].  The single shared implementation of the
    class-sliced predict_forest_binned loop (used by predict, the lazy rf
    train-pred reconstruction, and DART's dropped-tree sums)."""
    k = forest.leaf_value.shape[1]
    cols = [predict_forest_binned(
        jax.tree.map(lambda a, c=c: a[:, c], forest), bins,
        jnp.float32(shrink),
        float(inits[c]) if np.ndim(inits) else float(inits),
        jnp.int32(n_trees), depth_cap,
        start_iteration=jnp.int32(start_iteration), members=members)
        for c in range(k)]
    return jnp.stack(cols, axis=1)


@functools.lru_cache(maxsize=None)
def _linear_tree_pred_fn(depth_cap: int):
    """pred += shrink * (leaf_const + coef . raw_pathfeats) for ONE linear
    tree (traversal on binned codes, evaluation on raw values)."""

    @jax.jit
    def add(pred, tree, bins, xraw, shrink):
        n = bins.shape[0]
        b32 = bins.astype(jnp.int32)

        def step(node, _):
            feat = tree.split_feature[node]
            thr = tree.split_bin[node]
            code = jnp.take_along_axis(b32, feat[:, None], axis=1)[:, 0]
            go_left = code <= thr
            if tree.is_cat_split is not None:
                go_left = jnp.where(tree.is_cat_split[node],
                                    tree.cat_mask[node, code], go_left)
            nxt = jnp.where(go_left, tree.left[node], tree.right[node])
            return jnp.where(tree.is_leaf[node], node, nxt), None

        node, _ = lax.scan(step, jnp.zeros(n, jnp.int32), None,
                           length=depth_cap)
        feats = tree.linear_feat[node]                    # [n, K]
        xg = jnp.take_along_axis(xraw, jnp.maximum(feats, 0), axis=1)
        xg = jnp.where((feats >= 0) & jnp.isfinite(xg), xg, 0.0)
        val = tree.leaf_value[node] + jnp.sum(
            tree.linear_coef[node] * xg, axis=1)
        return pred + shrink * val

    return add


@functools.lru_cache(maxsize=None)
def _eval_fn(obj_key: tuple, metric_names: tuple, metric_cfg: tuple):
    obj = _rebuild_objective(obj_key)
    p = (Params(alpha=metric_cfg[0],
                tweedie_variance_power=(metric_cfg[1] if len(metric_cfg) > 1
                                        else 1.5))
         if metric_cfg else Params())
    metrics = [get_metric(m, p) for m in metric_names]

    @jax.jit
    def evaluate(pred_raw, y, w):
        t = obj.transform(pred_raw)
        return tuple(m.fn(t, y, w) for m in metrics)

    return evaluate


@functools.lru_cache(maxsize=None)
def _bag_fn():
    from ..ops.sampling import sample_bag

    return jax.jit(sample_bag)


@functools.lru_cache(maxsize=None)
def _feature_mask_fn(num_features: int, with_base: bool = False):
    from .feature_mask import compose_tree_mask

    if with_base:
        # screening composition (r20): feature_fraction samples WITHIN
        # the screener's active-set mask, so the two maskers can never
        # double-mask into an empty usable set
        @jax.jit
        def sample_features_within(key, fraction, base_mask):
            return compose_tree_mask(key, fraction, num_features,
                                     base_mask)

        return sample_features_within

    @jax.jit
    def sample_features(key, fraction):
        return compose_tree_mask(key, fraction, num_features)

    return sample_features


class _SegView:
    """Placeholder for round ``j`` of a stacked k-round tree segment."""

    __slots__ = ("seg", "j")

    def __init__(self, seg, j):
        self.seg = seg
        self.j = j


class _TreeStore(_MutableSequence):
    """Per-round tree list that keeps fused-segment output STACKED.

    ``update_many`` produces k rounds of trees as one stacked pytree per
    segment; slicing each round out eagerly enqueues a tiny device gather
    per pytree field per round — hundreds of tiny dispatches over a
    200-round reference run, which is exactly the fixed per-op cost that
    made the diamonds wall clock lose to the CPU baseline (r3 verdict).
    The store records (segment, round) placeholders instead: a per-tree
    view materializes lazily on first access, and ``stacked_runs`` hands
    intact segments straight to the predict-time forest with ONE slice
    per run.
    """

    def __init__(self, items=()):
        self._items = list(items)

    # -- segment-aware entry points --------------------------------------
    def append_stacked(self, seg, n: int) -> None:
        self._items.extend(_SegView(seg, j) for j in range(n))

    def cap_set(self) -> set:
        """Distinct node-capacities across the forest, without
        materializing any per-tree view."""
        caps = set()
        for it in self._items:
            t = it.seg if isinstance(it, _SegView) else it
            caps.add(int(t.split_feature.shape[-1]))
        return caps

    def stacked_runs(self) -> list:
        """Pytrees with a leading tree axis that concatenate into the
        forest: contiguous rounds of one segment come out as a single
        slice of it; materialized singles get a length-1 axis."""
        runs, items, i = [], self._items, 0
        while i < len(items):
            it = items[i]
            if isinstance(it, _SegView):
                k = i + 1
                while (k < len(items) and isinstance(items[k], _SegView)
                       and items[k].seg is it.seg
                       and items[k].j == items[k - 1].j + 1):
                    k += 1
                j0, j1 = it.j, items[k - 1].j + 1
                runs.append(jax.tree.map(
                    lambda a, j0=j0, j1=j1: a[j0:j1], it.seg))
                i = k
            else:
                runs.append(jax.tree.map(lambda a: a[None], it))
                i += 1
        return runs

    # -- MutableSequence -------------------------------------------------
    def _mat(self, i: int):
        it = self._items[i]
        if isinstance(it, _SegView):
            it = jax.tree.map(lambda a, j=it.j: a[j], it.seg)
            self._items[i] = it
        return it

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self._mat(j)
                    for j in range(*i.indices(len(self._items)))]
        return self._mat(i)

    def __setitem__(self, i, v):
        self._items[i] = v

    def __delitem__(self, i):
        del self._items[i]

    def __len__(self):
        return len(self._items)

    def insert(self, i, v):
        self._items.insert(i, v)


class Booster:
    """LightGBM-compatible Booster driving the jitted TPU round step.

    Reference API surface exercised: construction via ``lgb.train`` with a
    Dataset (r/gridsearchCV.R:57), ``predict`` over all or first-k trees
    (r/gridsearchCV.R:63, bagging_boosting.ipynb:136).
    """

    _members = None     # the training table's EFB bundles (ops.members)

    def __init__(self, params: Optional[Union[Dict[str, Any], Params]] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None):
        if model_file is not None or model_str is not None:
            from ..utils.serialize import load_booster_into
            load_booster_into(self, model_file=model_file, model_str=model_str)
            return
        if isinstance(params, Params):
            self.params = params
        else:
            self.params = parse_params(params)
        self.train_set = train_set
        self.obj = create_objective(self.params)
        self.trees: List[Tree] = _TreeStore()
        self._forest_cache: Optional[Tree] = None
        self.best_iteration: int = -1
        self.best_score: Dict[str, Dict[str, float]] = {}
        self._valid: List[Tuple[str, Dataset, Any]] = []  # (name, dataset, pred)
        self._iter = 0
        self.init_score_ = 0.0
        self._pred_train = None
        self._bag = None
        self._key = jax.random.PRNGKey(self.params.seed)

        if train_set is not None:
            self._setup_training()

    # ------------------------------------------------------------------
    @property
    def _num_class(self) -> int:
        if self.params.objective in ("multiclass", "multiclassova"):
            return self.params.num_class
        return 1

    @profiling.span("lgbtpu.train.setup")
    def _setup_training(self) -> None:
        ds = self.train_set
        ds.construct()
        if ds.y is None:
            raise ValueError("training Dataset requires a label")
        p = self.params
        y_host = ds.get_label()
        w_host = (ds.get_weight() if ds.get_weight() is not None
                  else np.ones(ds.num_data_))
        if hasattr(self.obj, "prepare"):
            self.obj.prepare(y_host, w_host)
        if getattr(self.obj, "needs_group", False):
            gs = ds.get_group()
            if gs is None:
                raise ValueError(
                    f"objective '{self.obj.name}' requires query group "
                    "information: Dataset(X, label=y, group=sizes)")
            self.obj.set_group(gs, y_host, int(ds.row_mask.shape[0]))
        # a group objective's packed layout: operands of every program
        # that takes its gradients (None for the pointwise objectives)
        self._groups = getattr(self.obj, "groups", None)
        k = self._num_class
        if k > 1:  # every boosting mode (gbdt/goss/rf/dart) supports K>1
            self.init_score_ = np.asarray(
                self.obj.init_score(y_host, w_host), np.float32)  # [K]
            if ds.get_init_score() is not None:
                raise NotImplementedError(
                    "per-row init_score with multiclass is not supported")
            self._pred_train = jnp.broadcast_to(
                jnp.asarray(self.init_score_)[None, :],
                (int(ds.row_mask.shape[0]), k))
        elif ds.get_init_score() is not None:
            base = np.concatenate([
                np.asarray(ds.get_init_score(), np.float32),
                np.zeros(int(ds.row_mask.shape[0]) - ds.num_data_, np.float32)])
            self._pred_train = jnp.asarray(base)
            self.init_score_ = 0.0
        else:
            self.init_score_ = float(self.obj.init_score(y_host, w_host))
            self._pred_train = jnp.full(
                ds.row_mask.shape, self.init_score_, jnp.float32)
        self._bag = ds.row_mask
        self._hyper = HyperScalars.from_params(p)
        # predict-time shrinkage base: stored leaf values are normalized to
        # THIS rate, so reset_parameter learning-rate schedules stay exact
        # (round i's tree is rescaled by lr_i / base at append time)
        self._base_lr = float(p.learning_rate)
        self._obj_key = _objective_static_key(self.obj, p)
        self._num_bins = ds.num_bins
        # an EFB table's bundles as the grower and the walks over its codes
        # read them (ops.members): operands, so every order of the table's
        # columns runs one program; None for a table with no bundle.  Every
        # per-feature key below is over the original features
        self._members = members_of(ds.bin_mapper, self._num_bins)
        self._w_eff = ds.w  # 0 on padding rows already
        # the categorical scan's scalars key the programs; the columns
        # are their operand: one program for every order of the columns
        self._cat_key = categorical_key(ds.bin_mapper, p)
        self._cat_cols = CatColumns.of(ds.bin_mapper)
        self._mono_key = self._resolve_monotone_constraints()
        self._ic_key = self._resolve_interaction_constraints()
        # per-training-column used-bin counts bound the extra_trees draw
        # (code-review r2: a global [0, num_bins) draw starves
        # low-cardinality features of valid thresholds)
        if p.extra_trees:
            self._nbins_key = tuple(int(x) for x in ds.bin_mapper.n_bins)
        else:
            self._nbins_key = None
        # ... and per TRAINING column (a bundle's merged codes) the bins its
        # codes lie below, which size the fused kernels' one-hots: their
        # sorted heights are static (GrowSpec.onehot_rows), the columns in
        # that order an operand, so every order of the table's columns runs
        # one program; the streamed grower keeps num_bins
        self._col_bins = self._col_order = None
        if not getattr(ds, "is_streamed", False):
            from ..ops.histogram_pallas import onehot_heights, onehot_order

            bundler = ds.bin_mapper.bundler
            self._col_bins = tuple(int(b) for b in (
                ds.bin_mapper.n_bins if bundler is None else bundler.col_bins))
            heights = onehot_heights(self._col_bins, self._num_bins)
            if heights is not None:
                self._col_order = jnp.asarray(onehot_order(heights))
        self._grow_specs = {}
        self._streamed = bool(getattr(ds, "is_streamed", False))
        if self._streamed:
            self._check_streamed_scope()
        self._xraw = None
        self._linear_k = None
        if p.linear_tree:
            self._setup_linear_tree()
        # r20 gain-informed feature screening: the host-side EWMA
        # screener plans a compacted active set per round (None on
        # refresh rounds); a checkpoint restore that arrived before this
        # setup re-applies its stashed EWMA state here
        self._screener = None
        self._screen_bins_cache = None
        if p.feature_screen == "ema":
            self._check_screen_scope()
            from .feature_mask import FeatureScreener

            self._screener = FeatureScreener(
                self._num_features(), p.screen_keep_ratio,
                p.screen_ema_decay, p.screen_refresh_rounds)
            stash = getattr(self, "_screen_restore", None)
            if stash is not None:
                self._screener.restore(*stash)
                self._screen_restore = None
        self._dp_mesh = None
        self._fp_mesh = None
        if self._streamed:
            ds.block_store.prefetch_blocks = int(
                p.extra.get("stream_prefetch_blocks", 1))
            if p.tree_learner == "data":
                # r19: streamed × data-parallel — per-shard BlockStores
                # on the dp mesh with per-block-round merges
                self._maybe_setup_stream_dp()
            elif p.tree_learner != "serial":
                import warnings

                warnings.warn(
                    f"tree_learner='{p.tree_learner}' is not routed under "
                    "streamed (from_blocks) training — only 'data' "
                    "composes with the block loop (r19); falling back to "
                    "serial")
        elif self._members is not None and p.tree_learner != "serial":
            import warnings

            warnings.warn(
                f"tree_learner='{p.tree_learner}' does not take an "
                "EFB-bundled table (its splits route by bundle ranges the "
                "mesh learners do not carry); training serially — "
                "construct the Dataset with params={'enable_bundle': False} "
                "for the mesh learner", stacklevel=3)
        elif p.tree_learner == "feature":
            self._maybe_setup_fp()
        elif p.tree_learner in ("data", "voting"):
            self._maybe_setup_dp()

    def _num_features(self) -> int:
        """Original features: the space of every split, feature mask and
        per-feature key (the training columns are fewer where EFB bundled
        them)."""
        if self._members is not None:
            return self._members.num_features
        return int(self.train_set.num_feature_)

    def _grow_spec(self, eff_rows: int) -> GrowSpec:
        """The static decisions of this booster's trees grown on
        ``eff_rows`` rows (GOSS grows on its ``k_top + k_other`` sample),
        resolved once: the round builders key their program caches on the
        value, so a dispatch must not re-derive it.  ``_setup_training``
        and ``reset_parameter`` drop the memo."""
        spec = self._grow_specs.get(eff_rows)
        if spec is None:
            spec = self._grow_specs[eff_rows] = resolve_grow_spec(
                self.params, eff_rows, self._num_bins,
                cat_key=self._cat_key, mono_key=self._mono_key,
                nbins_key=self._nbins_key, ic_key=self._ic_key,
                col_bins=self._col_bins)
        return spec

    def _check_streamed_scope(self) -> None:
        """Out-of-core training covers the PLAIN numeric path (ISSUE 7):
        the per-block grower kernels replicate the fused strict/wave
        bodies without the categorical / monotone / extra-trees /
        interaction / bynode machinery, and multiclass & ranking need
        per-round state the streamed round functions don't carry.  Each
        fence raises :class:`~lightgbm_tpu.faults.StreamScopeError`
        naming the EXACT offending key (r19 satellite) rather than a
        generic message — train something subtly different, never."""
        from ..faults import StreamScopeError

        p = self.params
        bad = key = None
        if self._num_class > 1:
            bad, key = "multiclass objectives", "num_class"
        elif getattr(self.obj, "needs_group", False):
            bad, key = f"ranking objective '{self.obj.name}'", "objective"
        elif p.linear_tree:
            bad = key = "linear_tree"
        elif p.extra_trees:
            bad = key = "extra_trees"
        elif self._mono_key is not None:
            bad = key = "monotone_constraints"
        elif self._ic_key is not None:
            bad = key = "interaction_constraints"
        elif self._cat_key is not None:
            bad, key = "categorical features", "categorical_feature"
        elif p.feature_fraction_bynode < 1.0:
            bad, key = ("feature_fraction_bynode < 1",
                        "feature_fraction_bynode")
        elif p.boosting == "dart":
            bad, key = "boosting='dart'", "boosting"
        if bad is not None:
            raise StreamScopeError(
                f"streamed (from_blocks) training does not support {bad} "
                f"(unsupported key: {key})", key=key)

    def _check_screen_scope(self) -> None:
        """Feature screening covers the plain gbdt/rf/goss growers (the
        serial, streamed, and data-parallel row meshes).  Configs whose
        static per-column state is indexed by GLOBAL feature id —
        categorical sets, monotone signs, interaction groups, per-column
        bin counts (extra_trees), linear leaf designs, the
        feature-sharded learner, DART's per-round replay — would need a
        remap per structure to grow in compacted space; each fence
        raises :class:`~lightgbm_tpu.faults.ScreenScopeError` naming the
        exact offending key, mirroring ``_check_streamed_scope``."""
        from ..faults import ScreenScopeError

        p = self.params
        bad = key = None
        if self._num_class > 1:
            bad, key = "multiclass objectives", "num_class"
        elif getattr(self.obj, "needs_group", False):
            bad, key = f"ranking objective '{self.obj.name}'", "objective"
        elif p.linear_tree:
            bad = key = "linear_tree"
        elif p.boosting == "dart":
            bad, key = "boosting='dart'", "boosting"
        elif p.extra_trees:
            bad = key = "extra_trees"
        elif self._mono_key is not None:
            bad = key = "monotone_constraints"
        elif self._ic_key is not None:
            bad = key = "interaction_constraints"
        elif self._cat_key is not None:
            bad, key = "categorical features", "categorical_feature"
        elif p.tree_learner == "feature":
            bad, key = "tree_learner='feature'", "tree_learner"
        if bad is not None:
            raise ScreenScopeError(
                f"feature_screen='ema' does not support {bad} "
                f"(unsupported key: {key})", key=key)

    def _resolve_monotone_constraints(self) -> Optional[tuple]:
        """User ``monotone_constraints`` (per ORIGINAL feature, the space
        every split is in), validating LightGBM's rules: the list must
        cover every feature and categorical features cannot be constrained
        (a category set has no order to be monotone in).  A feature that
        shares an EFB bundle takes no constraint.

        Returns a static tuple for the jit-compile cache, or None when no
        constraint is active.
        """
        p = self.params
        mc = p.monotone_constraints
        if mc is None or not any(int(c) != 0 for c in mc):
            return None
        bm = self.train_set.bin_mapper
        if len(mc) != bm.num_features:
            raise ValueError(
                f"monotone_constraints has {len(mc)} entries for "
                f"{bm.num_features} features")
        for f, c in enumerate(mc):
            if c != 0 and bm.is_categorical[f]:
                raise ValueError(
                    f"monotone constraint on categorical feature {f} is "
                    "not supported (matching lightgbm)")
        for g in getattr(bm.bundler, "groups", ()):
            if len(g) > 1 and any(int(mc[f]) != 0 for f in g):
                raise ValueError(
                    "monotone constraint on an EFB-bundled feature "
                    f"(bundle members {g}); pass enable_bundle=False "
                    "when constraining sparse features")
        return tuple(int(c) for c in mc)

    @staticmethod
    def _raw_to_device(raw, n_pad: int):
        """Raw feature matrix -> padded f32 device array (linear_tree)."""
        from ..dataset import _to_2d_float_array

        X = _to_2d_float_array(raw).astype(np.float32)
        if X.shape[0] < n_pad:
            X = np.concatenate(
                [X, np.zeros((n_pad - X.shape[0], X.shape[1]), np.float32)])
        return jnp.asarray(X)

    def _setup_linear_tree(self) -> None:
        """Device-resident raw feature matrix for linear leaves (upstream
        ``linear_tree``): the ridge fit and linear prediction read RAW
        values, which the binned pipeline otherwise never ships to the
        device.  EFB must be off (a merged bundle column has no single raw
        value; upstream LightGBM likewise forbids linear trees with EFB).
        """
        ds = self.train_set
        p = self.params
        if ds.bin_mapper.bundler is not None:
            raise ValueError(
                "linear_tree with EFB bundling is not supported; construct "
                "the Dataset with params={'enable_bundle': False}")
        raw = ds.raw_data
        if raw is None or isinstance(raw, str):
            raise ValueError(
                "linear_tree needs the raw feature values: keep "
                "free_raw_data=False and build the Dataset from an "
                "in-memory matrix (not a saved binary)")
        self._xraw = self._raw_to_device(raw, int(ds.row_mask.shape[0]))
        self._linear_k = max(1, min(int(p.extra.get("linear_k", 8)),
                                    int(ds.num_feature_)))

    def _resolve_interaction_constraints(self) -> Optional[tuple]:
        """interaction_constraints (original-feature groups) -> static
        group-membership over the original features (the space every split
        is in).

        sklearn-HistGBDT convention: features in no listed group become
        singleton groups (they can still split, alone).  A listed feature
        may not share an EFB bundle."""
        p = self.params
        ic = p.interaction_constraints
        if not ic:
            return None
        bm = self.train_set.bin_mapper
        f_orig = bm.num_features
        groups = [set(g) for g in ic]
        listed = set().union(*groups) if groups else set()
        bad = sorted(f for f in listed if not (0 <= f < f_orig))
        if bad:
            raise ValueError(
                f"interaction_constraints reference feature indices {bad} "
                f"but the dataset has {f_orig} features")
        for g in getattr(bm.bundler, "groups", ()):
            if len(g) > 1 and any(f in listed for f in g):
                raise ValueError(
                    "interaction_constraints split an EFB bundle "
                    f"(members {list(g)}); pass "
                    "params={'enable_bundle': False} on the Dataset "
                    "when constraining sparse features")
        for f in sorted(set(range(f_orig)) - listed):
            groups.append({f})
        return tuple(tuple(1 if f in g else 0 for f in range(f_orig))
                     for g in groups)

    def _dp_merge_mode(self):
        """Resolve the row-sharded learners' histogram merge topology.

        Returns static ``(merge_mode, voting_k)`` for the dp step builders:
        ``tree_learner="data"`` routes to ``reduce_scatter_pipelined``
        since r10 (LightGBM's data-parallel Reduce-Scatter realized as a
        chunked ppermute ring — each shard receives its F/D feature
        slice in sub-chunks whose ring hops overlap the per-chunk split
        scans; 1/D the comm bytes AND the transfer hidden behind
        compute, serial-parity-exact trees) and ``"voting"`` to the
        PV-Tree voting merge (``top_k`` ballots, approximate) — distinct
        topologies since r9, not aliases of the full psum.
        ``params={'histogram_merge': ...}`` overrides the routing (e.g.
        ``"psum"`` to A/B the r0 baseline, ``"reduce_scatter"`` for the
        fused single-collective scatter, or ``"reduce_scatter_ring"``
        for the unchunked ring).  Voting needs a numeric-threshold
        ballot, so categorical datasets fall back to reduce-scatter with
        a warning.
        """
        import warnings

        p = self.params
        override = p.extra.get("histogram_merge")
        if override is not None:
            valid = ("psum", "reduce_scatter", "reduce_scatter_ring",
                     "reduce_scatter_pipelined", "voting")
            if override not in valid:
                raise ValueError(
                    f"histogram_merge must be one of {valid}, "
                    f"got {override!r}")
            mode = override
        elif p.tree_learner == "voting":
            mode = "voting"
        else:
            mode = "reduce_scatter_pipelined"
        if mode == "voting" and self._cat_key is not None:
            warnings.warn(
                "tree_learner='voting' does not support categorical "
                "features (the local ballot scans numeric thresholds "
                "only); using the reduce_scatter merge instead",
                stacklevel=3)
            mode = "reduce_scatter"
        return mode, int(p.top_k)

    def _dp_wire(self, merge_mode: str, eff_rows: int):
        """Resolve the ring merge's static ``(wire_dtype, merge_chunks)``.

        ``params={'histogram_wire': 'f32'|'bf16'|'int8'}`` compresses
        ring-hop messages (2x / 4x fewer wire bytes); ``merge_chunks``
        (default 4) sets the pipelined mode's sub-chunk count.  Non-f32
        wire needs explicit hop boundaries, so it rejects the fused
        ``psum`` / ``reduce_scatter`` collectives.

        int8 wire exactness gate: hop messages carry partial-sum COUNT
        columns, so the quantization step grows with the per-shard row
        count; past the r9 int8-accumulator bound (``2^31/127`` rows per
        shard, ``ops.histogram_pallas.INT8_ACC_ROW_LIMIT`` — the same
        exact-accumulation cliff ``check_int8_row_limit`` guards) the
        wire's documented tolerance can no longer be honored and the
        Booster falls back to f32 wire with a warning instead of
        training silently degraded.  Within the bound, int8 wire is
        approximate-by-contract (bench quality gate: AUC drift <= 1e-4),
        NOT parity-exact — only f32 wire keeps the bit-identity bar.
        """
        import warnings

        p = self.params
        wire = str(p.extra.get("histogram_wire", "f32"))
        from ..ops.histogram import WIRE_DTYPES

        if wire not in WIRE_DTYPES:
            raise ValueError(
                f"histogram_wire must be one of {WIRE_DTYPES}, "
                f"got {wire!r}")
        chunks = int(p.extra.get("merge_chunks", 4))
        if chunks < 1:
            raise ValueError(
                f"merge_chunks must be >= 1, got {chunks}")
        if wire == "f32":
            return wire, chunks
        if merge_mode not in ("reduce_scatter_ring",
                              "reduce_scatter_pipelined"):
            raise ValueError(
                f"histogram_wire={wire!r} compresses ring-hop messages "
                f"and needs histogram_merge='reduce_scatter_ring' or "
                f"'reduce_scatter_pipelined', not {merge_mode!r}")
        if wire == "int8":
            from ..ops.histogram_pallas import INT8_ACC_ROW_LIMIT

            mesh = getattr(self, "_dp_mesh", None)
            n_shards = (int(mesh.shape["data"]) if mesh is not None
                        else 1)
            per_shard = -(-int(eff_rows) // max(n_shards, 1))
            if per_shard > INT8_ACC_ROW_LIMIT:
                warnings.warn(
                    f"histogram_wire='int8' with {per_shard:,} rows per "
                    f"shard exceeds the exact-accumulation bound "
                    f"({INT8_ACC_ROW_LIMIT:,}); falling back to f32 "
                    "wire", stacklevel=3)
                return "f32", chunks
        return wire, chunks

    def _dp2_shape(self, n_dev: int, n_features: int):
        """Resolve the data learner's mesh topology: ``None`` for the 1-D
        row mesh or ``(rows, cols)`` for the 2-D rows x features mesh.

        ``params={'mesh_shape': ...}`` controls it: ``"auto"`` (default)
        promotes to ``(n_dev//2, 2)`` when ``n_dev >= 8`` and
        ``n_features >= 64`` — wide-enough data that halving each
        shard's histogram width beats the wider row slice — ``"1d"``
        forces the row mesh, and an explicit ``"RxC"`` (e.g. ``"4x2"``)
        pins the shape.  The 2-D step psum-merges over the data axis
        (``grow_tree`` rejects ring merges composed with a feature
        axis), so explicit ``histogram_merge`` / ``histogram_wire``
        overrides keep the 1-D topology, as do configurations the 2-D
        step does not trace (multiclass, goss, linear, constraints,
        categoricals, per-feature bins, per-node sampling).
        """
        p = self.params
        spec = str(p.extra.get("mesh_shape", "auto"))
        if spec == "1d":
            return None
        plain = (p.tree_learner == "data"
                 and p.boosting in ("gbdt", "rf")
                 and self._num_class == 1
                 and not p.linear_tree and not p.extra_trees
                 and self._mono_key is None and self._ic_key is None
                 and self._cat_key is None and self._nbins_key is None
                 and p.feature_fraction_bynode >= 1.0
                 and p.feature_screen == "off"  # screening compacts the
                 # column axis per round; the 2-D mesh pins a static
                 # column shard width — keep the 1-D row mesh instead
                 and p.extra.get("histogram_merge") is None
                 and p.extra.get("histogram_wire", "f32") == "f32")
        if spec == "auto":
            if plain and n_dev >= 8 and n_dev % 2 == 0 \
                    and n_features >= 64:
                return n_dev // 2, 2
            return None
        try:
            rows, cols = (int(t) for t in spec.lower().split("x"))
            if rows < 1 or cols < 1:
                raise ValueError
        except ValueError:
            raise ValueError(
                f"mesh_shape must be 'auto', '1d', or 'RxC' "
                f"(e.g. '4x2'), got {spec!r}") from None
        if cols == 1:
            return None
        if not plain:
            import warnings
            warnings.warn(
                f"mesh_shape={spec!r} needs the plain single-class "
                "gbdt/rf data learner with the default psum-over-rows "
                "merge; using the 1-D row mesh", stacklevel=4)
            return None
        if rows * cols != n_dev:
            raise ValueError(
                f"mesh_shape={spec!r} wants {rows * cols} devices but "
                f"the row-divisible device count is {n_dev}")
        return rows, cols

    def _maybe_setup_dp(self) -> None:
        """Shard the training arrays over the local device mesh when the
        user asks for a row-sharded parallel tree learner (LightGBM
        ``tree_learner=data`` / ``voting`` — SURVEY.md §2C / VERDICT r1
        item 6).  The histogram merge topology each learner uses is
        resolved separately by :meth:`_dp_merge_mode`.
        """
        import warnings

        p = self.params
        ranking = getattr(self.obj, "needs_group", False)
        if (p.boosting == "dart"
                or getattr(self.obj, "renew_alpha", None) is not None
                # linear leaves under the mesh since r5: plain
                # single-class gbdt (the ridge psum path,
                # parallel.make_dp_linear_train_step)
                or (p.linear_tree and (p.boosting != "gbdt"
                                       or self._num_class > 1 or ranking
                                       or self._mono_key is not None
                                       or self._ic_key is not None
                                       or self._cat_key is not None
                                       or p.extra_trees))
                or (ranking and (p.boosting != "gbdt"
                                 or self._mono_key is not None
                                 or self._ic_key is not None
                                 or self._cat_key is not None
                                 or p.extra_trees))):
            warnings.warn(
                f"tree_learner='{p.tree_learner}' currently supports "
                "gbdt/rf/goss boosting without leaf renewal "
                "(ranking: plain gbdt only; linear_tree: plain "
                "single-class gbdt); training serially",
                stacklevel=3)
            return
        n_pad = int(self.train_set.row_mask.shape[0])
        n_dev = len(jax.devices())
        while n_dev > 1 and n_pad % n_dev != 0:
            n_dev -= 1
        if n_dev <= 1:
            if len(jax.devices()) <= 1:
                warnings.warn(
                    f"tree_learner='{p.tree_learner}' requested but only one "
                    "device is visible; training serially", stacklevel=3)
            return
        from ..parallel.data_parallel import make_mesh, shard_rows

        shape2 = (None if ranking else self._dp2_shape(
            n_dev, int(self.train_set.X_binned.shape[1])))
        if shape2 is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..parallel.feature_parallel import (
                FEATURE_AXIS, make_mesh_2d, pad_features)

            rows, cols = shape2
            self._dp_mesh = make_mesh_2d(rows, cols)
            self._dp2 = True
            ds = self.train_set
            padded = pad_features(np.asarray(ds.X_binned), cols)
            self._dp2_width = padded.shape[1]
            self._dp_bins = jax.device_put(
                jnp.asarray(padded),
                NamedSharding(self._dp_mesh, P("data", FEATURE_AXIS)))
            (self._dp_y, self._dp_w, self._pred_train,
             self._bag) = shard_rows(
                self._dp_mesh, ds.y, self._w_eff, self._pred_train,
                self._bag)
            return
        self._dp_mesh = make_mesh(n_dev)
        ds = self.train_set
        if ranking:
            # LambdaRank lambdas need whole queries: the packed pairwise
            # pass runs REPLICATED (cheap next to histogram work) and only
            # the grower is sharded — see make_dp_grow_step.
            self._dp_stats_only = True
            self._dp_bins = shard_rows(self._dp_mesh, ds.X_binned)
            return
        (self._dp_bins, self._dp_y, self._dp_w, self._pred_train,
         self._bag) = shard_rows(
            self._dp_mesh, ds.X_binned, ds.y, self._w_eff,
            self._pred_train, self._bag)
        if self._xraw is not None:   # linear_tree under the mesh (r5)
            self._dp_xraw = shard_rows(self._dp_mesh, self._xraw)

    def _maybe_setup_fp(self) -> None:
        """Shard the FEATURE axis over the local mesh (LightGBM
        ``tree_learner=feature`` — per-shard histograms over a column
        slice, split exchange via all_gather; parallel.feature_parallel).
        Falls back to data-parallel-style serial training when the
        configuration needs capabilities the fp step does not trace."""
        import warnings

        p = self.params
        # (multiclass and categorical are fp-supported since r4: the class
        # axis vmaps inside the shard_map and the static is_cat mask
        # slices per shard — make_fp_train_step)
        if (p.boosting in ("goss", "dart")
                or p.linear_tree
                or getattr(self.obj, "needs_group", False)
                or getattr(self.obj, "renew_alpha", None) is not None
                or self._mono_key is not None or p.extra_trees
                or self._ic_key is not None
                or p.feature_fraction_bynode < 1.0):
            warnings.warn(
                "tree_learner='feature' currently supports gbdt/rf "
                "(single or multiclass, with categoricals) without "
                "monotone/interaction constraints, extra_trees, goss, "
                "dart, linear_tree, ranking, or per-node feature "
                "sampling (bynode would sample per SHARD and diverge "
                "from serial); training serially", stacklevel=3)
            return
        n_dev = len(jax.devices())
        if n_dev <= 1:
            warnings.warn(
                "tree_learner='feature' requested but only one device is "
                "visible; training serially", stacklevel=3)
            return
        from ..parallel.feature_parallel import (
            make_feature_mesh, pad_features, shard_features)

        ds = self.train_set
        codes = np.asarray(ds.X_binned)
        padded = pad_features(codes, n_dev)
        base_mask = np.zeros(padded.shape[1], np.float32)
        base_mask[: codes.shape[1]] = 1.0
        self._fp_mesh = make_feature_mesh(n_dev)
        self._fp_bins, _ = shard_features(
            self._fp_mesh, jnp.asarray(padded), jnp.asarray(base_mask))
        self._fp_width = padded.shape[1]

    def _maybe_setup_stream_dp(self) -> None:
        """Compose out-of-core streaming with the dp mesh (r19 tentpole):
        split the block store into per-shard stores over contiguous block
        ranges, pin each to its own device, and shard the O(n) resident
        vectors row-wise so every device streams + scores ONLY its own
        row range.  Falls back to serial streaming (with a warning) when
        the mesh cannot be used, mirroring ``_maybe_setup_dp``."""
        import warnings

        from ..faults import StreamScopeError

        p = self.params
        if getattr(self.obj, "renew_alpha", None) is not None:
            warnings.warn(
                "tree_learner='data' under streamed training supports "
                "gbdt/rf/goss without leaf renewal (the renewal pass "
                "needs an extra full stream per round); training with "
                "the serial block loop", stacklevel=3)
            return
        if p.extra.get("histogram_merge") == "voting":
            # voting is a grower-level ballot, not a histogram merge the
            # per-block-round collective can express
            raise StreamScopeError(
                "streamed (from_blocks) dp training does not support "
                "histogram_merge='voting' — the PV-Tree ballot needs "
                "in-memory per-shard split scans (unsupported key: "
                "histogram_merge)", key="histogram_merge")
        store = self.train_set.block_store
        n_dev = len(jax.devices())
        cap = int(p.extra.get("stream_dp_devices", 0))
        if cap > 0:
            n_dev = min(n_dev, cap)
        from ..data.stream_dp import (choose_stream_dp_devices,
                                      setup_stream_shards)

        n_dev = choose_stream_dp_devices(store.num_blocks, n_dev)
        if n_dev <= 1:
            if len(jax.devices()) <= 1:
                warnings.warn(
                    "tree_learner='data' requested but only one device "
                    "is visible; streaming serially", stacklevel=3)
            else:
                warnings.warn(
                    f"tree_learner='data' requested but {store.num_blocks}"
                    " block(s) admit no >1-device lockstep shard split; "
                    "streaming serially", stacklevel=3)
            return
        from ..parallel.data_parallel import make_mesh, shard_rows

        self._dp_mesh = make_mesh(n_dev)
        self._stream_dp = True
        self._stream_shards = setup_stream_shards(store, self._dp_mesh)
        ds = self.train_set
        (self._dp_y, self._dp_w, self._pred_train,
         self._bag) = shard_rows(
            self._dp_mesh, ds.y, self._w_eff, self._pred_train, self._bag)

    # -- continuation ----------------------------------------------------
    @property
    def _depth_cap(self) -> int:
        """Static traversal depth bound covering every tree in the forest.

        Equals ``num_leaves`` for a homogeneous forest; an ``init_model``
        continuation may carry deeper ingested trees, whose own capacity
        then sets the bound.
        """
        caps = (self.trees.cap_set() if isinstance(self.trees, _TreeStore)
                else {int(t.split_feature.shape[-1]) for t in self.trees})
        cap = max([2 * self.params.num_leaves - 1, *caps])
        return (cap + 1) // 2

    def ingest_init_model(self, prev: "Booster") -> None:
        """Continue training from ``prev``'s forest (lgb.train init_model).

        The stored leaf values are raw (shrinkage applied at predict time by
        the CURRENT learning_rate), so ingested trees are rescaled by
        ``prev_lr / cur_lr`` — the uniform shrink then reproduces each
        ingested tree's original contribution exactly.
        """
        p = self.params
        if p.boosting == "rf" or prev.params.boosting == "rf":
            raise NotImplementedError(
                "init_model continuation is not supported for rf boosting "
                "(averaged forests have no additive continuation)")
        if prev.num_model_per_iteration() != self._num_class:
            raise ValueError(
                "init_model has a different number of classes "
                f"({prev.num_model_per_iteration()} vs {self._num_class})")
        if not prev.trees:
            return
        # the ingested trees' split_bin codes only mean something under the
        # bin mapper they were trained with — require an identical binning
        # (pass reference= to reuse the original Dataset's bins)
        if not self._same_binning(self.train_set.bin_mapper,
                                  prev._bin_mapper_for_predict()):
            raise ValueError(
                "init_model was trained with different feature binning than "
                "this Dataset; rebuild the Dataset with "
                "reference=<original training Dataset> (or identical data) "
                "before continuing training")
        prev_linear = bool(prev.trees
                           and prev.trees[0].linear_feat is not None)
        if prev_linear != bool(p.linear_tree):
            raise ValueError(
                "init_model and the continuation must agree on linear_tree "
                f"(init_model linear={prev_linear}, params "
                f"linear_tree={p.linear_tree}) — a forest cannot mix "
                "constant and linear leaves")
        prev_lr = float(getattr(prev, "_base_lr",
                                prev.params.learning_rate))
        scale = jnp.float32(prev_lr / self._base_lr)
        self.trees = [t._replace(
            leaf_value=t.leaf_value * scale,
            linear_coef=(None if t.linear_coef is None
                         else t.linear_coef * scale))
            for t in prev.trees]
        self._iter = len(self.trees)
        self._forest_cache = None
        # restart from the PREVIOUS model's base score and replay its trees
        # into the train predictions so gradients continue where it left off
        self._rebase_and_replay(prev.init_score_)

    @staticmethod
    def _same_binning(cur_m, prev_m) -> bool:
        """Whether two bin mappers give every original feature the SAME
        bins (trees split on original features and their own bins, so EFB
        bundling, a training-time layout, may differ)."""
        return (len(cur_m.upper_bounds) == len(prev_m.upper_bounds) and all(
            len(a) == len(b) and np.allclose(a, b)
            for a, b in zip(cur_m.upper_bounds, prev_m.upper_bounds)))

    def _rebase_and_replay(self, init_score) -> None:
        """Rebuild ``_pred_train`` from ``init_score`` and replay the
        current forest into it, so continued-training gradients pick up
        exactly where the source model stopped (shared by init_model
        ingest and the ``Booster(model_file=...)`` + ``update()`` path)."""
        ds = self.train_set
        p = self.params
        self.init_score_ = init_score
        if self._num_class > 1:
            self._pred_train = jnp.broadcast_to(
                jnp.asarray(self.init_score_, jnp.float32)[None, :],
                (int(ds.row_mask.shape[0]), self._num_class))
        else:
            self._pred_train = jnp.full(
                ds.row_mask.shape, float(self.init_score_), jnp.float32)
            if ds.get_init_score() is not None:
                # dataset per-row offsets apply ON TOP of the ingested
                # model's scores (upstream GBDT::ResetTrainingData keeps both)
                base = np.concatenate([
                    np.asarray(ds.get_init_score(), np.float32),
                    np.zeros(int(ds.row_mask.shape[0]) - ds.num_data_,
                             np.float32)])
                self._pred_train = self._pred_train + jnp.asarray(base)
        shrink = jnp.float32(self._base_lr)
        if getattr(self, "_streamed", False):
            # no resident X_binned on a streamed Dataset: replay each
            # tree with one traversal pass over the block store, then
            # apply the SAME jitted update shape the live streamed
            # rounds use — under jit XLA:CPU contracts the mul+add into
            # an FMA; an eager update would round differently and every
            # continued round would see 1-ulp-different gradients
            from ..data.stream_grow import _block_pred_fn, _replay_add_fn
            pred_fn = _block_pred_fn()
            store = ds.block_store
            for tree in self.trees:
                deltas = [pred_fn(tree, bins_b)
                          for _, bins_b in store.device_blocks()]
                delta = (deltas[0] if len(deltas) == 1
                         else jnp.concatenate(deltas))
                self._pred_train = _replay_add_fn()(
                    self._pred_train, shrink, delta)
            return
        if p.linear_tree:
            add_lin = _linear_tree_pred_fn(self._depth_cap)
            for tree in self.trees:
                self._pred_train = add_lin(
                    self._pred_train, tree, ds.X_binned, self._xraw, shrink)
        else:
            add = _tree_pred_fn(self._depth_cap, self._num_class)
            for tree in self.trees:
                self._pred_train = add(self._pred_train, tree, ds.X_binned,
                                       shrink, self._members)

    def _attach_continuation(self, ds: Dataset) -> None:
        """Attach a training Dataset to a deserialized Booster so
        ``update()`` continues the saved model (r13 satellite).

        Validates that the Dataset was binned identically to the saved
        model (targeted error otherwise), runs the normal training setup,
        then replays the loaded forest into the train predictions.  For
        deterministic configs the continued rounds are bit-identical to
        an uninterrupted run; mid-``bagging_freq`` bag state is NOT in
        the model file — resume from a training checkpoint
        (``lightgbm_tpu.training``) when that matters.
        """
        ds.construct()
        prev_m = self._bin_mapper_for_predict()
        if prev_m is not None and not self._same_binning(
                ds.bin_mapper, prev_m):
            raise ValueError(
                "this Booster was saved under a different feature binning "
                "than the offered Dataset (bin bounds differ); rebuild the "
                "Dataset with reference=<original training Dataset> (or "
                "identical data) before continuing training")
        loaded_init = self.init_score_
        loaded_iter = self._iter
        self.train_set = ds
        self._setup_training()
        if getattr(self, "_streamed", False) and prev_m is not None:
            # streamed continuation (r15): the split_bin codes in the
            # loaded forest only mean something under the binning they
            # were trained with — enforce via the checkpoint-grade
            # schema digest (covers bounds, nan bin, bin counts,
            # categorical flags, EFB bundling), same contract as
            # training.checkpoint.resume_booster
            from ..data.sketch import schema_digest
            got = schema_digest(ds.bin_mapper)
            want = schema_digest(prev_m)
            if got != want:
                raise ValueError(
                    "this Booster was saved under a different binning "
                    f"schema (digest {want[:12]}… vs the streamed "
                    f"Dataset's {got[:12]}…); rebuild the blocks with "
                    "Dataset.from_blocks(..., reference=<original "
                    "training Dataset>) before continuing training")
        self._iter = loaded_iter
        self._forest_cache = None
        self._rebase_and_replay(loaded_init)

    def _screen_finite(self, i: int) -> None:
        """Gradient/hessian finiteness screen (r13 streaming hardening):
        one non-finite raw prediction makes every objective's g/h
        non-finite and the round would grow a garbage tree out of NaN
        stats that silently poisons the rest of the run.  Costs one
        scalar host sync — the streamed block loop it guards is a host
        loop already.  Disable with ``finite_screen=false``."""
        from ..faults import NonFiniteGradientError

        if not bool(jnp.all(jnp.isfinite(self._pred_train))):
            raise NonFiniteGradientError(
                f"non-finite raw predictions entering round {i}: the "
                "gradient/hessian stats would be non-finite and the grown "
                "tree garbage — inspect labels/objective, or resume from "
                "the last good checkpoint (lightgbm_tpu.training)",
                round_index=i)

    # -- checkpoint state (r13) ------------------------------------------
    def checkpoint_state(self) -> tuple:
        """Complete training state as ``(arrays, meta)`` host payloads.

        Everything a bit-identical resume needs beyond the params:
        the forest (raw f32 buffers — NOT the decimal JSON codec), the
        train predictions and current bagging mask exactly as the next
        round would consume them, the base PRNG key, round counters, and
        the shrinkage base.  All other per-round randomness (bagging /
        feature-fraction / GOSS keys) is re-derived from params + round
        index by ``_sample_bag_and_fmask`` and the round functions, so
        no raw RNG stream state beyond the base key exists.  Sharded
        arrays gather to host here; resume re-shards lazily exactly like
        a fresh run does.
        """
        if self.train_set is None or self._pred_train is None:
            raise ValueError(
                "checkpoint_state() needs an attached training Dataset — "
                "this booster holds no round state")
        import dataclasses

        from ..data.sketch import schema_digest
        from .tree import tree_to_arrays

        p = self.params
        params_dict = dataclasses.asdict(p)
        extra = dict(params_dict.pop("extra", None) or {})
        params_dict.update(extra)
        arrays = {
            "pred_train": np.asarray(self._pred_train),
            "bag": np.asarray(self._bag),
            "key": np.asarray(self._key),
        }
        init_meta = None
        if isinstance(self.init_score_, np.ndarray):
            arrays["init_score"] = np.asarray(self.init_score_, np.float32)
        else:
            init_meta = float(self.init_score_)
        trees = list(self.trees)   # materializes stacked-segment views
        for t_idx, t in enumerate(trees):
            for fname, arr in tree_to_arrays(t).items():
                arrays[f"tree{t_idx:05d}/{fname}"] = arr
        parallel = {"tree_learner": p.tree_learner}
        if getattr(self, "_dp_mesh", None) is not None:
            parallel["n_devices"] = int(self._dp_mesh.devices.size)
            if getattr(self, "_dp2", False):
                parallel["mesh"] = "dp2"
            else:
                merge_mode, voting_k = self._dp_merge_mode()
                parallel["merge_mode"] = merge_mode
                parallel["voting_k"] = int(voting_k)
        elif getattr(self, "_fp_mesh", None) is not None:
            parallel["n_devices"] = int(self._fp_mesh.devices.size)
        meta = {
            "params": params_dict,
            "iter": int(self._iter),
            "num_trees": len(trees),
            "base_lr": float(self._base_lr),
            "init_score": init_meta,
            "best_iteration": int(self.best_iteration),
            "streamed": bool(getattr(self, "_streamed", False)),
            "parallel": parallel,
            "schema_digest": schema_digest(self.train_set.bin_mapper),
        }
        if getattr(self, "_screener", None) is not None:
            # r20: the EWMA vector + refresh counter ARE the screener's
            # whole state — with them restored, plan() reproduces the
            # identical active set every remaining round
            ema, rounds_since = self._screener.state()
            arrays["screen_ema"] = ema
            meta["screen_rounds_since_refresh"] = rounds_since
        return arrays, meta

    def restore_checkpoint_state(self, arrays, meta) -> None:
        """Inverse of :meth:`checkpoint_state` onto a booster already
        constructed with the SAME params and an equivalently-binned
        training Dataset (``training.checkpoint.resume_booster`` wraps
        the construction + schema validation)."""
        from .tree import tree_from_arrays

        trees = []
        for t_idx in range(int(meta["num_trees"])):
            prefix = f"tree{t_idx:05d}/"
            fields = {k[len(prefix):]: v for k, v in arrays.items()
                      if k.startswith(prefix)}
            trees.append(tree_from_arrays(fields))
        self.trees = _TreeStore(trees)
        self._forest_cache = None
        self._iter = int(meta["iter"])
        self._base_lr = float(meta["base_lr"])
        self.best_iteration = int(meta["best_iteration"])
        self.init_score_ = (
            float(meta["init_score"]) if meta.get("init_score") is not None
            else np.asarray(arrays["init_score"], np.float32))
        self._pred_train = jnp.asarray(arrays["pred_train"])
        self._bag = jnp.asarray(arrays["bag"])
        self._key = jnp.asarray(arrays["key"])
        if "screen_ema" in arrays:
            state = (np.asarray(arrays["screen_ema"], np.float32),
                     int(meta.get("screen_rounds_since_refresh", 0)))
            if getattr(self, "_screener", None) is not None:
                self._screener.restore(*state)
            else:
                # restore arrived before _setup_training (continuation
                # flows attach the Dataset later) — stash for it
                self._screen_restore = state
        if getattr(self, "_dp_mesh", None) is not None and \
                not getattr(self, "_dp_stats_only", False):
            # elastic resume (r19): the checkpoint gathered these to host
            # under the WRITER's device count; re-shard onto THIS run's
            # row mesh — values are unchanged, only placement moves, so a
            # D=8 checkpoint resumes bit-identically at D=4 (and back)
            from ..parallel.data_parallel import shard_rows

            self._pred_train, self._bag = shard_rows(
                self._dp_mesh, self._pred_train, self._bag)

    def _screen_view(self, bins, active_ids):
        """Compacted ``[N, F_active]`` gather of the binned matrix for a
        screened round, cached on (matrix identity, active-id bytes) so
        consecutive rounds with an unchanged active set reuse the device
        gather instead of re-materializing it."""
        ck = active_ids.tobytes()
        c = self._screen_bins_cache
        if c is not None and c[0] is bins and c[1] == ck:
            return c[2]
        out = jnp.take(bins, jnp.asarray(active_ids, jnp.int32), axis=1)
        self._screen_bins_cache = (bins, ck, out)
        return out

    def _sample_bag_and_fmask(self, i: int, screen_ids=None):
        """Per-round stochasticity shared by plain and DART rounds: resample
        the bagging mask on schedule (updating ``self._bag``, kept
        mesh-sharded under DP) and return this round's feature mask.  RNG
        streams are keyed by round index so any round path reproduces the
        same draws.  ``screen_ids`` (r20) threads the screener's active
        set in as the BASE mask, so ``feature_fraction`` samples within
        it — composition through the one mask layer, never a second
        masking pass."""
        ds = self.train_set
        p = self.params
        if p.bagging_freq > 0 and p.bagging_fraction < 1.0 and \
                i % p.bagging_freq == 0:
            bkey = jax.random.fold_in(
                jax.random.PRNGKey(p.bagging_seed + p.seed), i)
            self._bag = _bag_fn()(
                bkey, ds.row_mask, jnp.float32(p.bagging_fraction),
                jnp.float32(ds.num_data_))
            if getattr(self, "_dp_mesh", None) is not None:
                # keep the bag mesh-sharded: sampling ran on the default
                # device, and leaving it there would reshard every round
                from ..parallel.data_parallel import shard_rows
                self._bag = shard_rows(self._dp_mesh, self._bag)
        n_cols = self._num_features()
        base = None
        if screen_ids is not None:
            bm = np.zeros(n_cols, np.float32)
            bm[screen_ids] = 1.0
            base = jnp.asarray(bm)
        if p.feature_fraction < 1.0:
            fkey = jax.random.fold_in(
                jax.random.PRNGKey(p.feature_fraction_seed + p.seed), i)
            if base is not None:
                return _feature_mask_fn(n_cols, True)(
                    fkey, jnp.float32(p.feature_fraction), base)
            return _feature_mask_fn(n_cols)(
                fkey, jnp.float32(p.feature_fraction))
        return base if base is not None else jnp.ones(n_cols, jnp.float32)

    # -- round step ------------------------------------------------------
    @profiling.span("lgbtpu.train.update")
    def update(self, train_set: Optional[Dataset] = None, fobj=None) -> bool:
        """Run one boosting round (LightGBM Booster.update)."""
        if train_set is not None and train_set is not self.train_set:
            if self.train_set is None and len(self.trees) > 0:
                # a Booster(model_file=...) continuing training: attach
                # the dataset AND replay the loaded forest into the train
                # predictions so the gradients continue where the saved
                # run left off (r13 satellite — _setup_training alone
                # resets predictions to the init score and the next round
                # would re-learn the forest's contribution)
                self._attach_continuation(train_set)
            else:
                self.train_set = train_set
                self._setup_training()
        if self.params.boosting == "dart":
            return self._dart_round()
        ds = self.train_set
        p = self.params
        i = self._iter

        screener = getattr(self, "_screener", None)
        active_ids = None
        if screener is not None:
            active_ids, _ = screener.plan()   # None on refresh rounds
        fmask = self._sample_bag_and_fmask(i, screen_ids=active_ids)
        if self._members is not None:
            # an EFB table's columns are not the screener's features: its
            # active set masks the scan (the base mask above), and the
            # kernels read every column
            active_ids = None
        if active_ids is not None:
            # screened round: compact the mask to [F_active] — bins and
            # comms compact below per branch; exactly two program shapes
            # per config (full F on refresh rounds, F_active otherwise)
            fmask = jnp.take(fmask, jnp.asarray(active_ids, jnp.int32))

        goss_k = None
        eff_rows = int(ds.row_mask.shape[0])
        if p.boosting == "goss":
            goss_k = (int(p.top_rate * ds.num_data_),
                      int(p.other_rate * ds.num_data_))
            if self._num_class == 1:  # mc uses the masked (non-compacted) path
                eff_rows = goss_k[0] + goss_k[1]
        _dp_m = getattr(self, "_dp_mesh", None)
        check_int8_row_limit(
            p, eff_rows,
            int(_dp_m.shape["data"]) if _dp_m is not None else 1)
        spec = self._grow_spec(eff_rows)
        round_key = jax.random.fold_in(self._key, i)
        if getattr(self, "_streamed", False):
            from ..data.stream_grow import (stream_goss_round,
                                            stream_plain_round)

            if p.extra.get("finite_screen", True):
                self._screen_finite(i)

            renew_alpha = getattr(self.obj, "renew_alpha", None)
            renew_scale = getattr(self.obj, "renew_scale", None)
            store = ds.block_store
            if active_ids is not None:
                # screened round out-of-core: only the active columns
                # cross PCIe (the screener doubling as the hot-feature
                # prior for GOSS-at-the-source row gathers)
                from ..data.block_store import ColumnViewStore

                store = ColumnViewStore(store, active_ids)
            if getattr(self, "_stream_dp", False):
                # r19: streamed × dp — per-shard stores, per-block-round
                # merges; GOSS samples per shard at the source
                from ..data.stream_dp import (drain_shard_odometers,
                                              stream_dp_goss_round,
                                              stream_dp_plain_round)

                merge_mode, _ = self._dp_merge_mode()
                wire_dtype, merge_chunks = self._dp_wire(
                    merge_mode, eff_rows)
                shards = self._stream_shards
                if active_ids is not None:
                    from ..data.block_store import ColumnViewStore

                    shards = [ColumnViewStore(sh, active_ids)
                              for sh in shards]
                if goss_k is not None:
                    n_sh = len(self._stream_shards)
                    goss_k_shard = (max(goss_k[0] // n_sh, 1),
                                    max(goss_k[1] // n_sh, 1))
                    tree, new_pred = stream_dp_goss_round(
                        shards, self._dp_mesh,
                        self._obj_key, self._dp_y, self._dp_w,
                        self._bag, self._pred_train, fmask, self._hyper,
                        round_key, goss_k_shard, float(p.top_rate),
                        float(p.other_rate), p.seed * 1_000_003 + i,
                        spec, merge_mode, wire_dtype, merge_chunks)
                else:
                    tree, new_pred = stream_dp_plain_round(
                        shards, self._dp_mesh,
                        self._obj_key, self._dp_y, self._dp_w,
                        self._bag, self._pred_train, fmask, self._hyper,
                        spec, p.boosting == "rf", merge_mode, wire_dtype,
                        merge_chunks)
                drain_shard_odometers(ds.block_store,
                                      self._stream_shards)
            elif goss_k is not None:
                tree, new_pred = stream_goss_round(
                    store, self._obj_key, ds.y, self._w_eff,
                    self._bag, self._pred_train, fmask, self._hyper,
                    round_key, goss_k, float(p.top_rate),
                    float(p.other_rate), p.seed * 1_000_003 + i,
                    spec, renew_alpha, renew_scale)
            else:
                tree, new_pred = stream_plain_round(
                    store, self._obj_key, ds.y, self._w_eff,
                    self._bag, self._pred_train, fmask, self._hyper,
                    spec, p.boosting == "rf", renew_alpha, renew_scale)
        elif getattr(self, "_fp_mesh", None) is not None:
            from ..parallel.feature_parallel import make_fp_train_step

            fn = make_fp_train_step(self._fp_mesh, self._obj_key, spec,
                                    p.boosting == "rf", self._num_class)
            from .feature_mask import pad_feature_mask

            fmask_p = pad_feature_mask(fmask, self._fp_width)
            tree, new_pred = fn(self._fp_bins, ds.y, self._w_eff, self._bag,
                                self._pred_train, fmask_p, self._hyper,
                                round_key, cats=self._cat_cols)
        elif getattr(self, "_dp2", False):
            # 2-D rows x features mesh (r10 default at D>=8, F>=64):
            # per-block histograms psum over rows, split exchange over
            # columns — see parallel.feature_parallel.make_dp_fp_train_step
            from ..parallel.feature_parallel import make_dp_fp_train_step

            fn = make_dp_fp_train_step(self._dp_mesh, self._obj_key, spec,
                                       p.boosting == "rf")
            from .feature_mask import pad_feature_mask

            fmask_p = pad_feature_mask(fmask, self._dp2_width)
            tree, new_pred = fn(self._dp_bins, self._dp_y, self._dp_w,
                                self._bag, self._pred_train, fmask_p,
                                self._hyper, round_key, cats=self._cat_cols)
        elif getattr(self, "_dp_mesh", None) is not None and \
                getattr(self, "_dp_stats_only", False):
            from ..parallel.data_parallel import (make_dp_grow_step,
                                                  shard_rows)

            grad_fn, grad_args = self._group_grad_call()
            g, h = grad_fn(*grad_args)
            bag = self._bag
            stats = jnp.stack(
                [g * bag, h * bag, (bag > 0).astype(jnp.float32)], axis=-1)
            stats = shard_rows(self._dp_mesh, stats)
            merge_mode, voting_k = self._dp_merge_mode()
            wire_dtype, merge_chunks = self._dp_wire(merge_mode, eff_rows)
            fn = make_dp_grow_step(self._dp_mesh, spec, merge_mode,
                                   voting_k, wire_dtype, merge_chunks)
            dp_bins = (self._dp_bins if active_ids is None
                       else self._screen_view(self._dp_bins, active_ids))
            tree, row_leaf = fn(dp_bins, stats, fmask, self._hyper,
                                round_key, cats=self._cat_cols)
            new_pred = self._pred_train + jnp.float32(p.learning_rate) \
                * lookup_values(row_leaf, tree.leaf_value)
        elif getattr(self, "_dp_mesh", None) is not None and \
                self._linear_k is not None:
            from ..parallel.data_parallel import make_dp_linear_train_step

            merge_mode, voting_k = self._dp_merge_mode()
            wire_dtype, merge_chunks = self._dp_wire(merge_mode, eff_rows)
            fn = make_dp_linear_train_step(
                self._dp_mesh, self._obj_key, spec, self._linear_k,
                merge_mode, voting_k, wire_dtype, merge_chunks)
            tree, new_pred = fn(self._dp_bins, self._dp_y, self._dp_w,
                                self._bag, self._pred_train, self._dp_xraw,
                                fmask, self._hyper, round_key,
                                cats=self._cat_cols)
        elif getattr(self, "_dp_mesh", None) is not None:
            from ..parallel.data_parallel import make_dp_train_step

            goss_k_shard = None
            if goss_k is not None:
                # per-shard compaction (upstream's data-parallel GOSS
                # samples per machine); multiclass GOSS re-weights without
                # compacting, so its static sizing keeps the full rows
                n_dev = self._dp_mesh.devices.size
                goss_k_shard = (max(goss_k[0] // n_dev, 1),
                                max(goss_k[1] // n_dev, 1))
                if self._num_class == 1:   # each shard grows on its sample
                    eff_rows = sum(goss_k_shard)
                    spec = self._grow_spec(eff_rows)
            merge_mode, voting_k = self._dp_merge_mode()
            wire_dtype, merge_chunks = self._dp_wire(merge_mode, eff_rows)
            fn = make_dp_train_step(
                self._dp_mesh, self._obj_key, spec,
                p.boosting == "rf", goss_k_shard, self._num_class,
                merge_mode, voting_k, wire_dtype, merge_chunks)
            dp_bins = (self._dp_bins if active_ids is None
                       else self._screen_view(self._dp_bins, active_ids))
            tree, new_pred = fn(dp_bins, self._dp_y, self._dp_w,
                                self._bag, self._pred_train, fmask,
                                self._hyper, round_key, cats=self._cat_cols)
        else:
            fn = _round_fn(self._obj_key, spec, p.boosting == "rf",
                           self._num_class, goss_k, self._linear_k)
            if self._linear_k is not None:
                tree, new_pred = fn(ds.X_binned, ds.y, self._w_eff,
                                    self._bag, self._pred_train, fmask,
                                    self._hyper, round_key, self._xraw,
                                    self._groups, self._cat_cols)
            else:
                bins = (ds.X_binned if active_ids is None
                        else self._screen_view(ds.X_binned, active_ids))
                tree, new_pred = fn(bins, ds.y, self._w_eff,
                                    self._bag, self._pred_train, fmask,
                                    self._hyper, round_key, self._groups,
                                    self._members, self._col_order,
                                    self._cat_cols)
        if active_ids is not None:
            # the tree grew in compacted space — gather the winner ids
            # back to GLOBAL features before anything downstream
            # (predict, valid eval, checkpoints, the screener) sees it
            from .feature_mask import remap_split_features

            tree = remap_split_features(tree, active_ids)
        if screener is not None:
            # refresh rounds observe too — that is exactly how a feature
            # whose gain appears late re-enters the active set
            screener.observe(np.asarray(tree.split_feature),
                             np.asarray(tree.split_gain))
        if p.boosting != "rf":
            self._pred_train = new_pred
        if p.boosting != "rf" and p.learning_rate != self._base_lr:
            # reset_parameter schedule: bake lr_i/base into stored values so
            # the uniform predict-time shrink (base) reproduces lr_i exactly
            scale = jnp.float32(p.learning_rate / self._base_lr)
            tree = tree._replace(
                leaf_value=tree.leaf_value * scale,
                linear_coef=(None if tree.linear_coef is None
                             else tree.linear_coef * scale))
        self.trees.append(tree)
        self._forest_cache = None
        # incremental valid-set predictions
        shrink = 1.0 if p.boosting == "rf" else self._base_lr
        if self._linear_k is not None:
            add_lin = _linear_tree_pred_fn(self._depth_cap)
            for idx, (name, vds, vpred) in enumerate(self._valid):
                self._valid[idx] = (
                    name, vds, add_lin(vpred, tree, vds.X_binned,
                                       vds._xraw_dev, jnp.float32(shrink)))
        else:
            add_tree = _tree_pred_fn(p.num_leaves, self._num_class)
            for idx, (name, vds, vpred) in enumerate(self._valid):
                self._valid[idx] = (
                    name, vds, add_tree(vpred, tree, vds.X_binned,
                                        jnp.float32(shrink), self._members))
        self._iter += 1
        return False

    def can_fuse_rounds(self) -> bool:
        """Whether update_many can run rounds as one scanned device program
        (matching the host loop's RNG streams exactly)."""
        p = self.params
        return (self._num_class == 1
                and getattr(self, "_dp_mesh", None) is None
                and getattr(self, "_fp_mesh", None) is None
                and not getattr(self, "_streamed", False)
                and p.boosting in ("gbdt", "rf", "goss")
                and not p.linear_tree
                and p.feature_screen == "off"  # screener plans per round
                and not self._valid)

    def update_many(self, k: int) -> None:
        """Run ``k`` boosting rounds fused into scanned device programs.

        Falls back to per-round update() when the configuration needs
        host-side work between rounds (valid-set eval, multiclass,
        DP/FP mesh, DART's dropout bookkeeping).  Segments of at most
        ``fused_segment_rounds`` (default 25) bound per-dispatch runtime —
        one very long device execution can trip the TPU runtime watchdog —
        and keep the compile cache small (one program per segment length).
        """
        if k <= 0:
            return
        with profiling.span("lgbtpu.train.update_many", rounds=k):
            if not self.can_fuse_rounds():
                for _ in range(k):
                    self.update()
                return
            ds = self.train_set
            p = self.params
            # default segment length scales inversely with row count so
            # one dispatch stays a few device-seconds at most; big data
            # pays per-dispatch overhead rarely anyway — compute dominates
            # there.  TINY shapes (rows x features <= 2^20 cells — the
            # diamonds regime) fuse up to 200 rounds into ONE dispatch:
            # device time stays well under a second, and per-dispatch
            # round trips are the entire wall-clock story there
            n_pad = int(ds.row_mask.shape[0])
            cells = n_pad * max(int(ds.X_binned.shape[1]), 1)
            if cells <= (1 << 20):
                seg_default = max(1, min(200, (1 << 24) // max(n_pad, 1)))
            else:
                seg_default = max(1, min(25, (1 << 22) // max(n_pad, 1)))
            seg = max(1, int(p.extra.get("fused_segment_rounds",
                                         seg_default)))
            while k > 0:
                n_rounds = min(k, seg)
                # the three phases of a dispatch, as the host sees them: the
                # program lookup and its operands, the call (JAX's build
                # seconds land here when it compiles), the bookkeeping
                with profiling.span("lgbtpu.train.segment"):
                    fn, args = self._fused_segment(n_rounds)
                with profiling.span("lgbtpu.train.dispatch"):
                    pred, bag, trees, passes = fn(*args)
                with profiling.span("lgbtpu.train.commit"):
                    # kept unread: the pass log is fetched when asked for
                    profiling.defer("train.passes", passes)
                    self._pred_train = pred
                    self._bag = bag
                    if not isinstance(self.trees, _TreeStore):
                        # e.g. a loaded model's plain list
                        self.trees = _TreeStore(self.trees)
                    self.trees.append_stacked(trees, n_rounds)
                    self._iter += n_rounds
                    self._forest_cache = None
                k -= n_rounds

    def _root_planes(self):
        """The root's histogram planes ``[3, C, B]`` over the training
        columns at the booster's current scores (the probes' node)."""
        from ..ops.histogram import compute_histograms

        ds = self.train_set
        g, h = _grad_hess(self.obj, self._pred_train, ds.y, self._w_eff,
                          self._groups)
        bag = self._bag
        stats = jnp.stack([g * bag, h * bag, (bag > 0).astype(jnp.float32)],
                          axis=-1)
        hist = compute_histograms(ds.X_binned, stats,
                                  jnp.zeros(stats.shape[0], jnp.int32), 1,
                                  self._num_bins)[0]             # [C, B, 3]
        return jnp.moveaxis(hist, -1, 0)

    def _member_scan_call(self):
        """``(fn, args)``: an EFB table's member view and split scan ALONE
        (``ops.members.member_view`` + ``ops.split.find_best_split``, as the
        grower runs them on a node) on a real node histogram: the root's,
        at the booster's current scores, taken once here; the sparse
        cell's probe times the pair.  ``None`` for a table with no
        bundle."""
        if self._members is None:
            return None
        return _member_scan_fn(), (
            self._root_planes(), self._members, self._hyper.ctx(),
            jnp.ones(self._num_features(), jnp.float32))

    def _cat_scan_call(self):
        """``(fn, args)``: the categorical split scan ALONE
        (``ops.split.find_best_split``'s category-set search, as the
        grower runs it on a node) over the categorical columns of a real
        node histogram, the root's at the booster's current scores, taken
        once here; the categorical cell's probe times the pair.  ``None``
        for a table with no categorical column, or one whose columns are
        bundled."""
        if self._cat_key is None or self._members is not None:
            return None
        idx = np.flatnonzero(self.train_set.bin_mapper.is_categorical)
        cats = CatColumns(is_cat=jnp.ones(len(idx), bool),
                          cat_bins=self._cat_cols.cat_bins[idx])
        return _cat_scan_fn(self._cat_key), (
            self._root_planes()[:, idx], self._hyper.ctx(), cats)

    def _group_grad_call(self):
        """``(fn, args)``: a group objective's jitted lambda pass ALONE and
        its operands at the booster's current scores (the replicated pass
        of the data-parallel learner; the ranking cell's probe times the
        same pair); ``None`` for an objective without groups."""
        if self._groups is None:
            return None
        return _group_grad_fn(self._obj_key), (
            self._pred_train, self.train_set.y, self._w_eff, self._groups)

    def _fused_segment(self, n_rounds: int):
        """``(fn, args)``: the jitted ``n_rounds``-round program and its
        operands at the booster's current state — what ``update_many``
        dispatches per segment (``chip_smoke.py`` lowers the same pair to
        look for the kernels in the compiled round)."""
        ds = self.train_set
        p = self.params
        use_bagging = p.bagging_freq > 0 and p.bagging_fraction < 1.0
        eff_rows = int(ds.row_mask.shape[0])
        goss_k = None
        if p.boosting == "goss":
            goss_k = (int(p.top_rate * ds.num_data_),
                      int(p.other_rate * ds.num_data_))
            eff_rows = goss_k[0] + goss_k[1]
        spec = self._grow_spec(eff_rows)
        wave, hist_dtype = spec.wave, spec.hist_dtype
        # what the pass that runs was decided to be: the shapes a roofline
        # counts its work from (the benchmark's named metrics read them);
        # overgrow_leaves is the exact tail's cap, not what each tree grows to
        segments = wave_extent(wave, p.num_leaves)[1]
        features = int(ds.X_binned.shape[1])
        # ... and what the kernels' VMEM blocking made of a wave pass at
        # this width: feature blocks, the feature rows they cover (the
        # last block is padded) and the rows their feature loops run over
        # (the padding skipped: histogram_pallas._feature_loop), rows per
        # grid step, and kernel calls per pass (two under the hi/lo split
        # that serves "f32")
        from ..ops.histogram_pallas import _vmem_blocking, feature_layout
        f_blk, n_fblk, _, chunk = _vmem_blocking(features, self._num_bins,
                                                 3 * segments)
        # the grower's FeatureLayout: the looped rows' one-hot heights
        layout = feature_layout(features, f_blk, self._num_bins,
                                spec.onehot_rows)
        for fact, value in (
                ("wave_width", segments),
                # the schedule's narrow phase (0 = none); it runs where the
                # grower's partition-fused kernel does, and the trace's
                # lgbtpu_hist_narrow events say how often
                ("wave_narrow_width",
                 wave.narrow_width if wave.narrow_width < segments else 0),
                ("wave_tail", wave.tail),
                ("overgrow_leaves", wave.cap_leaves),
                ("hist_dtype", hist_dtype), ("rows_padded", eff_rows),
                ("num_bins", self._num_bins),
                ("features", features),
                ("features_raw", self._num_features()),
                ("feature_blocks", n_fblk),
                ("features_padded", n_fblk * f_blk),
                ("feature_rows_looped", layout.rows_looped),
                # the one-hot rows the fused kernels build over those the
                # table's num_bins would (1.0: every column that tall)
                ("onehot_bin_share", layout.onehot_rows
                 / (layout.rows_looped * self._num_bins)),
                ("chunk_rows", chunk),
                ("hist_calls_per_pass",
                 2 if hist_dtype == "f32" or (
                     hist_dtype == "f32x"
                     and spec.hist_impl == "pallas") else 1)):
            profiling.note("train." + fact, value)
        # a group objective's layout: queries, block shapes, document and
        # pair slots against what LightGBM's loops visit (rank_* facts)
        for fact, value in getattr(self.obj, "facts", {}).items():
            profiling.note("train." + fact, value)
        if self._cat_cols is not None:
            # the categorical columns; where their category sets route rows
            # (train.cat_route) the grower notes as the program is traced
            profiling.note("train.cat_features",
                           int(np.sum(ds.bin_mapper.is_categorical)))
        fn = _multi_round_fn(
            self._obj_key, spec, p.boosting == "rf", n_rounds,
            p.bagging_freq if use_bagging else 0,
            p.feature_fraction < 1.0, goss_k)
        return fn, (
            ds.X_binned, ds.y, self._w_eff, self._bag, self._pred_train,
            self._hyper, self._key,
            jax.random.PRNGKey(p.bagging_seed + p.seed),
            jax.random.PRNGKey(p.feature_fraction_seed + p.seed),
            ds.row_mask, jnp.float32(ds.num_data_), jnp.int32(self._iter),
            jnp.float32(p.bagging_fraction),
            jnp.float32(p.feature_fraction), self._groups, self._members,
            self._col_order, self._cat_cols)

    def _dart_round(self) -> bool:
        """One DART boosting round (upstream dart.hpp semantics).

        A random subset of existing trees is "dropped": the new tree fits
        gradients of the ensemble WITHOUT them, then (non-xgboost mode) the
        new tree is scaled by 1/(k+1) and each dropped tree rescaled to
        k/(k+1) so the expected ensemble output is preserved (MART's
        shrinkage-induced over-specialization fix — Rashmi &
        Gilad-Bachrach 2015).  Stored leaf values carry the DART scales
        directly, so the uniform learning-rate shrink at predict time stays
        correct; with probability ``skip_drop`` a round degenerates to
        plain gbdt.
        """
        ds = self.train_set
        p = self.params
        i = self._iter
        fmask = self._sample_bag_and_fmask(i)

        rng = np.random.default_rng(p.drop_seed + p.seed + i * 7919)
        n_t = len(self.trees)
        dropped: List[int] = []
        if n_t > 0 and p.drop_rate > 0 and rng.random() >= p.skip_drop:
            m = rng.random(n_t) < p.drop_rate
            dropped = [int(t) for t in np.flatnonzero(m)]
            if p.max_drop > 0 and len(dropped) > p.max_drop:
                dropped = sorted(
                    int(t) for t in rng.choice(dropped, p.max_drop,
                                               replace=False))
        k = len(dropped)
        nc = self._num_class
        lr = jnp.float32(p.learning_rate)
        add = _tree_pred_fn(self._depth_cap, nc)

        drop_sum = None
        if k > 0:
            # ONE stacked forest pass computes the dropped trees' summed raw
            # values per dataset (not k separate single-tree dispatches)
            caps = {int(self.trees[t].split_feature.shape[-1])
                    for t in dropped}
            cap = max(caps)
            stack = jax.tree.map(
                lambda *xs: jnp.stack(xs),
                *[pad_tree(self.trees[t], cap) for t in dropped])

            def dropped_sum(bins):
                if nc > 1:  # [k, K, M] stacked trees -> [n, K] summed raw
                    return _predict_forest_mc(stack, bins, 1.0, 0.0, k,
                                              self._depth_cap,
                                              members=self._members)
                return predict_forest_binned(
                    stack, bins, 1.0, 0.0, jnp.int32(k), self._depth_cap,
                    members=self._members)

            drop_sum = dropped_sum(ds.X_binned)

        pred = self._pred_train
        if k > 0:
            pred = pred - lr * drop_sum

        eff_rows = int(ds.row_mask.shape[0])
        fn = _round_fn(self._obj_key, self._grow_spec(eff_rows), False, nc,
                       None, None)
        round_key = jax.random.fold_in(self._key, i)
        tree, new_pred = fn(ds.X_binned, ds.y, self._w_eff, self._bag, pred,
                            fmask, self._hyper, round_key, self._groups,
                            self._members, None, self._cat_cols)

        if k > 0:
            # upstream Normalize(): on drop rounds the new tree's weight is
            # 1/(k+1) (xgboost mode: lr/(k+lr)) INSTEAD of the learning
            # rate, and dropped trees rescale to k/(k+1) (resp. k/(k+lr)).
            # Stored values are raw (uniform lr applied at predict), so the
            # baked factor divides lr back out.
            lr_f = float(p.learning_rate)
            if p.xgboost_dart_mode:
                new_scale = 1.0 / (k + lr_f)
                drop_scale = k / (k + lr_f)
            else:
                new_scale = 1.0 / ((k + 1.0) * lr_f)
                drop_scale = k / (k + 1.0)
            tree = tree._replace(
                leaf_value=tree.leaf_value * jnp.float32(new_scale))
            new_pred = pred + (new_pred - pred) * jnp.float32(new_scale)
            # valid-set deltas from rescaling dropped trees — one stacked
            # forest pass per valid set, using the OLD leaf values
            for idx, (name, vds, vpred) in enumerate(self._valid):
                vsum = dropped_sum(vds.X_binned)
                self._valid[idx] = (
                    name, vds,
                    vpred + lr * jnp.float32(drop_scale - 1.0) * vsum)
            for t in dropped:
                self.trees[t] = self.trees[t]._replace(
                    leaf_value=self.trees[t].leaf_value
                    * jnp.float32(drop_scale))
            # re-add the (now rescaled) dropped trees' contribution
            new_pred = new_pred + lr * jnp.float32(drop_scale) * drop_sum

        self._pred_train = new_pred
        self.trees.append(tree)
        self._forest_cache = None
        for idx, (name, vds, vpred) in enumerate(self._valid):
            self._valid[idx] = (name, vds, add(vpred, tree, vds.X_binned, lr,
                                               self._members))
        self._iter += 1
        return False

    # -- evaluation ------------------------------------------------------
    def _metric_names(self) -> List[str]:
        names = [m for m in self.params.metric if m != "none"]
        if not names:
            default = default_metric_for_objective(self.params.objective)
            if default != "none":
                names = [default]
        return names

    def _eval_on(self, pred_raw, ds: Dataset, name: str):
        metric_names = tuple(self._metric_names())
        if not metric_names:
            return []
        out = []
        # ranking metrics need the query grouping — they bypass the plain
        # (pred, y, w) metric signature via the grouped eval path
        plain = tuple(m for m in metric_names if m not in ("ndcg", "map"))
        if plain:
            fn = _eval_fn(self._obj_key, plain,
                          (self.params.alpha,
                           self.params.tweedie_variance_power))
            vals = fn(pred_raw, ds.y, ds.w)
            for mname, v in zip(plain, vals):
                m = get_metric(mname, self.params)
                out.append((name, mname, float(v), m.higher_better))
        grouped = tuple(m for m in metric_names if m in ("ndcg", "map"))
        if grouped:
            from ..ranking import eval_ranking
            for mname, val, hib in eval_ranking(
                    pred_raw, ds, self.params.eval_at,
                    self.params.label_gain, metrics=grouped):
                out.append((name, mname, val, hib))
        return out

    def eval_train(self, feval=None):
        pred = self._pred_train_effective()
        res = self._eval_on(pred, self.train_set, "training")
        return res + self._feval_results(feval, pred, self.train_set,
                                         "training")

    def eval_valid(self, feval=None):
        out = []
        for name, vds, vpred in self._valid:
            vp = self._rf_scale(vpred)
            out.extend(self._eval_on(vp, vds, name))
            out.extend(self._feval_results(feval, vp, vds, name))
        return out

    def _feval_results(self, feval, pred_raw, ds, name):
        if feval is None:
            return []
        fevals = feval if isinstance(feval, (list, tuple)) else [feval]
        out = []
        n = ds.num_data_
        pred_host = np.asarray(self.obj.transform(pred_raw))[:n]
        for f in fevals:
            mname, val, hib = f(pred_host, ds)
            out.append((name, mname, float(val), bool(hib)))
        return out

    def _rf_scale(self, pred_raw):
        if self.params.boosting == "rf" and self._iter > 0:
            return (pred_raw - self.init_score_) / self._iter + self.init_score_
        return pred_raw

    def _pred_train_effective(self):
        if self.params.boosting == "rf":
            # rf keeps _pred_train at init; reconstruct mean over trees lazily
            if not self.trees:
                return self._pred_train
            forest = self._stacked_forest()
            if self._num_class > 1:
                return _predict_forest_mc(
                    forest, self.train_set.X_binned, 1.0 / self._iter,
                    self.init_score_, self._iter, self.params.num_leaves,
                    members=self._members)
            pred = predict_forest_binned(
                forest, self.train_set.X_binned, 1.0 / self._iter,
                self.init_score_, jnp.int32(self._iter), self.params.num_leaves,
                members=self._members)
            return pred
        return self._pred_train

    def add_valid(self, data: Dataset, name: str) -> "Booster":
        data.construct()
        if getattr(data, "is_streamed", False):
            raise ValueError(
                f"valid set '{name}' is a streamed (from_blocks) dataset — "
                "incremental valid-set scoring needs a resident binned "
                "matrix; bin the valid set in memory with "
                "reference=<streamed train set> instead")
        if data.y is None:
            raise ValueError(f"valid set '{name}' requires a label")
        k = self._num_class
        if k > 1:
            vpred = jnp.broadcast_to(
                jnp.asarray(self.init_score_)[None, :],
                (int(data.row_mask.shape[0]), k))
        else:
            vpred = jnp.full(data.row_mask.shape, self.init_score_,
                             jnp.float32)
        # replay existing trees (valid sets are usually added before round 0)
        shrink = (1.0 if self.params.boosting == "rf"
                  else getattr(self, "_base_lr", self.params.learning_rate))
        if getattr(self, "_linear_k", None) is not None:
            raw = data.raw_data
            if raw is None or isinstance(raw, str):
                raise ValueError(
                    "linear_tree valid sets need raw feature values "
                    "(free_raw_data=False, in-memory matrix)")
            data._xraw_dev = self._raw_to_device(
                raw, int(data.row_mask.shape[0]))
            add_lin = _linear_tree_pred_fn(self._depth_cap)
            for tree in self.trees:
                vpred = add_lin(vpred, tree, data.X_binned, data._xraw_dev,
                                jnp.float32(shrink))
        else:
            add_tree = _tree_pred_fn(self._depth_cap, k)
            for tree in self.trees:
                vpred = add_tree(vpred, tree, data.X_binned,
                                 jnp.float32(shrink), self._members)
        self._valid.append((name, data, vpred))
        return self

    # -- prediction ------------------------------------------------------
    def _stacked_forest(self) -> Tree:
        if self._forest_cache is None or \
                getattr(self, "_forest_count", -1) != len(self.trees):
            if not self.trees:
                raise ValueError("no trees trained yet")
            trees = self.trees
            caps = (trees.cap_set() if isinstance(trees, _TreeStore)
                    else {int(t.split_feature.shape[-1]) for t in trees})
            if len(caps) > 1:  # init_model continuation, different num_leaves
                cap = max(caps)
                trees = [pad_tree(t, cap) for t in trees]
            if isinstance(trees, _TreeStore):
                runs = trees.stacked_runs()
                forest = (runs[0] if len(runs) == 1 else jax.tree.map(
                    lambda *xs: jnp.concatenate(xs), *runs))
            else:
                forest = jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
            from ..ops.predict import DEFAULT_TREE_CHUNK, forest_depth_cap
            self._forest_depth = forest_depth_cap(forest)
            # pad the tree axis to a chunk multiple so predict() compiles
            # once per forest-size bucket, not once per forest size (padded
            # trees are zeroed and excluded by the traced round mask)
            t_real = forest.leaf_value.shape[0]
            t_pad = -(-t_real // DEFAULT_TREE_CHUNK) * DEFAULT_TREE_CHUNK
            if t_pad != t_real:
                forest = jax.tree.map(
                    lambda a: jnp.concatenate(
                        [a, jnp.zeros((t_pad - t_real,) + a.shape[1:],
                                      a.dtype)]), forest)
            self._forest_cache = forest
            self._forest_count = len(self.trees)
        return self._forest_cache

    def predict(
        self,
        data,
        num_iteration: Optional[int] = None,
        raw_score: bool = False,
        pred_leaf: bool = False,
        pred_contrib: bool = False,
        start_iteration: int = 0,
        ntree_limit: Optional[int] = None,  # xgboost-style alias
        **kwargs,
    ) -> np.ndarray:
        """Predict on raw (unbinned) features.

        ``num_iteration``/``ntree_limit`` truncate to the first k trees —
        the staged-prediction contract of bagging_boosting.ipynb:136.
        ``pred_contrib`` returns exact path-dependent TreeSHAP values
        ``[n, F+1]`` (``[n, K*(F+1)]`` multiclass) in raw-score space with
        the expected value in the last column, matching LightGBM's
        ``predict(..., pred_contrib=True)`` contract (ops/shap.py).
        """
        if num_iteration is None:
            num_iteration = ntree_limit
        if num_iteration is None:
            # None -> best_iteration when early stopping found one
            num_iteration = (self.best_iteration
                             if self.best_iteration > 0 else len(self.trees))
        elif num_iteration <= 0:
            # explicit <= 0 -> ALL trees (LightGBM contract)
            num_iteration = len(self.trees)
        start_iteration = max(int(start_iteration), 0)
        num_iteration = min(num_iteration, len(self.trees) - start_iteration)
        if isinstance(data, Dataset):
            raise TypeError(
                "predict() expects a raw feature matrix, not a Dataset "
                "(matching lightgbm)")
        from ..dataset import _to_2d_float_array
        X = _to_2d_float_array(data)
        # trees split on original features: their codes, unbundled
        codes = self._bin_mapper_for_predict()._transform_unbundled(X)
        bins = jnp.asarray(codes)
        if pred_leaf:
            forest = self._stacked_forest()
            # LightGBM contract: [n, num_iteration * num_class], iteration-
            # major, values are per-tree leaf ordinals in [0, num_leaves)
            # — not node-array slots (ADVICE r1): rank leaf slots by node id
            leaves = []
            for t in range(start_iteration, start_iteration + num_iteration):
                for c in range(self._num_class):
                    tree = jax.tree.map(
                        (lambda a: a[t]) if self._num_class == 1
                        else (lambda a: a[t, c]), forest)
                    node = self._leaf_index(tree, bins)
                    ordinal = jnp.cumsum(tree.is_leaf.astype(jnp.int32)) - 1
                    leaves.append(np.asarray(ordinal[node]))
            return np.stack(leaves, axis=1)
        if pred_contrib:
            if self.trees and self.trees[0].linear_feat is not None:
                raise NotImplementedError(
                    "pred_contrib with linear_tree is not supported")
            return self._pred_contrib(bins, start_iteration, num_iteration)
        shrink = (1.0 if self.params.boosting == "rf"
                  else getattr(self, "_base_lr", self.params.learning_rate))
        if self.trees and self.trees[0].linear_feat is not None:
            xr = np.ascontiguousarray(X, dtype=np.float32)
            add_lin = _linear_tree_pred_fn(self._depth_cap)
            raw = jnp.full(bins.shape[0], float(self.init_score_),
                           jnp.float32)
            xr_dev = jnp.asarray(xr)
            for t in range(start_iteration,
                           start_iteration + num_iteration):
                raw = add_lin(raw, self.trees[t], bins, xr_dev,
                              jnp.float32(shrink))
            if raw_score:
                return np.asarray(raw)
            return np.asarray(self.obj.transform(raw))
        forest = self._stacked_forest()
        k = self._num_class
        if k > 1:
            raw = _predict_forest_mc(
                forest, bins, shrink, self.init_score_, num_iteration,
                min(self._depth_cap, self._forest_depth),
                start_iteration=start_iteration)          # [n, K]
            if self.params.boosting == "rf" and num_iteration > 0:
                raw = ((raw - jnp.asarray(self.init_score_)[None, :])
                       / num_iteration
                       + jnp.asarray(self.init_score_)[None, :])
        else:
            raw = predict_forest_binned(
                forest, bins, jnp.float32(shrink), self.init_score_,
                jnp.int32(num_iteration),
                min(self._depth_cap, self._forest_depth),
                start_iteration=jnp.int32(start_iteration))
            if self.params.boosting == "rf" and num_iteration > 0:
                raw = (raw - self.init_score_) / num_iteration \
                    + self.init_score_
        if raw_score:
            return np.asarray(raw)
        return np.asarray(self.obj.transform(raw))

    def _pred_contrib(self, bins, start: int, num: int) -> np.ndarray:
        """Exact TreeSHAP contributions over the selected trees.

        Reported per ORIGINAL feature (every split is on one); the bias
        column carries the per-tree expected values plus the init score,
        so rows sum to the raw prediction.
        """
        from ..ops.shap import forest_pred_contrib

        f_orig = self._bin_mapper_for_predict().num_features
        p = self.params
        k = self._num_class
        sel = self.trees[start:start + num]
        caps = {int(t.split_feature.shape[-1]) for t in sel}
        if len(caps) > 1:  # init_model continuation with mixed num_leaves
            sel = [pad_tree(t, max(caps)) for t in sel]
        fields = [f for f in Tree._fields
                  if getattr(sel[0], f, None) is not None] if sel else []

        def to_np(t, c=None):
            return {f: np.asarray(getattr(t, f) if c is None
                                  else getattr(t, f)[c]) for f in fields}

        is_rf = p.boosting == "rf"
        shrink = np.full(
            len(sel),
            1.0 if is_rf else getattr(self, "_base_lr", p.learning_rate),
            np.float32)
        outs = []
        for c in range(k):
            tree_dicts = [to_np(t, c if k > 1 else None) for t in sel]
            phi = forest_pred_contrib(tree_dicts, bins, f_orig, shrink)
            if is_rf and len(sel) > 0:
                phi /= len(sel)
            init = (float(self.init_score_[c]) if k > 1
                    else float(np.float32(self.init_score_)))
            phi[:, -1] += init
            outs.append(phi)
        return np.concatenate(outs, axis=1) if k > 1 else outs[0]

    def _leaf_index(self, tree: Tree, bins) -> jnp.ndarray:
        from jax import lax

        n = bins.shape[0]
        b32 = bins.astype(jnp.int32)

        def step(node, _):
            feat = tree.split_feature[node]
            thr = tree.split_bin[node]
            code = jnp.take_along_axis(b32, feat[:, None], axis=1)[:, 0]
            left = code <= thr
            if tree.is_cat_split is not None:
                left = jnp.where(tree.is_cat_split[node],
                                 tree.cat_mask[node, code], left)
            nxt = jnp.where(left, tree.left[node], tree.right[node])
            return jnp.where(tree.is_leaf[node], node, nxt), None

        node, _ = lax.scan(step, jnp.zeros(n, jnp.int32), None,
                           length=self._depth_cap)
        return node

    def _bin_mapper_for_predict(self):
        if self.train_set is not None:
            return self.train_set.bin_mapper
        return self._bin_mapper  # loaded from a model file

    # -- introspection ---------------------------------------------------
    def current_iteration(self) -> int:
        return self._iter

    def num_trees(self) -> int:
        return len(self.trees)

    def num_feature(self) -> int:
        if self.train_set is not None:
            return self.train_set.num_feature()
        return self._bin_mapper.num_features

    def feature_name(self) -> List[str]:
        if self.train_set is not None:
            return list(self.train_set.feature_names)
        return list(self._feature_names or [])

    def num_model_per_iteration(self) -> int:
        return self._num_class

    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        """Per-feature split counts or total gains.

        ``iteration`` counts boosting ROUNDS (for multiclass each round holds
        ``num_class`` trees); ``None`` or <= 0 means all rounds (ADVICE r1:
        no falsy-zero conflation).  Vectorized over the stacked forest — no
        Python double loop at 1000 trees (VERDICT r1 weak #9).
        """
        k = len(self.trees) if (iteration is None or iteration <= 0) \
            else min(int(iteration), len(self.trees))
        out = np.zeros(self.num_feature(), dtype=np.float64)
        if k == 0:
            return (out.astype(np.int64) if importance_type == "split"
                    else out)
        forest = jax.tree.map(lambda a: a[:k], self._stacked_forest())
        feats = np.asarray(forest.split_feature).ravel()
        gains = np.asarray(forest.split_gain).ravel()
        # internal nodes = slots that were actually split: not a leaf AND
        # have a child written (unused slots keep left == -1)
        used = (~np.asarray(forest.is_leaf).ravel()
                & (np.asarray(forest.left).ravel() >= 0))
        vals = (np.ones_like(gains) if importance_type == "split" else gains)
        np.add.at(out, feats[used], vals[used])
        if importance_type == "split":
            return out.astype(np.int64)
        return out

    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        """Update trace-dynamic hyper-parameters mid-training (LightGBM
        ``Booster.reset_parameter``, driven by the ``reset_parameter``
        callback).  Continuous knobs (learning_rate, lambdas, fractions,
        min_data_in_leaf, ...) are traced scalars, so NO recompilation
        happens; shape-static parameters cannot change on a live booster.
        """
        newp = parse_params(params, base=self.params)
        static = ["num_leaves", "max_bin", "objective", "boosting",
                  "num_class", "tree_learner", "grow_policy",
                  "max_cat_threshold", "max_cat_to_onehot",
                  "min_data_per_group", "extra_trees", "linear_tree"]
        if self.params.boosting == "goss":
            # GOSS sampling counts are compile-time constants (goss_k)
            static += ["top_rate", "other_rate"]
        for f in static:
            if getattr(newp, f) != getattr(self.params, f):
                raise ValueError(
                    f"cannot reset shape-static parameter '{f}' on a "
                    "trained booster (it changes the compiled program)")
        self.params = newp
        self._hyper = HyperScalars.from_params(newp)
        self._grow_specs = {}    # hist_dtype, bynode, ... take effect next round
        return self

    def rollback_one_iter(self) -> "Booster":
        if self.trees:
            tree = self.trees.pop()
            self._forest_cache = None
            self._iter -= 1
            is_rf = self.params.boosting == "rf"
            shrink = jnp.float32(
                1.0 if is_rf
                else getattr(self, "_base_lr", self.params.learning_rate))
            if tree.linear_feat is not None:
                add_lin = _linear_tree_pred_fn(self._depth_cap)
                if not is_rf:
                    self._pred_train = add_lin(
                        self._pred_train, tree, self.train_set.X_binned,
                        self._xraw, -shrink)
                for idx, (name, vds, vpred) in enumerate(self._valid):
                    self._valid[idx] = (
                        name, vds, add_lin(vpred, tree, vds.X_binned,
                                           vds._xraw_dev, -shrink))
                return self
            add = _tree_pred_fn(self._depth_cap, self._num_class)
            if not is_rf:  # rf keeps _pred_train at init score
                self._pred_train = add(
                    self._pred_train, tree, self.train_set.X_binned, -shrink,
                    self._members)
            for idx, (name, vds, vpred) in enumerate(self._valid):
                self._valid[idx] = (
                    name, vds, add(vpred, tree, vds.X_binned, -shrink,
                                   self._members))
        return self

    # -- persistence (full model dump lands with utils.serialize) --------
    def save_model(self, filename: str, num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> "Booster":
        from ..utils.serialize import save_booster
        save_booster(self, filename, num_iteration=num_iteration,
                     start_iteration=start_iteration)
        return self

    def refit(self, data, label, decay_rate: float = 0.9,
              weight=None, group=None, **kwargs) -> "Booster":
        """Refit leaf values on new data, keeping every tree's structure
        (LightGBM ``Booster.refit``): sequentially per tree, the new leaf
        value is ``decay_rate * old + (1 - decay_rate) * newton`` where the
        Newton step comes from the new data's grad/hess at the ensemble's
        running prediction.  Returns a NEW booster; self is untouched.

        Ranking models pass ``group=`` (query sizes of the NEW data) — a
        fresh lambda layout is packed for it and the pairwise gradients
        drive the same Newton renewal.
        """
        import copy as _copy

        if self.params.boosting in ("rf", "dart"):
            raise NotImplementedError(
                "refit supports additive boosting (gbdt/goss); rf averages "
                "trees and dart bakes dropout scales into leaf values")
        if self.trees and self.trees[0].linear_feat is not None:
            raise NotImplementedError(
                "refit with linear_tree is not supported (leaf models need "
                "re-solving, not Newton-constant renewal)")
        if kwargs:
            raise TypeError(f"refit got unsupported arguments: "
                            f"{sorted(kwargs)}")
        from ..dataset import _to_2d_float_array

        X = _to_2d_float_array(data)
        y = jnp.asarray(np.asarray(label, np.float32))
        w = (jnp.ones_like(y) if weight is None
             else jnp.asarray(np.asarray(weight, np.float32)))
        codes = jnp.asarray(
            self._bin_mapper_for_predict()._transform_unbundled(X))
        p = self.params
        lam = jnp.float32(p.lambda_l2)
        decay = jnp.float32(decay_rate)
        lr = jnp.float32(getattr(self, "_base_lr", p.learning_rate))
        obj, groups = self.obj, None
        if getattr(obj, "needs_group", False):
            if group is None:
                raise ValueError(
                    "refit with a ranking objective requires group= "
                    "(query sizes of the refit data)")
            # fresh lambda layout packed for the NEW data
            obj = create_objective(p)
            obj.set_group(np.asarray(group, np.int64).reshape(-1),
                          np.asarray(label, np.float32),
                          int(np.asarray(label).reshape(-1).shape[0]))
            groups = obj.groups
            obj = _rebuild_objective(_objective_static_key(obj, p))
        elif group is not None:
            raise TypeError("refit got group= for a non-ranking objective")
        depth_cap = self._depth_cap

        def leaf_of(tree):
            n = codes.shape[0]
            b32 = codes.astype(jnp.int32)

            def step(node, _):
                feat = tree.split_feature[node]
                thr = tree.split_bin[node]
                code = jnp.take_along_axis(b32, feat[:, None], axis=1)[:, 0]
                left = code <= thr
                if tree.is_cat_split is not None:
                    left = jnp.where(tree.is_cat_split[node],
                                     tree.cat_mask[node, code], left)
                nxt = jnp.where(left, tree.left[node], tree.right[node])
                return jnp.where(tree.is_leaf[node], node, nxt), None

            leafs, _ = lax.scan(step, jnp.zeros(n, jnp.int32), None,
                                length=depth_cap)
            return leafs

        def renew(tree, leafs, g, h):
            m = tree.leaf_value.shape[0]
            gs = jnp.zeros(m, jnp.float32).at[leafs].add(g)
            hs = jnp.zeros(m, jnp.float32).at[leafs].add(h)
            cnt = jnp.zeros(m, jnp.float32).at[leafs].add(1.0)
            newton = -gs / (hs + lam + 1e-15)
            vals = jnp.where(tree.is_leaf & (cnt > 0),
                             decay * tree.leaf_value
                             + (1.0 - decay) * newton,
                             tree.leaf_value)
            return tree._replace(leaf_value=vals), vals[leafs]

        @jax.jit
        def one_tree(tree, pred, groups):
            g, h = _grad_hess(obj, pred, y, w, groups)
            new_tree, delta = renew(tree, leaf_of(tree), g, h)
            return new_tree, pred + lr * delta

        @jax.jit
        def one_round_mc(tree, pred, _groups):   # tree [K, M]; pred [n, K]
            g, h = obj.grad_hess(pred, y, w)            # [n, K]
            leafs = jax.vmap(leaf_of)(tree)             # [K, n]
            new_tree, delta = jax.vmap(renew)(tree, leafs, g.T, h.T)
            return new_tree, pred + lr * delta.T

        if self._num_class > 1:
            pred = jnp.broadcast_to(
                jnp.asarray(self.init_score_, jnp.float32)[None, :],
                (codes.shape[0], self._num_class))
            step_fn = one_round_mc
        else:
            pred = jnp.full(codes.shape[0], float(self.init_score_),
                            jnp.float32)
            step_fn = one_tree
        new_trees = []
        for t in self.trees:
            nt, pred = step_fn(t, pred, groups)
            new_trees.append(nt)
        out = _copy.copy(self)
        out.trees = new_trees
        out._forest_cache = None
        out._valid = []
        # the refit booster is predict-only: its training-state caches
        # (_pred_train/_bag) reflect the OLD leaf values, so continuing
        # training on it would fit wrong residuals
        out.train_set = None
        out._bin_mapper = self._bin_mapper_for_predict()
        out._feature_names = list(self.feature_name())
        out._pred_train = None
        out._bag = None
        return out

    def trees_to_dataframe(self):
        """Flat per-node pandas DataFrame (LightGBM ``trees_to_dataframe``):
        one row per node with tree_index / node_depth / node_index /
        children / parent / split_feature / split_gain / threshold /
        decision_type / value / count, node names in LightGBM's
        ``{tree}-S{split}`` / ``{tree}-L{leaf}`` convention."""
        import pandas as pd

        names = self.feature_name()
        rows: List[Dict[str, Any]] = []

        def walk(node: Dict[str, Any], tree_idx: int, depth: int,
                 parent: Optional[str]) -> str:
            is_leaf = "leaf_index" in node
            nid = (f"{tree_idx}-L{node['leaf_index']}" if is_leaf
                   else f"{tree_idx}-S{node['split_index']}")
            row = {
                "tree_index": tree_idx, "node_depth": depth,
                "node_index": nid, "left_child": None, "right_child": None,
                "parent_index": parent, "split_feature": None,
                "split_gain": None, "threshold": None,
                "decision_type": None,
                "value": node.get("leaf_value"),
                "count": int(node.get("leaf_count",
                                      node.get("internal_count", 0))),
            }
            rows.append(row)
            if not is_leaf:
                row["split_feature"] = names[node["split_feature"]]
                row["split_gain"] = node["split_gain"]
                row["threshold"] = node["threshold"]
                row["decision_type"] = node.get("decision_type", "<=")
                row["value"] = None
                row["left_child"] = walk(node["left_child"], tree_idx,
                                         depth + 1, nid)
                row["right_child"] = walk(node["right_child"], tree_idx,
                                          depth + 1, nid)
            return nid

        dump = self.dump_model()
        for ti, tinfo in enumerate(dump["tree_info"]):
            walk(tinfo["tree_structure"], ti, 1, None)
        return pd.DataFrame(rows)

    def dump_model(self, num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> Dict[str, Any]:
        """Nested-dict model dump (LightGBM ``dump_model`` contract)."""
        from ..utils.serialize import dump_booster_dict
        return dump_booster_dict(self, num_iteration=num_iteration,
                                 start_iteration=start_iteration)

    def model_to_string(self, num_iteration: Optional[int] = None,
                        start_iteration: int = 0) -> str:
        from ..utils.serialize import booster_to_string
        return booster_to_string(self, num_iteration=num_iteration,
                                 start_iteration=start_iteration)
