"""Fused cross-validation trainer: the TPU answer to the reference sweep.

The reference's workload (SURVEY.md §3.2-3.3) is `lgb.cv` inside a serial
108-config grid — 5 folds × ≤1000 rounds × 108 configs, early-stopped on the
fold-mean metric, ~30 CPU-minutes.  A host-loop port pays a device round-trip
per boosting round per fold (early stopping is data-dependent), which is
latency-bound on TPU.

This module folds an ENTIRE batch of cv trainings into one XLA program:

  * rounds       -> `lax.while_loop` with ON-DEVICE early stopping (the
                    patience counters live in the carry: zero host syncs
                    until every config has stopped);
  * folds        -> a vmapped batch axis over fold train-masks;
  * grid configs -> the same batch axis: every regularization knob is a
                    traced scalar (HyperScalars/SplitContext), so one
                    compiled program serves all configs sharing
                    (num_leaves, num_bins), batched as [configs × folds];
  * histograms   -> the batched one-hot einsum gains a configs*folds*stats
                    inner dimension — the shape that finally feeds the MXU
                    properly.

Key trick: all rows (train + held-out) live in ONE binned matrix; held-out
rows simply carry zero gradient/hessian/count weight.  `grow_tree` partitions
every row through the split decisions regardless of weight, so fold-valid
predictions fall out of the same `leaf_value[row_leaf]` gather that updates
training scores — no separate traversal pass.

CV does not keep trees (the reference reads only best_iter / best_score —
r/gridsearchCV.R:116-117), so per-element memory is O(rows) predictions plus
O(T_max) metric history, letting a 36-config × 5-fold batch run as one
program.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, NamedTuple, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..config import Params, default_metric_for_objective
from ..metrics import get_metric
from .gbdt import HyperScalars, _objective_static_key, _rebuild_objective
from ..ops.lookup import lookup_values
from ..ops.split import CatColumns
from .spec import (STRICT, GrowSpec, WaveSchedule, categorical_key,
                   resolve_grow_spec, resolve_wave)
from .tree import grower_from_spec


class FusedCVCarry(NamedTuple):
    r: jnp.ndarray              # i32[] current round
    pred: jnp.ndarray           # f32[BATCH, n] raw scores (all rows)
    bag: jnp.ndarray            # f32[BATCH, n] current bagging mask
    history: jnp.ndarray        # f32[T_max, BATCH] per-round valid metric
    best_score: jnp.ndarray     # f32[C] sign-normalized best mean metric
    best_iter: jnp.ndarray      # i32[C] 0-based round of the best score
    done: jnp.ndarray           # bool[C]


class FusedCVResult(NamedTuple):
    history: jnp.ndarray        # f32[T_max, C, K] per-round per-fold metric
    best_iter: jnp.ndarray      # i32[C] 1-based best iteration
    best_score: jnp.ndarray     # f32[C] raw mean metric at the best round
    rounds_run: jnp.ndarray     # i32[]


from ..ops.sampling import sample_bag as _sample_bag
# tree-level column sampling goes through the shared mask-composition
# layer (models.feature_mask, r20) — same traced ops as the direct
# sampler, so the fused-CV RNG stream is unchanged
from .feature_mask import compose_tree_mask as _sample_features_within


@functools.lru_cache(maxsize=None)
def _fused_cv_fn(obj_key: tuple, spec: GrowSpec,
                 metric_name: str, metric_alpha: float,
                 metric_rho: float, t_max: int,
                 bagging_freq: int, n_configs: int, n_folds: int,
                 num_class: int = 1):
    """Build the jitted fused-cv program for one static configuration."""
    obj = _rebuild_objective(obj_key)
    grow = grower_from_spec(spec)
    metric = get_metric(metric_name,
                        Params(alpha=metric_alpha,
                               tweedie_variance_power=metric_rho))
    sign = 1.0 if metric.higher_better else -1.0
    batch = n_configs * n_folds

    def one_element_round(bins, y, w, pred, bag, hyper: HyperScalars, ff,
                          key, cats):
        """One boosting round for one (config, fold) batch element.

        ``pred`` is [n] (single-output) or [n, K] (multiclass — K trees
        grown simultaneously, the class axis vmapped over the grower
        exactly like the host loop's round_fn_mc); ``cats`` the table's
        categorical columns (``ops.split.CatColumns``, ``None``: none)."""
        num_features = bins.shape[1]
        g, h = obj.grad_hess(pred, y, w)
        fmask = _sample_features_within(jax.random.fold_in(key, 1), ff,
                                        num_features)

        def grow_one(gc, hc, kc):
            stats = jnp.stack([gc * bag, hc * bag, bag], axis=-1)
            return grow(bins, stats, fmask, hyper.ctx(), hyper.max_depth,
                        hyper.feature_fraction_bynode, kc, cats=cats)[:2]

        if num_class > 1:
            from .gbdt import mc_round_update
            _, new_pred = mc_round_update(
                grow_one, g, h,
                jax.random.split(jax.random.fold_in(key, 2), num_class),
                pred, hyper.learning_rate)
            return new_pred
        tree, row_leaf = grow_one(g, h, jax.random.fold_in(key, 2))
        return pred + hyper.learning_rate * lookup_values(
            row_leaf, tree.leaf_value)

    @jax.jit
    def run_segment(carry: FusedCVCarry, seg_end, bins, y, w, train_masks,
                    valid_masks, hyper_b: HyperScalars, bag_frac_b, ff_b,
                    n_in_fold_b, es_rounds, es_min_delta_c,
                    base_key, cats) -> FusedCVCarry:
        """Run rounds [carry.r, seg_end) — bounded per-dispatch runtime so a
        multi-minute cv batch is many short device programs, not one long
        one (long single executions can trip TPU runtime watchdogs), while
        early stopping still runs fully on device within each segment."""

        def body(c: FusedCVCarry) -> FusedCVCarry:
            r = c.r
            rkey = jax.random.fold_in(base_key, r)
            bkeys = jax.random.split(jax.random.fold_in(rkey, 0), batch)
            tkeys = jax.random.split(jax.random.fold_in(rkey, 1), batch)

            if bagging_freq > 0:
                bag = lax.cond(
                    r % bagging_freq == 0,
                    lambda _: jax.vmap(_sample_bag)(
                        bkeys, train_masks, bag_frac_b, n_in_fold_b),
                    lambda _: c.bag, None)
            else:
                bag = c.bag

            pred = jax.vmap(
                one_element_round,
                in_axes=(None, None, None, 0, 0, 0, 0, 0, None))(
                    bins, y, w, c.pred, bag, hyper_b, ff_b, tkeys, cats)

            tpred = obj.transform(pred)
            mvals = jax.vmap(lambda p, vm: metric.fn(p, y, w * vm))(
                tpred, valid_masks)                      # [BATCH]
            history = c.history.at[r].set(mvals)

            mean_by_cfg = mvals.reshape(n_configs, n_folds).mean(axis=1)
            score = sign * mean_by_cfg
            # early_stopping_min_delta (per config, traced): an improvement
            # only counts when it beats the incumbent by more than the
            # tolerance — callback.early_stopping's compare, on device
            improved = (score > c.best_score + es_min_delta_c) & ~c.done
            best_score = jnp.where(improved, score, c.best_score)
            best_iter = jnp.where(improved, r, c.best_iter)
            stalled = (r - best_iter >= es_rounds) & (es_rounds > 0)
            return FusedCVCarry(r + 1, pred, bag, history, best_score,
                                best_iter, c.done | stalled)

        def cond(c: FusedCVCarry) -> jnp.ndarray:
            return (c.r < seg_end) & ~jnp.all(c.done)

        return lax.while_loop(cond, body, carry)

    def init_carry(n: int, pred0) -> FusedCVCarry:
        if num_class > 1:                  # pred0 [K] class priors
            pred = jnp.broadcast_to(pred0[None, None, :],
                                    (batch, n, num_class))
        else:                              # pred0 [batch] scalars
            pred = jnp.broadcast_to(pred0[:, None], (batch, n))
        return FusedCVCarry(
            r=jnp.int32(0),
            pred=pred,
            bag=jnp.zeros((batch, n), jnp.float32),  # set by caller
            history=jnp.full((t_max, batch), jnp.nan, jnp.float32),
            best_score=jnp.full((n_configs,), -jnp.inf, jnp.float32),
            best_iter=jnp.zeros((n_configs,), jnp.int32),
            done=jnp.zeros((n_configs,), bool),
        )

    def finalize(carry: FusedCVCarry) -> FusedCVResult:
        return FusedCVResult(
            history=carry.history.reshape(t_max, n_configs, n_folds),
            best_iter=carry.best_iter + 1,
            best_score=sign * carry.best_score,
            rounds_run=carry.r,
        )

    return run_segment, init_carry, finalize


def _fused_wave_width(p: Params, n_pad: int,
                      hist_dtype: str) -> WaveSchedule:
    """Wave schedule for the BATCHED regime: strict growth below ~2^19 rows.

    With the configs x folds batch axis already amortizing per-pass fixed
    costs, waves' extra FLOPs and per-wave partition work LOSE at small n
    (measured r4: nl=127 strict 192 ms/round vs waves 368 ms at the
    46k-row sweep shape; at 1M rows the trade flips, same as the host
    path).  Exact-f32 ("f32x") and int8 dtypes also stay strict: they are
    excluded from the wide-segment batched kernel, and the segstats
    fallback at wave width materializes [n, E*W*S] in HBM (~15 GB at the
    1M-row 30-element shape).  An EXPLICIT grow_policy or wave_width
    still wins — cv must grow trees the way the final training will.
    """
    explicit = (p.grow_policy != "auto"
                or int(p.extra.get("wave_width", 0)) != 0)
    if not explicit and (n_pad < (1 << 19)
                        or hist_dtype in ("f32x", "int8")):
        return STRICT
    return resolve_wave(p, n_pad)


def fused_cv_eligible(p: Params, feval, callbacks, train_set=None) -> bool:
    """The fused path covers the reference's cv contract; anything needing
    per-round host hooks falls back to the host loop.

    Pass ``train_set`` to also apply dataset-dependent exclusions
    (categorical subset splits need the strict grower's cat path, which the
    fused batch program does not trace yet).
    """
    if feval is not None or callbacks:
        return False
    if p.extra.get("fobj") is not None:
        return False
    if p.objective in ("lambdarank", "none"):
        # (multiclass IS eligible since r4: the class axis vmaps inside
        # the batch program exactly like the host loop's round_fn_mc)
        return False
    metrics = [m for m in p.metric if m != "none"]
    if len(metrics) > 1:
        return False
    if p.boosting not in ("gbdt",):
        return False
    if p.monotone_constraints is not None or p.extra_trees \
            or p.linear_tree or p.interaction_constraints:
        # constrained/randomized split selection needs the per-booster
        # mono_key plumbing; the fused batch program does not trace it yet
        return False
    if train_set is not None and getattr(train_set, "is_streamed", False):
        # the batch program consumes one device-resident X_binned; a
        # streamed (BlockStore) Dataset has none — densify it first
        # (pipeline/daemon.py does) or take the host loop
        return False
    if getattr(getattr(train_set, "bin_mapper", None), "bundler",
               None) is not None:
        # an EFB table grows through its member tables (ops.members),
        # which the batch program does not carry: the host loop does
        return False
    return True


class FusedCVProgram:
    """Stepper interface over one fused-cv program (r17).

    Owns everything :func:`run_fused_cv_batch` used to set up inline —
    fold masks, batched hyper scalars, the objective, the jitted
    segment program — and exposes the execution as explicit
    init/step/finalize calls plus a carry <-> numpy round-trip, so the
    sweep service can CHECKPOINT a hyper-batch between segments through
    the r13 protocol and resume it bit-identically.  The carry restore
    is exact: every field is f32/i32/bool, so the npz round-trip loses
    nothing, and per-round RNG is keyed by round index, so replaying
    from a segment boundary reproduces the uninterrupted stream.
    """

    # the checkpointable state, in FusedCVCarry field order
    CARRY_DTYPES = {"r": jnp.int32, "pred": jnp.float32,
                    "bag": jnp.float32, "history": jnp.float32,
                    "best_score": jnp.float32, "best_iter": jnp.int32,
                    "done": jnp.bool_}

    def __init__(self, train_set, param_list: Sequence[Params],
                 fold_masks: np.ndarray, num_boost_round: int,
                 early_stopping_rounds: int, seed: int):
        p0 = param_list[0]
        metrics = [m for m in p0.metric if m != "none"] or \
            [default_metric_for_objective(p0.objective)]
        self.metric_name = metrics[0]
        self.num_boost_round = int(num_boost_round)

        train_set.construct()
        self._train_set = train_set
        n_pad = int(train_set.row_mask.shape[0])
        n = train_set.num_data()
        n_folds, _ = fold_masks.shape
        n_configs = len(param_list)
        self.n_configs, self.n_folds, self.n_pad = n_configs, n_folds, n_pad

        # [BATCH, n_pad] masks; padding rows excluded everywhere
        tm = np.zeros((n_configs * n_folds, n_pad), np.float32)
        vm = np.zeros((n_configs * n_folds, n_pad), np.float32)
        for ci in range(n_configs):
            for ki in range(n_folds):
                b = ci * n_folds + ki
                tm[b, :n] = fold_masks[ki]
                vm[b, :n] = ~fold_masks[ki]
        n_in_fold = tm.sum(axis=1).astype(np.float32)

        def rep(vals):
            return jnp.asarray(
                np.repeat(np.asarray(vals, np.float32), n_folds))

        hyper_b = HyperScalars(
            learning_rate=rep([p.learning_rate for p in param_list]),
            lambda_l1=rep([p.lambda_l1 for p in param_list]),
            lambda_l2=rep([p.lambda_l2 for p in param_list]),
            min_data_in_leaf=rep([p.min_data_in_leaf for p in param_list]),
            min_sum_hessian=rep(
                [p.min_sum_hessian_in_leaf for p in param_list]),
            min_gain_to_split=rep(
                [p.min_gain_to_split for p in param_list]),
            max_depth=rep(
                [p.max_depth for p in param_list]).astype(jnp.int32),
            feature_fraction_bynode=rep(
                [p.feature_fraction_bynode for p in param_list]),
            top_rate=rep([p.top_rate for p in param_list]),
            other_rate=rep([p.other_rate for p in param_list]),
            max_delta_step=rep([p.max_delta_step for p in param_list]),
            path_smooth=rep([p.path_smooth for p in param_list]),
            linear_lambda=rep([p.linear_lambda for p in param_list]),
        )
        bag_frac_b = rep([p.bagging_fraction for p in param_list])
        ff_b = rep([p.feature_fraction for p in param_list])

        # all configs in a bucket share bagging_freq (bucketing key) —
        # LightGBM's grid fixes it at 4 anyway (r/gridsearchCV.R:98)
        bagging_freq = p0.bagging_freq if p0.bagging_fraction < 1.0 or any(
            p.bagging_fraction < 1.0 for p in param_list) else 0

        from ..objectives import create_objective

        obj = create_objective(p0)
        y_host = train_set.get_label()
        w_host = (train_set.get_weight()
                  if train_set.get_weight() is not None else np.ones(n))
        if hasattr(obj, "prepare"):
            obj.prepare(y_host, w_host)
        num_class = (p0.num_class
                     if p0.objective in ("multiclass", "multiclassova")
                     else 1)
        init = obj.init_score(y_host, w_host)  # [K] priors mc, scalar else
        if num_class == 1:
            init = float(init)
        self._num_class = num_class
        self._init_score = init

        cat_key = categorical_key(train_set.bin_mapper, p0)
        spec = resolve_grow_spec(p0, n_pad, train_set.num_bins,
                                 cat_key=cat_key)
        spec = dataclasses.replace(
            spec, wave=_fused_wave_width(p0, n_pad, spec.hist_dtype),
            bynode_off=all(p.feature_fraction_bynode >= 1.0
                           for p in param_list))
        self._run_segment, self._init_carry, self._finalize = _fused_cv_fn(
            _objective_static_key(obj, p0), spec, self.metric_name,
            float(p0.alpha), float(p0.tweedie_variance_power),
            num_boost_round, int(bagging_freq), n_configs, n_folds,
            num_class)

        self._tm_d = jnp.asarray(tm)
        self._args = (
            self._tm_d, jnp.asarray(vm), hyper_b, bag_frac_b, ff_b,
            jnp.asarray(n_in_fold), jnp.int32(early_stopping_rounds),
            jnp.asarray([p.early_stopping_min_delta for p in param_list],
                        jnp.float32),
            jax.random.PRNGKey(seed), CatColumns.of(train_set.bin_mapper))
        self.segment_rounds = int(p0.extra.get("cv_segment_rounds", 100))

    def init(self) -> FusedCVCarry:
        """Fresh round-0 carry (bag seeded to the train masks)."""
        carry = self._init_carry(
            self.n_pad,
            jnp.asarray(self._init_score, jnp.float32)
            if self._num_class > 1
            else jnp.full((self.n_configs * self.n_folds,),
                          self._init_score, jnp.float32))
        return carry._replace(bag=self._tm_d)

    def step(self, carry: FusedCVCarry, seg_end: int) -> FusedCVCarry:
        """One device dispatch: rounds [carry.r, seg_end) with on-device
        early stopping."""
        ts = self._train_set
        return self._run_segment(carry, jnp.int32(seg_end), ts.X_binned,
                                 ts.y, ts.w, *self._args)

    def done(self, carry: FusedCVCarry) -> bool:
        return bool(jnp.all(carry.done)) \
            or int(carry.r) >= self.num_boost_round

    def finalize(self, carry: FusedCVCarry) -> FusedCVResult:
        return self._finalize(carry)

    def carry_arrays(self, carry: FusedCVCarry) -> dict:
        """Carry -> host numpy dict, the r13 checkpoint payload shape."""
        return {f: np.asarray(getattr(carry, f))
                for f in FusedCVCarry._fields}

    def restore_carry(self, arrays: dict) -> FusedCVCarry:
        """Exact inverse of :meth:`carry_arrays`."""
        return FusedCVCarry(**{
            f: jnp.asarray(arrays[f], self.CARRY_DTYPES[f])
            for f in FusedCVCarry._fields})


def run_fused_cv_batch(
    train_set,
    param_list: Sequence[Params],
    fold_masks: np.ndarray,        # bool [n_folds, n] True = in-train
    num_boost_round: int,
    early_stopping_rounds: int,
    seed: int,
    timings: Optional[dict] = None,
):
    """Execute a batch of cv trainings (all sharing num_leaves/max_bin/
    objective statics) as one fused program.

    Returns (history [T, C, K] numpy with NaN tail, best_iter [C],
    best_score_raw [C], rounds_run).  When ``timings`` is passed, it is
    filled with ``compile_s`` (first-dispatch overhead above the
    steady-state segment cost — compile + first-touch) and ``exec_s``
    (estimated pure execution) so sweep reports can separate the two
    (VERDICT r3: "instrument compile-vs-execute, then fix").

    Since r17 this is a thin driver over :class:`FusedCVProgram` — the
    sweep service uses the same stepper with checkpoints between
    segments; this entry point keeps the original run-to-completion
    contract bit-identical.
    """
    prog = FusedCVProgram(train_set, param_list, fold_masks,
                          num_boost_round, early_stopping_rounds, seed)
    carry = prog.init()
    seg = prog.segment_rounds
    import time as _time
    if timings is not None:
        # isolate compile exactly: a seg_end=0 call compiles the full
        # program but its while_loop condition is immediately false, so
        # execution cost is one empty dispatch (~terminal latency)
        t0 = _time.perf_counter()
        carry = prog.step(carry, 0)
        jax.block_until_ready(carry.r)
        timings["compile_s"] = _time.perf_counter() - t0
    t_exec = _time.perf_counter()
    for seg_end in range(seg, num_boost_round + seg, seg):
        carry = prog.step(carry, min(seg_end, num_boost_round))
        if bool(jnp.all(carry.done)) or int(carry.r) >= num_boost_round:
            break
    if timings is not None:
        timings["exec_s"] = _time.perf_counter() - t_exec
    res = prog.finalize(carry)
    return (np.asarray(res.history), np.asarray(res.best_iter),
            np.asarray(res.best_score), int(res.rounds_run),
            prog.metric_name)
