"""Best-split search over histograms.

TPU-native replacement for LightGBM's ``FindBestSplit`` bin scan (upstream
``treelearner``, exercised via ``num_leaves`` / ``min_data_in_leaf`` in the
reference grid — r/gridsearchCV.R:96-97; SURVEY.md §2C "Leaf-wise best-first
split finder").  The scan is fully vectorized: a cumulative sum along the bin
axis yields every candidate left-partition's (G, H, count) at once, the split
gain is evaluated for all (feature, bin) pairs in parallel on the VPU, and a
flat argmax picks the winner.

All regularization thresholds (lambda_l1/l2, min_data_in_leaf,
min_sum_hessian, min_gain_to_split) are *traced* scalars, so hyper-parameter
configs can be vmapped without recompilation (SURVEY.md §7 sweep design).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

NEG_INF = -jnp.inf
# gains of a categorical feature's two scan directions closer than this
# (relative) are one tie, which the ascending direction wins
TIE_RTOL = 1e-5


class SplitContext(NamedTuple):
    """Traced regularization scalars for gain evaluation.

    ``max_delta_step`` (<= 0 means unlimited) caps |leaf output| (upstream
    ``max_delta_step``); ``path_smooth`` > 0 shrinks child outputs toward the
    parent's value by ``n / (n + path_smooth)`` (upstream ``path_smooth``).
    Both default off, in which case every output is the unconstrained optimum
    and the gain reduces to the closed-form scan.
    """

    lambda_l1: jnp.ndarray
    lambda_l2: jnp.ndarray
    min_data_in_leaf: jnp.ndarray
    min_sum_hessian: jnp.ndarray
    min_gain_to_split: jnp.ndarray
    max_delta_step: jnp.ndarray = 0.0
    path_smooth: jnp.ndarray = 0.0

    @staticmethod
    def from_params(p) -> "SplitContext":
        return SplitContext(
            lambda_l1=jnp.float32(p.lambda_l1),
            lambda_l2=jnp.float32(p.lambda_l2),
            min_data_in_leaf=jnp.float32(p.min_data_in_leaf),
            min_sum_hessian=jnp.float32(p.min_sum_hessian_in_leaf),
            min_gain_to_split=jnp.float32(p.min_gain_to_split),
            max_delta_step=jnp.float32(p.max_delta_step),
            path_smooth=jnp.float32(getattr(p, "path_smooth", 0.0)),
        )


def threshold_l1(g: jnp.ndarray, l1: jnp.ndarray) -> jnp.ndarray:
    """Soft-threshold for L1 regularization (LightGBM ThresholdL1)."""
    return jnp.sign(g) * jnp.maximum(jnp.abs(g) - l1, 0.0)


def leaf_objective(sum_g, sum_h, ctx: SplitContext):
    """-0.5 * optimal loss reduction contribution of a leaf:
    ThresholdL1(G)^2 / (H + lambda_l2)."""
    tg = threshold_l1(sum_g, ctx.lambda_l1)
    return tg * tg / (sum_h + ctx.lambda_l2 + 1e-15)


def leaf_output(sum_g, sum_h, ctx: SplitContext):
    """Optimal leaf value: -ThresholdL1(G) / (H + lambda_l2)."""
    return -threshold_l1(sum_g, ctx.lambda_l1) / (sum_h + ctx.lambda_l2 + 1e-15)


def leaf_objective_at(w, sum_g, sum_h, ctx: SplitContext):
    """Objective contribution of a leaf FORCED to output ``w`` (upstream
    ``GetLeafGainGivenOutput``): -2 * (G*w + (H + l2)/2 * w^2 + l1*|w|).

    Equals :func:`leaf_objective` when ``w`` is the unconstrained optimum;
    needed when monotone bounds / max_delta_step / path_smooth move the
    output off the optimum."""
    return -2.0 * (sum_g * w + 0.5 * (sum_h + ctx.lambda_l2) * w * w
                   + ctx.lambda_l1 * jnp.abs(w))


def constrained_leaf_output(sum_g, sum_h, count, ctx: SplitContext,
                            lo, hi, parent_out):
    """Leaf output under path smoothing, max_delta_step, and monotone
    ancestor bounds ``[lo, hi]``.

    Order matches upstream: smooth toward the parent first
    (``w * n/(n+ps) + parent * ps/(n+ps)``), then clip to the intersection
    of the monotone bounds and ``[-max_delta_step, +max_delta_step]``."""
    w = leaf_output(sum_g, sum_h, ctx)
    ps = ctx.path_smooth
    factor = count / (count + jnp.maximum(ps, 1e-30))
    w = jnp.where(ps > 0, w * factor + parent_out * (1.0 - factor), w)
    cap = jnp.where(ctx.max_delta_step > 0, ctx.max_delta_step, jnp.inf)
    return jnp.clip(w, jnp.maximum(lo, -cap), jnp.minimum(hi, cap))


def split_gain_scan(lg, lh, lc, rg, rh, rc, tg, th, ctx: SplitContext,
                    lo, hi, p_out):
    """Core regularized-gain evaluation over channel-split cumsum arrays.

    SINGLE SOURCE for the numeric gain formula: :func:`find_best_split`
    (the XLA scan) and the Pallas split-iteration mega-kernel
    (``ops.histogram_pallas._split_iter_kernel``) both call this pure-jnp
    helper, so the two paths agree BITWISE by construction — same ops in
    the same order on the same operands (the kernel's interpret mode IS
    jax ops, and the parity suite asserts exact equality).

    Returns (gain, wl, wr) with shapes following the broadcast of the
    inputs (``[F, B]`` in the scan, lane-tiled in the kernel).
    """
    wl = constrained_leaf_output(lg, lh, lc, ctx, lo, hi, p_out)
    wr = constrained_leaf_output(rg, rh, rc, ctx, lo, hi, p_out)
    parent_obj = leaf_objective_at(p_out, tg, th, ctx)
    gain = (leaf_objective_at(wl, lg, lh, ctx)
            + leaf_objective_at(wr, rg, rh, ctx) - parent_obj)
    return gain, wl, wr


def split_stats_valid(lc, rc, lh, rh, gain, ctx: SplitContext):
    """Shared data-driven validity mask (min_data / min_hessian /
    min_gain) — the feature-mask and depth terms stay caller-side, since
    their shapes differ between the XLA scan and the mega-kernel."""
    return (
        (lc >= ctx.min_data_in_leaf)
        & (rc >= ctx.min_data_in_leaf)
        & (lh >= ctx.min_sum_hessian)
        & (rh >= ctx.min_sum_hessian)
        & (gain > ctx.min_gain_to_split)
    )


class CatInfo(NamedTuple):
    """Static-per-dataset categorical split configuration.

    ``is_cat`` marks the original features holding categorical codes (the
    scan's features: an EFB table's scan reads its member view);
    ``cat_bins`` the bins of each one's kept categories (``dataset.
    BinMapper.category_values``: the overflow bin after them, which holds
    the rare categories and NaN, is in no category set; ``None``: every
    bin is a category).  A column whose bins, the overflow bin counted,
    are at most ``max_cat_to_onehot`` takes one-vs-rest splits.  The
    scalars mirror upstream ``cat_smooth`` / ``cat_l2`` /
    ``max_cat_threshold`` / ``max_cat_to_onehot`` / ``min_data_per_group``.
    """

    is_cat: jnp.ndarray        # bool [F]
    cat_smooth: jnp.ndarray    # f32 []
    cat_l2: jnp.ndarray        # f32 []
    max_cat_threshold: int     # static
    cat_bins: Optional[jnp.ndarray] = None    # i32 [F]
    max_cat_to_onehot: int = 0                # static
    min_data_per_group: jnp.ndarray = 0.0     # f32 []

    def per_feature(self, fn) -> "CatInfo":
        """This configuration with ``fn`` applied to each per-feature
        array (a shard's slice of the features)."""
        return self._replace(**{
            k: fn(getattr(self, k)) for k in ("is_cat", "cat_bins")
            if getattr(self, k) is not None})


class CatColumns(NamedTuple):
    """The categorical columns of a table, an OPERAND of every program that
    grows on it (``models.tree.build_cat_info`` makes the CatInfo): which
    columns, and the bins of each one's categories (0 for a numeric
    column)."""

    is_cat: jnp.ndarray        # bool [F]
    cat_bins: jnp.ndarray      # i32 [F]

    @staticmethod
    def of(mapper) -> Optional["CatColumns"]:
        """A ``dataset.BinMapper``'s categorical columns, each one's bins
        but its overflow bin; ``None`` where no column is categorical."""
        is_cat = np.asarray(mapper.is_categorical, bool)
        if not is_cat.any():
            return None
        return CatColumns(
            is_cat=jnp.asarray(is_cat),
            cat_bins=jnp.asarray(np.where(
                is_cat, np.asarray(mapper.n_bins) - 1, 0), jnp.int32))


def feature_best_gains(
    hist: jnp.ndarray,
    ctx: SplitContext,
    feature_mask: jnp.ndarray,
    depth_ok: jnp.ndarray,
    mono=None,
    bound_lo=None,
    bound_hi=None,
    parent_out=None,
    rand_bins=None,
) -> jnp.ndarray:
    """Per-feature best NUMERIC split gain ``[F]`` over one histogram.

    The voting-parallel learner's ballot (upstream
    ``VotingParallelTreeLearner`` / PV-Tree): each shard scores its LOCAL
    partial histogram with this scan and nominates its top-k features;
    only the nominated union's columns get a histogram merge.  Same
    numeric core as :func:`find_best_split` (``split_gain_scan`` /
    ``split_stats_valid``), reduced over the bin axis instead of
    globally argmax'd; invalid candidates score ``-inf``.
    """
    cum = jnp.cumsum(hist, axis=1)
    total = cum[:, -1:, :]
    lg, lh, lc = cum[..., 0], cum[..., 1], cum[..., 2]
    tg, th = total[..., 0], total[..., 1]
    tc = total[..., 2]
    rg, rh, rc = tg - lg, th - lh, tc - lc
    lo = jnp.float32(-jnp.inf) if bound_lo is None else bound_lo
    hi = jnp.float32(jnp.inf) if bound_hi is None else bound_hi
    p_out = (leaf_output(tg, th, ctx) if parent_out is None else parent_out)
    gain, wl, wr = split_gain_scan(lg, lh, lc, rg, rh, rc, tg, th, ctx,
                                   lo, hi, p_out)
    valid = (
        split_stats_valid(lc, rc, lh, rh, gain, ctx)
        & (feature_mask[:, None] > 0)
        & depth_ok
    )
    if mono is not None:
        m = mono[:, None].astype(wl.dtype)
        valid &= (m == 0) | (m * (wr - wl) >= 0)
    if rand_bins is not None:
        pos_b = jnp.arange(hist.shape[1])[None, :]
        valid &= pos_b == rand_bins[:, None]
    return jnp.max(jnp.where(valid, gain, NEG_INF), axis=1)


class BestSplit(NamedTuple):
    gain: jnp.ndarray      # f32 [] best gain (NEG_INF if no valid split)
    feature: jnp.ndarray   # i32 []
    bin: jnp.ndarray       # i32 [] split threshold: go left iff code <= bin
    left_g: jnp.ndarray    # f32 []
    left_h: jnp.ndarray
    left_c: jnp.ndarray
    right_g: jnp.ndarray
    right_h: jnp.ndarray
    right_c: jnp.ndarray
    # child outputs under constraints (== unconstrained optimum when no
    # monotone bounds / max_delta_step / path_smooth are active)
    left_out: jnp.ndarray = None   # f32 []
    right_out: jnp.ndarray = None  # f32 []
    # categorical subset splits (None when the dataset has no categoricals)
    cat: jnp.ndarray = None       # bool [] winner is a k-vs-rest cat split
    cat_mask: jnp.ndarray = None  # bool [B] bins that go LEFT


def find_best_split(
    hist: jnp.ndarray,
    ctx: SplitContext,
    feature_mask: jnp.ndarray,
    depth_ok: jnp.ndarray,
    cat_info=None,
    mono=None,
    bound_lo=None,
    bound_hi=None,
    parent_out=None,
    rand_bins=None,
    bins_minor: bool = False,
) -> BestSplit:
    """Scan one leaf's histogram for the best (feature, bin) split.

    Args:
      hist: f32 ``[F, B, 3]`` per-(feature, bin) sums of (grad, hess, count);
        with ``bins_minor`` the same sums as three planes ``[3, F, B]``, the
        layout the frontier grower keeps its per-node histograms in.  The
        scan itself always runs on the planes: the bin axis it sums along
        is then the array's minor axis, where the chip's tiled HBM layout
        pads 255 bins to 256 lanes instead of 3 statistics to 128.
      ctx: regularization scalars.
      feature_mask: f32/bool ``[F]`` — 1 for usable features this tree
        (feature_fraction sampling; SURVEY.md §2C "Stochasticity").
      depth_ok: bool [] — False disqualifies every split (max_depth cap).
      cat_info: optional :class:`CatInfo`.  Categorical columns take
        category-SET splits by LightGBM's ``FindBestThresholdCategorical``
        (:func:`_categorical_scan`); the set is the left child's.
      mono: optional i32 ``[F]`` per-feature monotone constraints in
        {-1, 0, +1} (upstream ``monotone_constraints``, basic method):
        candidates whose child outputs violate the required ordering are
        rejected; categorical subset splits are disqualified on constrained
        features.
      bound_lo / bound_hi: optional scalar output bounds inherited from
        monotone ancestor splits (basic-method mid-point refinement); child
        outputs are clipped into ``[bound_lo, bound_hi]``.
      parent_out: optional scalar — this node's actual (constrained) output;
        the gain baseline and the path-smoothing anchor.  Defaults to the
        node's unconstrained optimum.
      rand_bins: optional i32 ``[F]`` — when given (``extra_trees``), each
        feature considers ONLY this one randomized threshold position
        (upstream ExtraTrees mode; sklearn ExtraTreesRegressor semantics).

    Returns BestSplit with child statistics AND constrained child outputs so
    the grower can update node state without touching the histogram again.
    """
    if not bins_minor:
        hist = jnp.moveaxis(hist, -1, 0)
    cum = jnp.cumsum(hist, axis=2)                 # [3, F, B] inclusive prefix
    total = cum[:, :, -1:]                         # [3, F, 1]
    lg, lh, lc = cum[0], cum[1], cum[2]
    tg, th, tc = total[0], total[1], total[2]
    rg, rh, rc = tg - lg, th - lh, tc - lc

    lo = jnp.float32(-jnp.inf) if bound_lo is None else bound_lo
    hi = jnp.float32(jnp.inf) if bound_hi is None else bound_hi
    p_out = (leaf_output(tg, th, ctx) if parent_out is None
             else parent_out)                      # [F,1] or scalar
    gain, wl, wr = split_gain_scan(lg, lh, lc, rg, rh, rc, tg, th, ctx,
                                   lo, hi, p_out)  # [F, B]

    valid = (
        split_stats_valid(lc, rc, lh, rh, gain, ctx)
        & (feature_mask[:, None] > 0)
        & depth_ok
    )
    if mono is not None:
        m = mono[:, None].astype(wl.dtype)         # [F, 1]
        valid &= (m == 0) | (m * (wr - wl) >= 0)
    if rand_bins is not None:
        pos_b = jnp.arange(hist.shape[2])[None, :]
        valid &= pos_b == rand_bins[:, None]
    gain = jnp.where(valid, gain, NEG_INF)

    num_features, num_bins = gain.shape

    if cat_info is None:
        flat_idx = jnp.argmax(gain.reshape(-1))
        feat = (flat_idx // num_bins).astype(jnp.int32)
        bin_idx = (flat_idx % num_bins).astype(jnp.int32)
        # The winner's numbers are picked by MASK and sum, not gathered:
        # exactly one cell is kept, so the sum is that cell's value to the
        # bit, and the pick fuses into one more read of the planes.  A
        # gather of the (g, h, c) triple made XLA re-lay the whole batch of
        # cumulative planes with the 3-wide axis minor once the frontier
        # grower vmaps this over its wave (308 MB a wave at Higgs's width,
        # 22 GB at 2,000 features).  The right triple is total - left.
        hit = ((jnp.arange(num_features)[:, None] == feat)
               & (jnp.arange(num_bins)[None, :] == bin_idx))      # [F, B]

        def pick(x):
            return jnp.sum(jnp.where(hit, x, 0.0), axis=(-2, -1))

        win_l = pick(cum)                                 # [3] (g, h, c)
        tot = jnp.sum(jnp.where(jnp.arange(num_features) == feat,
                                total[:, :, 0], 0.0), axis=1)      # [3]
        win_r = tot - win_l
        return BestSplit(
            gain=jnp.max(gain), feature=feat, bin=bin_idx,
            left_g=win_l[0], left_h=win_l[1], left_c=win_l[2],
            right_g=win_r[0], right_h=win_r[1], right_c=win_r[2],
            left_out=pick(wl), right_out=pick(wr))

    with jax.named_scope("lgbtpu.wave.cat_scan"):
        gain_c, cat_pick, cat_mask_at = _categorical_scan(
            hist, total, ctx, feature_mask, depth_ok, cat_info, mono, lo, hi,
            p_out, rand_bins)
    is_cat = cat_info.is_cat
    # categorical columns ONLY take set splits; numeric only thresholds
    gain_all = jnp.where(is_cat[:, None], gain_c, gain)

    flat_idx = jnp.argmax(gain_all.reshape(-1))
    feat = (flat_idx // num_bins).astype(jnp.int32)
    bin_idx = (flat_idx % num_bins).astype(jnp.int32)
    cat_won = is_cat[feat]
    hit = ((jnp.arange(num_features)[:, None] == feat)
           & (jnp.arange(num_bins)[None, :] == bin_idx))          # [F, B]

    def pick(x):
        return jnp.sum(jnp.where(hit, x, 0.0), axis=(-2, -1))

    plain = (lg, lh, lc, rg, rh, rc, wl, wr)
    won = [jnp.where(cat_won, pick(c), pick(jnp.broadcast_to(n, gain.shape)))
           for c, n in zip(cat_pick, plain)]
    return BestSplit(
        gain=jnp.max(gain_all), feature=feat, bin=bin_idx,
        left_g=won[0], left_h=won[1], left_c=won[2],
        right_g=won[3], right_h=won[4], right_c=won[5],
        left_out=won[6], right_out=won[7],
        cat=cat_won, cat_mask=cat_won & cat_mask_at(hit))


def _group_evaluated(cum_c, ok, min_data_per_group):
    """Which positions of LightGBM's sorted categorical scan are evaluated
    (``[F, K]``): a position whose split is allowed (``ok``) once the
    categories added since the last evaluated one hold ``min_data_per_group``
    rows (``cum_c``: the left side's count at each position).  The rule is
    sequential, so it runs as a scan over the ``K`` positions."""
    def step(last, x):
        c, o = x
        ev = o & (c - last >= min_data_per_group)
        return jnp.where(ev, c, last), ev

    _, ev = lax.scan(step, jnp.zeros(cum_c.shape[0], cum_c.dtype),
                     (cum_c.T, ok.T))
    return ev.T


def _categorical_scan(hist, total, ctx: SplitContext, feature_mask,
                      depth_ok, cat_info: CatInfo, mono, lo, hi, p_out,
                      rand_bins):
    """LightGBM's ``FindBestThresholdCategorical`` over every feature's
    bins (planes ``hist [3, F, B]``, their sums ``total [3, F, 1]``), each
    candidate the set of categories that goes LEFT:

    * a feature of at most ``max_cat_to_onehot`` bins, the overflow bin
      counted, tries each category alone against the rest, at
      ``lambda_l2``;
    * any other sorts the categories whose count reaches ``cat_smooth`` by
      ``G / (H + cat_smooth)`` and scans the prefixes of that order from
      both ends (the second direction is the first read backwards), at most
      ``min(max_cat_threshold, (used + 1) // 2)`` categories, at
      ``lambda_l2 + cat_l2``, each side holding ``min_data_in_leaf`` rows
      and ``min_sum_hessian_in_leaf`` and the right one ``min_data_per_group``
      rows, and a candidate evaluated only once ``min_data_per_group`` rows
      came in since the last one (:func:`_group_evaluated`);
    * the overflow bin (rare categories, NaN) is in no set.

    The gain is the children's objective less the node's at ``lambda_l2``
    (LightGBM's ``gain_shift``), so it ranks against numeric splits.
    Departures from LightGBM: counts are the histogram's own (it estimates
    them from the hessian), no ``kEpsilon`` is added to a hessian sum, and
    its exact tie between the two directions (the ascending one wins) is
    taken to ``TIE_RTOL``, the scale of float32 rounding.

    Returns ``(gain [F, B], picks, mask_at)``: the gain of the candidate at
    each (feature, position) (``-inf`` where none), its eight numbers
    ``(lg, lh, lc, rg, rh, rc, wl, wr)`` as ``[F, B]`` arrays, and
    ``mask_at(hit)``, the bins of the candidate at ``hit`` (bool ``[F, B]``,
    one cell) as bool ``[B]``."""
    num_features, num_bins = hist.shape[1], hist.shape[2]
    g_, h_, c_ = hist[0], hist[1], hist[2]                        # [F, B]
    tg, th, tc = total[0], total[1], total[2]                    # [F, 1]
    pos = jnp.arange(num_bins)[None, :]
    cat_bins = (jnp.full((num_features,), num_bins, jnp.int32)
                if cat_info.cat_bins is None else cat_info.cat_bins)
    # LightGBM counts a column's bins with the one of NaN and the rest
    onehot = cat_bins + 1 <= cat_info.max_cat_to_onehot
    in_range = pos < cat_bins[:, None]
    usable = (feature_mask[:, None] > 0) & depth_ok
    if mono is not None:
        # monotonicity is undefined over unordered category sets:
        # constrained features take no set splits (upstream rejects
        # monotone_constraints on categorical columns at parse time)
        usable &= mono[:, None] == 0
    if rand_bins is not None:
        usable &= pos == rand_bins[:, None]
    parent_obj = leaf_objective_at(p_out, tg, th, ctx)

    def gain_at(lg, lh, lc, c):
        rg, rh, rc = tg - lg, th - lh, tc - lc
        wl = constrained_leaf_output(lg, lh, lc, c, lo, hi, p_out)
        wr = constrained_leaf_output(rg, rh, rc, c, lo, hi, p_out)
        gain = (leaf_objective_at(wl, lg, lh, c)
                + leaf_objective_at(wr, rg, rh, c) - parent_obj)
        return gain, (lg, lh, lc, rg, rh, rc, wl, wr)

    # one-vs-rest: bin t alone goes left
    gain_o, stats_o = gain_at(g_, h_, c_, ctx)
    valid_o = (split_stats_valid(c_, stats_o[5], h_, stats_o[4], gain_o, ctx)
               & in_range & usable)
    gain_o = jnp.where(valid_o, gain_o, NEG_INF)

    # the sorted scan over the categories that hold cat_smooth rows
    ctx_cat = ctx._replace(lambda_l2=ctx.lambda_l2 + cat_info.cat_l2)
    elig = in_range & (c_ >= cat_info.cat_smooth)
    used = jnp.sum(elig, axis=1, dtype=jnp.int32)                 # [F]
    ratio = g_ / (h_ + cat_info.cat_smooth)
    # Each order is one sort that carries the bin numbers and the three
    # planes with its key, so the planes come out in order: a gather of a
    # plane by the order cost ~10 ns an element on the chip.  Ascending:
    # the eligible categories by ratio, ties by bin, the rest after them.
    bins = lax.broadcasted_iota(jnp.int32, g_.shape, 1)
    _, *asc = lax.sort((jnp.where(elig, ratio, jnp.inf), bins, g_, h_, c_),
                       dimension=1, num_keys=1, is_stable=True)
    # Descending: the ascending order's eligible prefix read backwards, the
    # rest in place; the key is each sorted place's place in that order
    back = used[:, None] - 1 - pos
    _, *desc = lax.sort((jnp.where(back >= 0, back, pos), *asc),
                        dimension=1, num_keys=1)
    order_asc, order_desc = asc[0], desc[0]
    span = jnp.minimum(used, jnp.minimum(cat_info.max_cat_threshold,
                                         (used + 1) // 2))
    k = min(cat_info.max_cat_threshold, num_bins)
    mdpg = cat_info.min_data_per_group

    def direction(planes):
        cum = [jnp.cumsum(x, axis=1) for x in planes]
        gain_d, stats_d = gain_at(cum[0], cum[1], cum[2], ctx_cat)
        lc, rg, rh, rc = stats_d[2], stats_d[3], stats_d[4], stats_d[5]
        ok = ((lc >= ctx.min_data_in_leaf)
              & (stats_d[1] >= ctx.min_sum_hessian)
              & (rc >= ctx.min_data_in_leaf) & (rc >= mdpg)
              & (rh >= ctx.min_sum_hessian) & (pos < span[:, None]))
        ev = _group_evaluated(lc[:, :k], ok[:, :k], mdpg)
        ev = jnp.pad(ev, ((0, 0), (0, num_bins - k)))
        valid = ev & (gain_d > ctx.min_gain_to_split) & usable
        return jnp.where(valid, gain_d, NEG_INF), stats_d

    gain_a, stats_a = direction(asc[1:])
    gain_d, stats_d = direction(desc[1:])
    # LightGBM scans ascending first and keeps a later candidate only where
    # it gains more.  The two ends reach one partition of a node's
    # categories as mirror sets (left and right swapped), whose gains differ
    # by float32 rounding alone; a descending set wins only where it beats
    # every ascending one by more than TIE_RTOL, or rounding would decide
    # which side the categories that no row holds go to
    best_a = jnp.max(gain_a, axis=1, keepdims=True)
    beat = jnp.where(jnp.isfinite(best_a),
                     best_a + TIE_RTOL * jnp.abs(best_a), NEG_INF)
    gain_d = jnp.where(gain_d > beat, gain_d, NEG_INF)
    use_desc = gain_d > gain_a
    gain_s = jnp.maximum(gain_a, gain_d)
    oh = onehot[:, None]
    gain = jnp.where(oh, gain_o, gain_s)
    picks = tuple(jnp.where(oh, o, jnp.where(use_desc, d, a))
                  for o, a, d in zip(stats_o, stats_a, stats_d))

    def mask_at(hit):
        row = jnp.any(hit, axis=1)                                # [F]
        at = jnp.sum(jnp.where(hit, pos, 0))
        desc = jnp.any(hit & use_desc)
        order_f = jnp.sum(jnp.where(row[:, None],
                                    jnp.where(desc, order_desc, order_asc),
                                    0), axis=0)                   # [B]
        rank = jnp.argsort(order_f)          # each bin's place in the order
        one = jnp.any(row & onehot)
        return jnp.where(one, pos[0] == at, rank <= at)

    return gain, picks, mask_at
