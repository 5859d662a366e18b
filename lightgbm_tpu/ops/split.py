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

from typing import NamedTuple

import jax.numpy as jnp

NEG_INF = -jnp.inf


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
    scan's features: an EFB table's scan reads its member view); the
    scalars mirror upstream ``cat_smooth`` / ``cat_l2`` /
    ``max_cat_threshold`` (cat-specific regularization of the k-vs-rest
    subset search).
    """

    is_cat: jnp.ndarray        # bool [F]
    cat_smooth: jnp.ndarray    # f32 []
    cat_l2: jnp.ndarray        # f32 []
    max_cat_threshold: int     # static


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
      cat_info: optional :class:`CatInfo`.  Categorical columns use
        LightGBM's gradient-ordered k-vs-rest subset search (Fisher 1958
        trick, upstream ``FindBestThresholdCategorical``): bins sort by
        grad/(hess + cat_smooth), the usual prefix scan runs in that order,
        and the winning prefix becomes the left-child category SET.
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

    is_cat = cat_info.is_cat
    # Fisher ordering: bins ranked by grad/(hess + cat_smooth); empty bins
    # push to the end (+/-inf) so prefixes only accumulate populated
    # categories and unseen-at-this-node categories fall to the RIGHT
    # child.  Upstream scans ASCENDING and DESCENDING (each prefix capped
    # at max_cat_threshold), which together reach small-subset partitions
    # on either end of the ordering.
    g_, h_, c_ = hist[0], hist[1], hist[2]
    raw_score = g_ / (h_ + cat_info.cat_smooth)
    pos = jnp.arange(num_bins)[None, :]
    ctx_cat = ctx._replace(lambda_l2=ctx.lambda_l2 + cat_info.cat_l2)
    p_out_cat = (leaf_output(tg, th, ctx_cat) if parent_out is None
                 else parent_out)

    def scan_direction(order):
        hist_s = jnp.take_along_axis(hist, order[None], axis=2)
        cum_s = jnp.cumsum(hist_s, axis=2)
        slg, slh, slc = cum_s[0], cum_s[1], cum_s[2]
        srg, srh, src = tg - slg, th - slh, tc - slc
        gain_c, swl, swr = split_gain_scan(slg, slh, slc, srg, srh, src,
                                           tg, th, ctx_cat, lo, hi,
                                           p_out_cat)
        valid_c = (
            split_stats_valid(slc, src, slh, srh, gain_c, ctx)
            & (feature_mask[:, None] > 0)
            & depth_ok
            & (pos < cat_info.max_cat_threshold)
        )
        if mono is not None:
            # monotonicity is undefined over unordered category sets:
            # constrained features take no subset splits (upstream rejects
            # monotone_constraints on categorical columns at parse time)
            valid_c &= mono[:, None] == 0
        if rand_bins is not None:
            valid_c &= pos == rand_bins[:, None]
        return (jnp.where(valid_c, gain_c, NEG_INF),
                (slg, slh, slc, srg, srh, src, swl, swr))

    order_asc = jnp.argsort(jnp.where(c_ > 0, raw_score, jnp.inf), axis=1)
    order_desc = jnp.argsort(jnp.where(c_ > 0, -raw_score, jnp.inf), axis=1)
    gain_a, stats_a = scan_direction(order_asc)
    gain_d, stats_d = scan_direction(order_desc)
    use_desc = gain_d > gain_a
    gain_c = jnp.maximum(gain_a, gain_d)
    # categorical columns ONLY take subset splits; numeric only thresholds
    gain_all = jnp.where(is_cat[:, None], gain_c, gain)

    flat_idx = jnp.argmax(gain_all.reshape(-1))
    feat = (flat_idx // num_bins).astype(jnp.int32)
    bin_idx = (flat_idx % num_bins).astype(jnp.int32)
    cat_won = is_cat[feat]
    desc_won = use_desc[feat, bin_idx]
    order_f = jnp.where(desc_won, order_desc[feat], order_asc[feat])  # [B]
    inv = jnp.argsort(order_f)                     # rank of each bin
    cat_mask = cat_won & (inv <= bin_idx)          # bool [B]

    def pick(ia, ib, plain):
        cat_val = jnp.where(desc_won, ib[feat, bin_idx], ia[feat, bin_idx])
        return jnp.where(cat_won, cat_val, plain[feat, bin_idx])

    return BestSplit(
        gain=gain_all.reshape(-1)[flat_idx], feature=feat, bin=bin_idx,
        left_g=pick(stats_a[0], stats_d[0], lg),
        left_h=pick(stats_a[1], stats_d[1], lh),
        left_c=pick(stats_a[2], stats_d[2], lc),
        right_g=pick(stats_a[3], stats_d[3], rg),
        right_h=pick(stats_a[4], stats_d[4], rh),
        right_c=pick(stats_a[5], stats_d[5], rc),
        left_out=pick(stats_a[6], stats_d[6], wl),
        right_out=pick(stats_a[7], stats_d[7], wr),
        cat=cat_won, cat_mask=cat_mask)
