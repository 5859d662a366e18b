"""Tensorized leaf-wise (best-first) tree grower.

TPU-native replacement for LightGBM's ``SerialTreeLearner::Train`` (SURVEY.md
§3.1): no leaf objects, no row-index vectors, no OpenMP — the tree is a
struct-of-arrays with a static node capacity ``2*num_leaves - 1``, rows carry a
leaf-id vector updated by gathered split decisions, and growth is a
``lax.fori_loop`` with exactly ``num_leaves - 1`` trips where exhausted trees
execute masked no-ops (SURVEY.md §7 "Dynamic tree growth under static
shapes").

Best-first semantics match LightGBM: each trip splits the single active leaf
with the highest cached split gain.  When a leaf is split, both children's
histograms are built in **one** pass over all rows (segments = {left child,
right child}; other rows contribute nothing), so no per-node histogram storage
and no subtraction trick is needed — under static shapes a one-child pass
costs the same as a two-child pass, and dropping stored histograms keeps
memory at O(num_leaves) scalars per node, which is what lets folds × configs
be vmapped later.

Everything data-dependent stays on device; all regularization thresholds are
traced scalars (vmap-able across hyper-parameter configs).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.histogram import compute_histograms, histogram_merge, histogram_psum
from ..ops.lookup import lookup_rows, lookup_values
from ..ops.members import go_left as member_go_left
from ..ops.members import member_view, split_route
from ..ops.split import (
    BestSplit,
    CatInfo,
    SplitContext,
    constrained_leaf_output,
    find_best_split,
    leaf_output,
)
from ..utils import profiling
from .spec import STRICT, GrowSpec, WaveSchedule

# tools/hlo_counts.py flips this to compile the fused strict grower with
# the split-iteration kernel replaced by an optimization barrier, so the
# CPU HLO counts only the XLA-side launches (the kernel is one TPU
# custom-call but inlines under interpret mode).  Never set in production.
_SPLIT_ITER_OPCOUNT_STUB = False

# The ROLE a histogram pass plays in a round, given to whichever Pallas
# kernel serves it as its name in the compiled program and the device
# trace; a PR that changes what implements a role keeps the name, so the
# benchmark's named metrics still find it.  The stages of a round are
# ``jax.named_scope``s of one vocabulary in both growers: lgbtpu.root,
# lgbtpu.wave.{rank,hist,sibling,scan,commit}, lgbtpu.replay (and
# lgbtpu.grad, lgbtpu.pred_update in models/gbdt.py).
HIST_ROOT = "lgbtpu_hist_root"
HIST_WAVE = "lgbtpu_hist_wave"
# a wave pass of the narrow phase (grow_tree_frontier): its own role, and
# no prefix of the two above, whose metrics count a full-width pass's work
HIST_NARROW = "lgbtpu_hist_narrow"


class Tree(NamedTuple):
    """One tensorized decision tree (node arrays of length 2*num_leaves-1).

    Traversal rule at internal node i: go left iff
    ``bin_code[row, split_feature[i]] <= split_bin[i]`` for numeric splits;
    for categorical k-vs-rest splits (``is_cat_split[i]``) go left iff
    ``cat_mask[i, bin_code[row, split_feature[i]]]``.
    Unused slots have ``is_leaf=False`` and are unreachable.
    """

    split_feature: jnp.ndarray  # i32[M]
    split_bin: jnp.ndarray      # i32[M]
    left: jnp.ndarray           # i32[M]
    right: jnp.ndarray          # i32[M]
    leaf_value: jnp.ndarray     # f32[M] (raw, no shrinkage)
    is_leaf: jnp.ndarray        # bool[M]
    count: jnp.ndarray          # f32[M] rows that reached the node (bagged)
    split_gain: jnp.ndarray     # f32[M] gain of the split at internal nodes
    num_leaves: jnp.ndarray     # i32[] leaves actually grown
    # categorical subset splits — None for datasets without categoricals
    is_cat_split: Optional[jnp.ndarray] = None  # bool[M]
    cat_mask: Optional[jnp.ndarray] = None      # bool[M, B] bins going LEFT
    # linear leaves (upstream linear_tree) — None for constant-leaf models.
    # Prediction at a linear leaf: leaf_value[l] + sum_k coef[l,k] *
    # raw[linear_feat[l,k]] (feat -1 = unused slot; NaN raw imputes 0).
    linear_feat: Optional[jnp.ndarray] = None   # i32[M, K] training columns
    linear_coef: Optional[jnp.ndarray] = None   # f32[M, K]

    @property
    def capacity(self) -> int:
        return self.split_feature.shape[-1]


class _PK:
    """Column layout of the strict grower's packed per-node table.

    The strict grower's per-split bookkeeping used to live in 22 separate
    ``[capacity]`` arrays; at small n the fused-cv sweep is bound by KERNEL
    COUNT, not FLOPs (PERF_HISTORY.md r4 finding 3), and the 15 tiny per-field
    gathers plus ~44 per-field masked scatters per split iteration were
    most of its while-body kernels.  One f32 ``[capacity, NC]`` table makes
    that ONE row gather and THREE row scatters per iteration.  Integer
    fields (node ids <= capacity, feature ids, bin ids <= 256, depth) are
    all exactly representable in f32.
    """

    SPLIT_FEAT = 0    # init -1
    SPLIT_BIN = 1
    LEFT = 2          # init -1
    RIGHT = 3         # init -1
    LEAF_VALUE = 4
    IS_LEAF = 5       # 0/1
    COUNT = 6
    SPLIT_GAIN = 7
    DEPTH = 8
    CAND_GAIN = 9     # init -inf
    CAND_FEAT = 10
    CAND_BIN = 11
    CAND_LG = 12
    CAND_LH = 13
    CAND_LC = 14
    CAND_RG = 15
    CAND_RH = 16
    CAND_RC = 17
    CAND_WL = 18
    CAND_WR = 19
    BOUND_LO = 20     # init -inf
    BOUND_HI = 21     # init +inf
    CAND_CAT = 22     # 0/1 (unused when the dataset has no categoricals)
    PM = 23           # pathmin: min candidate gain over ancestors-or-self
    NC = 24           # (set at creation; drives exact-tail selection)


class _PASS:
    """Columns of the wave grower's pass log, ``f32[NC, grow_leaves - 1]``:
    one column per wave pass (passes on the minor axis: a 5-wide minor
    axis is stored 128 lanes wide on the chip), the root pass not logged;
    columns of passes that did not run stay zero, so ``SPLITS > 0`` marks
    those that did.  Counts are in-bag rows, from the count channel of
    each split parent's chosen candidate.  The loops carry it flat, pass
    after pass, and write a pass as one 5-element slice: the compiled
    round's temporaries grow least that way (v5e, 10.5M x 28: +1.0 MB, for
    +1.5 MB with the ``[5, passes]`` carry and a column write)."""

    ROLE = 0          # 0: narrow (HIST_NARROW), 1: full width (HIST_WAVE)
    SPLITS = 1        # splits the pass made
    STREAMED = 2      # rows the pass's kernel reads
    PARENTS = 3       # rows of the leaves it split: what the partition routes
    DIRECT = 4        # rows of their smaller children: what it histograms
    NC = 5


def _empty_pass_log(grow_leaves: int) -> jnp.ndarray:
    return jnp.zeros((_PASS.NC, grow_leaves - 1), jnp.float32)


class _GrowState(NamedTuple):
    nodes: jnp.ndarray          # f32[M, _PK.NC] packed per-node table
    row_leaf: jnp.ndarray       # i32[n]
    n_nodes: jnp.ndarray        # i32[]
    n_leaves: jnp.ndarray       # i32[]
    done: jnp.ndarray           # bool[]
    # categorical candidate split masks (None when the dataset has none)
    cand_catmask: Optional[jnp.ndarray] = None  # bool[M, B]
    # interaction constraints: surviving group set per node (None = off)
    ic_sets: Optional[jnp.ndarray] = None       # bool[M, NG]


def _write(arr, idx, val, active):
    """Masked scalar write arr[idx] = val if active."""
    return arr.at[idx].set(jnp.where(active, val, arr[idx]))


def _empty_packed_table(capacity: int) -> jnp.ndarray:
    """All-sentinel packed [capacity, _PK.NC] node table (unused slots:
    no children, no candidate, unbounded)."""
    K = _PK
    nodes0 = jnp.zeros((capacity, K.NC), jnp.float32)
    nodes0 = nodes0.at[:, K.SPLIT_FEAT].set(-1.0)
    nodes0 = nodes0.at[:, K.LEFT].set(-1.0)
    nodes0 = nodes0.at[:, K.RIGHT].set(-1.0)
    nodes0 = nodes0.at[:, K.CAND_GAIN].set(-jnp.inf)
    nodes0 = nodes0.at[:, K.BOUND_LO].set(-jnp.inf)
    nodes0 = nodes0.at[:, K.BOUND_HI].set(jnp.inf)
    nodes0 = nodes0.at[:, K.PM].set(-jnp.inf)
    return nodes0


def _packed_root_table(capacity, root_out, root_tot, root_best,
                       cat_info) -> jnp.ndarray:
    """Initial packed [capacity, _PK.NC] node table with the root's row set
    (shared by the strict and frontier growers)."""
    K = _PK
    nodes0 = _empty_packed_table(capacity)
    root_row = jnp.zeros((K.NC,), jnp.float32)
    root_row = root_row.at[jnp.array([
        K.SPLIT_FEAT, K.LEFT, K.RIGHT, K.LEAF_VALUE, K.IS_LEAF, K.COUNT,
        K.CAND_GAIN, K.CAND_FEAT, K.CAND_BIN, K.CAND_LG, K.CAND_LH,
        K.CAND_LC, K.CAND_RG, K.CAND_RH, K.CAND_RC, K.CAND_WL, K.CAND_WR,
        K.BOUND_LO, K.BOUND_HI, K.CAND_CAT, K.PM])].set(jnp.stack([
            jnp.float32(-1.0), jnp.float32(-1.0), jnp.float32(-1.0),
            root_out, jnp.float32(1.0), root_tot[2],
            root_best.gain, root_best.feature.astype(jnp.float32),
            root_best.bin.astype(jnp.float32), root_best.left_g,
            root_best.left_h, root_best.left_c, root_best.right_g,
            root_best.right_h, root_best.right_c, root_best.left_out,
            root_best.right_out, jnp.float32(-jnp.inf),
            jnp.float32(jnp.inf),
            (root_best.cat.astype(jnp.float32) if cat_info is not None
             else jnp.float32(0.0)),
            root_best.gain]))
    return nodes0.at[0].set(root_row)


def _tree_from_packed(P, n_leaves, cat_info, cand_catmask) -> Tree:
    """Unpack the packed node table into the public Tree struct."""
    K = _PK
    is_leaf = P[:, K.IS_LEAF] > 0.5
    left = P[:, K.LEFT].astype(jnp.int32)
    internal = (~is_leaf) & (left >= 0)
    return Tree(
        split_feature=P[:, K.SPLIT_FEAT].astype(jnp.int32),
        split_bin=P[:, K.SPLIT_BIN].astype(jnp.int32),
        left=left,
        right=P[:, K.RIGHT].astype(jnp.int32),
        leaf_value=P[:, K.LEAF_VALUE],
        is_leaf=is_leaf,
        count=P[:, K.COUNT],
        split_gain=P[:, K.SPLIT_GAIN],
        num_leaves=n_leaves,
        is_cat_split=(None if cat_info is None
                      else internal & (P[:, K.CAND_CAT] > 0.5)),
        cat_mask=(None if cat_info is None else cand_catmask),
    )


def _rand_bins_for_node(key, node_id, num_features, num_bins, col_bins):
    """ExtraTrees: one random threshold position per feature per node
    (upstream ``extra_trees``), drawn WITHIN each feature's own used-bin
    range (``col_bins``, the per-training-column bin counts) so
    low-cardinality features keep their full split chance — a global
    [0, num_bins) draw would almost always land outside a binary feature's
    single valid threshold.  Distinct stream from the bynode sampler.
    """
    k = jax.random.fold_in(jax.random.fold_in(key, 0x0EF7), node_id)
    u = jax.random.uniform(k, (num_features,))
    hi = (jnp.asarray(col_bins, jnp.float32) - 1.0 if col_bins is not None
          else jnp.float32(max(num_bins - 1, 1)))
    return jnp.floor(u * jnp.maximum(hi, 1.0)).astype(jnp.int32)


def _ic_allowed(group_sets, member):
    """Interaction constraints: allowed-feature mask for nodes.

    ``group_sets`` bool [..., NG] — which constraint groups the node's
    path-used feature set still fits inside (upstream col_sampler's
    interaction-constraint tracking, re-derived as a set recurrence:
    ``S_child = {G in S_node : split_feature in G}``).  ``member`` bool
    [NG, F].  Allowed features = union of the surviving groups — one
    boolean matmul."""
    return (group_sets.astype(jnp.float32) @ member.astype(jnp.float32)
            > 0.5).astype(jnp.float32)


def _mono_child_bounds(mono, feat, wl, wr, lo, hi):
    """Basic-method monotone bounds for a split's children (upstream
    LeafConstraintsBase 'basic'): descendants on the low side of an
    increasing split are capped at the split's output mid-point, and vice
    versa.  Shapes follow (feat, wl, wr, lo, hi) — scalar in the strict
    grower, [W] vectors in the frontier grower."""
    if mono is None:
        return lo, hi, lo, hi
    mval = mono[feat]
    mid = 0.5 * (wl + wr)
    hi_l = jnp.where(mval > 0, jnp.minimum(hi, mid), hi)
    lo_l = jnp.where(mval < 0, jnp.maximum(lo, mid), lo)
    lo_r = jnp.where(mval > 0, jnp.maximum(lo, mid), lo)
    hi_r = jnp.where(mval < 0, jnp.minimum(hi, mid), hi)
    return lo_l, hi_l, lo_r, hi_r


def _fp_reduce_best(bs: BestSplit, axis_name: str,
                    f_local: int) -> BestSplit:
    """Feature-parallel combine: each shard found the best split over its
    OWN feature slice; all-gather the per-shard winners, take the global
    argmax, and globalize the winning feature index (upstream
    FeatureParallelTreeLearner's split exchange — one tiny allgather
    instead of allreducing full histograms).  Shared with the data-parallel
    reduce-scatter/voting merge modes — single source lives in
    parallel.feature_parallel (imported lazily: that module imports
    models.gbdt at load time)."""
    from ..parallel.feature_parallel import reduce_best_split

    return reduce_best_split(bs, axis_name, f_local)


def _fp_column(bins_local: jnp.ndarray, feat_global, axis_name: str,
               f_local: int) -> jnp.ndarray:
    """Fetch the GLOBAL feature column under feature sharding: only the
    owning shard has it, so it contributes the codes and a psum broadcasts
    them (the [n] bitmap exchange of upstream's feature-parallel split)."""
    from ..parallel.feature_parallel import broadcast_feature_column

    return broadcast_feature_column(bins_local, feat_global, axis_name,
                                    f_local)


def _make_dist_scorer(axis_name: str, hist_merge: str, n_shards: int,
                      num_features: int, ctx, cat_info, mono, voting_k: int,
                      merge_chunks: int = 1):
    """Build the batched split scorer for the distributed histogram-merge
    modes (``reduce_scatter`` / ``reduce_scatter_ring`` /
    ``reduce_scatter_pipelined`` / ``voting``).

    Returns ``score(hist_s, masks, depth_ok_s, lo_s, hi_s, po_s, rand_s)
    -> BestSplit`` batched over the leading segment axis, with GLOBAL
    feature ids (the per-shard winners are combined through the same
    all-gather + argmax exchange the feature-parallel learner uses —
    :func:`~lightgbm_tpu.parallel.feature_parallel.reduce_best_split`).

    ``hist_s`` is the merged ``[S, F_pad/D, B, 3]`` feature SLICE under
    reduce-scatter, or the LOCAL unmerged ``[S, F, B, 3]`` partials under
    voting (the ballot and the candidate-union merge both happen here).
    All other per-feature arguments stay GLOBAL ``[.., F]`` — the scorer
    slices them to match, so monotone/categorical/extra-trees/interaction
    masks need no caller-side changes.  Because every shard holds
    contiguous ascending feature ranges, the cross-shard argmax preserves
    the serial scan's first-occurrence tie-break (lowest shard = lowest
    global feature id), which is what makes reduce-scatter mode
    serial-parity-exact.

    Under ``reduce_scatter_pipelined`` the scorer consumes the slice in
    ``merge_chunks`` static sub-chunks (the units the chunked ring lands):
    each chunk is scanned by its own ``find_best_split`` call the moment
    the slice-of-concat dataflow makes it available — XLA's async
    scheduler can then run chunk ``k``'s ring hops behind chunk ``k−1``'s
    scan — and the per-chunk winners combine with a first-occurrence
    argmax over the chunk axis (lowest chunk = lowest feature id, so the
    serial tie-break survives chunking too).
    """
    from ..ops.histogram import merge_slice_width
    from ..ops.split import feature_best_gains
    from ..parallel.feature_parallel import reduce_best_split

    rs = hist_merge in ("reduce_scatter", "reduce_scatter_ring",
                        "reduce_scatter_pipelined")
    chunks = (max(int(merge_chunks), 1)
              if hist_merge == "reduce_scatter_pipelined" else 1)
    f_loc = merge_slice_width(num_features, n_shards, hist_merge, chunks)
    f_pad = f_loc * n_shards

    def pad_f(a, axis, value):
        if f_pad == num_features:
            return a
        pads = [(0, 0)] * a.ndim
        pads[axis] = (0, f_pad - num_features)
        return jnp.pad(a, pads, constant_values=value)

    def fslice(a, axis, value=0):
        start = lax.axis_index(axis_name) * f_loc
        return lax.dynamic_slice_in_dim(pad_f(a, axis, value), start, f_loc,
                                        axis=axis)

    if rs:
        # static per-feature config arrays slice ONCE; padded tail columns
        # carry mask 0 / mono 0 / is_cat False so a ragged last shard (or a
        # fully-padded shard when D > F) scores every pad slot -inf
        cat_l = (None if cat_info is None else cat_info.per_feature(
            lambda a: fslice(a, 0, 0)))
        mono_l = None if mono is None else fslice(mono, 0, 0)
        sub = f_loc // chunks           # divisible by construction

        def csl(a, axis, c):            # static chunk window c of a slice
            return lax.slice_in_dim(a, c * sub, (c + 1) * sub, axis=axis)

        def score(hist_s, masks, depth_ok_s, lo_s, hi_s, po_s, rand_s=None):
            masks_l = fslice(masks, 1, 0.0)
            rand_l = None if rand_s is None else fslice(rand_s, 1, 0)
            per_chunk = []
            for c in range(chunks):
                cat_c = (None if cat_l is None else cat_l.per_feature(
                    lambda a, c=c: csl(a, 0, c)))
                mono_c = None if mono_l is None else csl(mono_l, 0, c)
                if rand_l is None:
                    def one(h, m, d, lo, hi, po,
                            cat_c=cat_c, mono_c=mono_c):
                        return find_best_split(h, ctx, m, d, cat_c, mono_c,
                                               lo, hi, po)

                    bs = jax.vmap(one)(csl(hist_s, 1, c), csl(masks_l, 1, c),
                                       depth_ok_s, lo_s, hi_s, po_s)
                else:
                    def one(h, m, d, lo, hi, po, rb,
                            cat_c=cat_c, mono_c=mono_c):
                        return find_best_split(h, ctx, m, d, cat_c, mono_c,
                                               lo, hi, po, rb)

                    bs = jax.vmap(one)(csl(hist_s, 1, c), csl(masks_l, 1, c),
                                       depth_ok_s, lo_s, hi_s, po_s,
                                       csl(rand_l, 1, c))
                if c:
                    bs = bs._replace(feature=bs.feature + c * sub)
                per_chunk.append(bs)
            if chunks == 1:
                bs = per_chunk[0]
            else:
                # first-occurrence argmax over the chunk axis: gain ties
                # resolve to the lowest chunk, hence the lowest global
                # feature id — the serial scan's tie-break, preserved
                stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                                       *per_chunk)
                win = jnp.argmax(stacked.gain, axis=0)
                bs = jax.tree.map(
                    lambda x: jax.vmap(lambda xc, w: xc[w],
                                       in_axes=(1, 0))(x, win), stacked)
            return jax.vmap(
                lambda b: reduce_best_split(b, axis_name, f_loc))(bs)

        return score

    # ---- voting merge (PV-Tree / upstream VotingParallelTreeLearner) ----
    # Each shard nominates its local top-k features by LOCAL gain; the
    # global candidate set is the top-(2k) by vote count, and only those
    # columns are reduce-scattered.  Approximate by construction (a
    # feature strong globally but nowhere locally top-k is never merged);
    # when 2k >= F the union is exact and the result matches reduce-scatter
    # (minus candidate ORDER, so the exact-union short-circuit below keeps
    # ascending ids for strict parity).
    k_top = max(1, min(int(voting_k) if voting_k else 20, num_features))
    kc = min(2 * k_top, num_features)
    kc_pad = -(-kc // n_shards) * n_shards
    kc_loc = kc_pad // n_shards
    exact_union = kc == num_features

    def one_vote(h_local, m, d, lo, hi, po, rb):
        if exact_union:
            cand_ids = lax.iota(jnp.int32, kc)
        else:
            g_loc = feature_best_gains(h_local, ctx, m, d, mono=mono,
                                       bound_lo=lo, bound_hi=hi,
                                       parent_out=po, rand_bins=rb)
            kth = -jnp.sort(-g_loc)[k_top - 1]
            local_top = jnp.isfinite(g_loc) & (g_loc >= kth)
            votes = lax.psum(local_top.astype(jnp.float32), axis_name)
            # stable argsort of -votes: vote ties resolve to the lower
            # feature id on every shard identically
            cand_ids = jnp.argsort(-votes, stable=True)[:kc].astype(
                jnp.int32)
        cand_hist = jnp.take(h_local, cand_ids, axis=0)       # [kc, B, 3]
        if kc_pad != kc:
            cand_hist = jnp.pad(cand_hist,
                                ((0, kc_pad - kc), (0, 0), (0, 0)))
            cand_ids = jnp.pad(cand_ids, (0, kc_pad - kc))
        merged = lax.psum_scatter(cand_hist, axis_name,
                                  scatter_dimension=0, tiled=True)
        shard = lax.axis_index(axis_name)
        ids_l = lax.dynamic_slice_in_dim(cand_ids, shard * kc_loc, kc_loc)
        slot = shard * kc_loc + lax.iota(jnp.int32, kc_loc)
        valid = slot < kc               # pad slots: zero hist, masked out
        m_l = jnp.where(valid, m[ids_l], 0.0)
        mono_l2 = None if mono is None else jnp.where(valid, mono[ids_l], 0)
        rb_l = None if rb is None else rb[ids_l]
        bs = find_best_split(merged, ctx, m_l, d, None, mono_l2, lo, hi,
                             po, rb_l)
        return reduce_best_split(bs, axis_name, kc_loc, feature_map=ids_l)

    def score(hist_s, masks, depth_ok_s, lo_s, hi_s, po_s, rand_s=None):
        if rand_s is None:
            def onev(h, m, d, lo, hi, po):
                return one_vote(h, m, d, lo, hi, po, None)

            return jax.vmap(onev)(hist_s, masks, depth_ok_s, lo_s, hi_s,
                                  po_s)
        return jax.vmap(one_vote)(hist_s, masks, depth_ok_s, lo_s, hi_s,
                                  po_s, rand_s)

    return score


def renew_leaf_values(tree: Tree, row_leaf: jnp.ndarray, residual: jnp.ndarray,
                      weight: jnp.ndarray, alpha) -> Tree:
    """Refit leaf values as weighted alpha-quantiles of the residuals.

    TPU-native equivalent of LightGBM's ``RegressionL1loss::RenewTreeOutput``
    (and the quantile variant): the Newton step is a poor leaf estimator for
    L1/quantile losses, so after the tree structure is fixed each leaf's
    value is replaced by the weighted alpha-quantile (alpha=0.5 -> weighted
    median) of ``residual`` over its rows.

    Formulation without per-leaf loops: one global sort of rows by residual,
    one stable sort by leaf id, then every leaf's quantile is found with a
    vectorized ``searchsorted`` on the global cumulative-weight vector.
    Zero-weight rows (padding, bagged-out) advance no cumulative weight and
    therefore never become a quantile.  O(n log n) VPU work, off the MXU
    hot loop, only traced in when the objective requests renewal.
    """
    capacity = tree.leaf_value.shape[-1]
    alpha = jnp.float32(alpha)
    order = jnp.argsort(residual)
    leaf_o = row_leaf[order]
    order2 = jnp.argsort(leaf_o, stable=True)
    perm = order[order2]
    leaf_s = row_leaf[perm]
    r_s = residual[perm]
    w_s = weight[perm]
    cw = jnp.cumsum(w_s)
    # per-leaf row spans via binary search on the (sorted) leaf ids — no
    # [n, capacity] one-hot materialization
    ids = lax.iota(jnp.int32, capacity)
    starts = jnp.searchsorted(leaf_s, ids, side="left")
    ends = jnp.searchsorted(leaf_s, ids, side="right")
    cw0 = jnp.concatenate([jnp.zeros(1), cw])
    w_before = cw0[starts]
    totals = cw0[ends] - w_before
    target = w_before + alpha * totals
    idx = jnp.clip(jnp.searchsorted(cw, target, side="left"), 0,
                   r_s.shape[0] - 1)
    quant = r_s[idx]
    new_vals = jnp.where((totals > 0) & tree.is_leaf, quant,
                         tree.leaf_value)
    return tree._replace(leaf_value=new_vals)


def pad_tree(tree: Tree, capacity: int) -> Tree:
    """Pad a tree's node arrays (last axis) up to ``capacity`` slots.

    Used when stacking forests of mixed ``num_leaves`` — e.g. an
    ``init_model`` continuation trained with a different leaf budget.  Padded
    slots are unreachable (no node points at them) and carry the grower's
    unused-slot sentinels: is_leaf=False, children=-1, zero values — so
    downstream used-node masks (``~is_leaf & (left >= 0)``) stay correct.
    """
    m = tree.split_feature.shape[-1]
    if m == capacity:
        return tree
    if m > capacity:
        raise ValueError(f"cannot shrink tree capacity {m} -> {capacity}")
    pad = [(0, 0)] * (tree.split_feature.ndim - 1) + [(0, capacity - m)]

    def p(a, val=0):
        return jnp.pad(a, pad, constant_values=val)

    def p_node2(a, val=False):
        """Pad the NODE axis of a [..., M, B] array (cat_mask)."""
        pads = [(0, 0)] * a.ndim
        pads[-2] = (0, capacity - m)
        return jnp.pad(a, pads, constant_values=val)

    return Tree(
        split_feature=p(tree.split_feature), split_bin=p(tree.split_bin),
        left=p(tree.left, -1), right=p(tree.right, -1),
        leaf_value=p(tree.leaf_value), is_leaf=p(tree.is_leaf, False),
        count=p(tree.count), split_gain=p(tree.split_gain),
        num_leaves=tree.num_leaves,
        is_cat_split=(None if tree.is_cat_split is None
                      else p(tree.is_cat_split, False)),
        cat_mask=(None if tree.cat_mask is None
                  else p_node2(tree.cat_mask)),
        linear_feat=(None if tree.linear_feat is None
                     else p_node2(tree.linear_feat, -1)),
        linear_coef=(None if tree.linear_coef is None
                     else p_node2(tree.linear_coef, 0.0)))


def build_cat_info(cat_key, cats):
    """Static ``GrowSpec.cat_key`` and the operand ``cats``
    (``ops.split.CatColumns``: which columns, their bins) -> traced
    CatInfo; ``None`` where the key is.  The key holds the scan's scalars
    alone (``models.spec.categorical_key``), so every order of a table's
    columns is one program."""
    if cat_key is None:
        return None
    if cats is None:
        raise ValueError("a categorical spec needs its columns: pass the "
                         "table's ops.split.CatColumns as cats")
    smooth, l2, mct, to_onehot, mdpg = cat_key
    return CatInfo(is_cat=cats.is_cat, cat_smooth=jnp.float32(smooth),
                   cat_l2=jnp.float32(l2), max_cat_threshold=int(mct),
                   cat_bins=cats.cat_bins, max_cat_to_onehot=int(to_onehot),
                   min_data_per_group=jnp.float32(mdpg))


def grower_from_spec(spec: GrowSpec, **placement):
    """The ONE place a :class:`GrowSpec` is mapped onto :func:`grow_tree`.

    Returns ``grow(bins, stats, feature_mask, ctx, max_depth, ff_bynode,
    key) -> (Tree, row_leaf, passes)``, ``passes`` the wave grower's pass
    log (:class:`_PASS`; all zero from the strict grower): the fused round
    program keeps it, every other caller drops it.  ``placement`` is what
    the learner, not the spec, decides and goes to ``grow_tree`` as it is:
    ``axis_name``, ``fp_axis``, ``fuse_partition`` and the merge settings.
    Call it where the round program is BUILT: the per-column constraint
    arrays become constants the traced bodies close over.
    ``cats`` (an operand: ``ops.split.CatColumns``) are the categorical
    columns of ``bins`` (a feature shard's, where the features are
    sharded), given wherever ``spec.cat_key`` is.
    ``members`` (``ops.members.Members``, an operand of the caller's
    program) grows on an EFB table's bundle columns; every per-feature key
    of the spec is over the original features.  ``col_order`` (an operand
    too: ``ops.histogram_pallas.onehot_order`` of the table's columns)
    puts the partition-fused kernels' feature rows in the order of
    ``spec.onehot_rows``; without it every one-hot is ``num_bins`` tall.
    """
    mono = (None if spec.mono_key is None
            else jnp.asarray(spec.mono_key, jnp.int32))
    col_bins = (None if spec.nbins_key is None
                else jnp.asarray(spec.nbins_key, jnp.int32))
    ic_member = (None if spec.ic_key is None
                 else jnp.asarray(spec.ic_key, bool))

    def grow(bins, stats, feature_mask, ctx, max_depth, ff_bynode, key,
             members=None, col_order=None, cats=None):
        return grow_tree_logged(
            bins, stats, feature_mask, ctx, spec.num_leaves, spec.num_bins,
            max_depth, ff_bynode=None if spec.bynode_off else ff_bynode,
            key=key, hist_impl=spec.hist_impl, row_chunk=spec.row_chunk,
            hist_dtype=spec.hist_dtype, wave=spec.wave,
            cat_info=build_cat_info(spec.cat_key, cats),
            mono=mono, extra_trees=spec.extra_trees, col_bins=col_bins,
            ic_member=ic_member, members=members,
            onehot_rows=spec.onehot_rows, col_order=col_order, **placement)

    return grow


def grow_tree(*args, **kwargs) -> Tuple[Tree, jnp.ndarray]:
    """:func:`grow_tree_logged` without the pass log: ``(Tree, row_leaf)``."""
    return grow_tree_logged(*args, **kwargs)[:2]


def grow_tree_logged(
    bins: jnp.ndarray,
    stats: jnp.ndarray,
    feature_mask: jnp.ndarray,
    ctx: SplitContext,
    num_leaves: int,
    num_bins: int,
    max_depth,
    ff_bynode=None,
    key: Optional[jnp.ndarray] = None,
    axis_name: Optional[str] = None,
    hist_impl: str = "auto",
    row_chunk: int = 131072,
    hist_dtype: str = "f32",
    wave: WaveSchedule = STRICT,
    cat_info=None,
    fp_axis: Optional[str] = None,
    mono=None,
    extra_trees: bool = False,
    col_bins=None,
    ic_member=None,
    fuse_partition: bool = False,
    fuse_split: bool = True,
    hist_merge: str = "psum",
    n_shards: int = 1,
    voting_k: int = 0,
    hist_wire: str = "f32",
    merge_chunks: int = 4,
    members=None,
    onehot_rows: Optional[tuple] = None,
    col_order: Optional[jnp.ndarray] = None,
) -> Tuple[Tree, jnp.ndarray, jnp.ndarray]:
    """Grow one best-first tree.

    Args:
      bins: uint8/int32 ``[n, F]`` binned features (full, static shape; rows
        not in this tree's bag simply carry zero stats).
      stats: f32 ``[n, 3]`` of (grad, hess, in-bag indicator).  grad/hess must
        already include sample weights and bagging mask; padding rows all-zero.
      feature_mask: f32 ``[F]`` — 1 for features usable this tree.
      ctx: traced regularization scalars.
      num_leaves: static leaf budget (r/gridsearchCV.R:96 grid axis).
      num_bins: static histogram bin-axis size.
      max_depth: traced i32; <= 0 means unlimited (LightGBM default -1).
      ff_bynode: traced per-node feature-sampling fraction (LightGBM
        ``feature_fraction_bynode`` — sklearn RandomForest's per-split
        ``max_features``); None/1.0 disables sampling.
      key: PRNG key for per-node sampling (folded with the node id, so the
        sampled set differs per node but is deterministic under the seed).
      axis_name: if set, per-shard histograms are psum-merged over this mesh
        axis — the data-parallel tree learner (SURVEY.md §2C).
      mono: optional i32 ``[F]`` monotone constraints in {-1, 0, +1}
        (upstream ``monotone_constraints``, basic method: violating splits
        rejected, descendants clipped at the split's output mid-point).
      extra_trees: ExtraTrees randomization (upstream ``extra_trees``) —
        each node considers ONE random threshold per feature, drawn
        deterministically from ``key`` and the node id within the
        feature's own used-bin range (``col_bins``).
      col_bins: optional i32 ``[F]`` per-training-column used-bin counts
        (BinMapper.n_bins / EFB col_bins) bounding the extra_trees draw.
      fuse_split: run each strict split iteration as ONE Pallas call
        (:func:`~lightgbm_tpu.ops.histogram_pallas.split_iter_pallas`:
        cumsum gain scan + argmax + winner gather + packed-table update
        in VMEM) instead of the ~49-fusion XLA body.  Engages only on
        the plain numeric path (no categorical/monotone/extra-trees/
        interaction/bynode-sampling/feature-parallel).  On the CPU the
        two paths are bitwise identical (tests/test_split_iter_fused.py);
        compiled for the chip the kernel's prefix sums add in another
        order, and ``chip_smoke.py`` holds it to the SAME tree as
        ``fuse_split=False`` at 1M rows (values within 1e-5).
      hist_merge: how per-shard histogram partials combine under
        ``axis_name`` (see :func:`~lightgbm_tpu.ops.histogram.
        histogram_merge`): ``"psum"`` (full allreduce, the r0 baseline),
        ``"reduce_scatter"`` / ``"reduce_scatter_ring"`` (each shard
        receives only its ``F/D`` feature slice and scans splits over it
        — LightGBM's data-parallel Reduce-Scatter topology, 1/D the comm
        bytes, serial-parity-exact), or ``"voting"`` (PV-Tree: shards
        nominate local top-k features, only the voted candidate union is
        merged — approximate, cheapest).  ``n_shards`` must give the
        static mesh-axis size for the non-psum modes; ``voting_k`` is
        the per-shard ballot size (top-2k candidates merge globally).
        ``"reduce_scatter_pipelined"`` splits the ring into
        ``merge_chunks`` sub-rings whose hops interleave with the
        per-chunk split scans (r10 comm/compute overlap); ``hist_wire``
        (``"f32"``/``"bf16"``/``"int8"``) compresses ring-hop messages —
        f32 keeps the exactness bar, bf16/int8 are quality-gated.
      members: ``ops.members.Members`` when ``bins`` holds an EFB table's
        bundle columns: histograms stay in bundle space, the scan reads the
        member view (``feature_mask``, ``mono``, ``col_bins``, ``ic_member``
        and ``cat_info`` are over the original features) and a split is
        (original feature, its own bin), routed by range.  The mesh
        learners take no bundled table.
      onehot_rows, col_order: the partition-fused kernels' feature rows
        by the height of their one-hot: static, the sorted heights
        (:func:`~lightgbm_tpu.ops.histogram_pallas.onehot_heights`), and
        traced, ``i32[C]`` the column of each row
        (:func:`~lightgbm_tpu.ops.histogram_pallas.onehot_order`).  Either
        ``None``, or heights of another length (a screened round's
        compacted view), keeps ``num_bins`` for every column.

    Returns:
      (Tree, row_leaf, passes) — row_leaf gives each training row's final
      leaf node id so the boosting loop can update train predictions with
      one gather; ``passes`` is the wave grower's pass log (:class:`_PASS`),
      all zero from the strict grower.

    A ``wave`` of width > 1 dispatches to :func:`grow_tree_frontier`
    (multiple splits per histogram pass via the subtraction trick — the
    large-data fast path); its tail policy is the schedule's
    (:func:`~lightgbm_tpu.models.spec.resolve_wave`).
    """
    if members is not None and (axis_name is not None or fp_axis is not None):
        raise ValueError(
            "the mesh learners do not take an EFB-bundled table: construct "
            "the Dataset with params={'enable_bundle': False}")
    if wave.width > 1 and not (fp_axis is not None and cat_info is not None):
        # (frontier + feature-parallel since r5; categorical k-vs-rest
        # splits under fp keep the strict grower's psum-broadcast path)
        return grow_tree_frontier(
            bins, stats, feature_mask, ctx, num_leaves, num_bins, max_depth,
            wave, ff_bynode=ff_bynode, key=key, axis_name=axis_name,
            hist_impl=hist_impl, row_chunk=row_chunk, hist_dtype=hist_dtype,
            cat_info=cat_info, mono=mono, extra_trees=extra_trees,
            col_bins=col_bins, ic_member=ic_member, fp_axis=fp_axis,
            fuse_partition=fuse_partition, hist_merge=hist_merge,
            n_shards=n_shards, voting_k=voting_k, hist_wire=hist_wire,
            merge_chunks=merge_chunks, members=members,
            onehot_rows=onehot_rows, col_order=col_order)
    if cat_info is not None:
        # the strict grower routes category sets in XLA
        profiling.note("train.cat_route", "xla")
    n, num_features = bins.shape
    if members is not None:
        num_features = members.num_features
    capacity = 2 * num_leaves - 1
    max_depth = jnp.asarray(max_depth, jnp.int32)
    neg_inf = jnp.float32(-jnp.inf)
    if key is None:
        key = jax.random.PRNGKey(0)
    bynode_off = ff_bynode is None   # static: skip the per-node RNG draw

    if axis_name is None:
        hist_merge = "psum"          # single-shard: nothing to merge
    dist_mode = hist_merge != "psum"
    if dist_mode and fp_axis is not None:
        raise ValueError(
            f"hist_merge={hist_merge!r} is a data-parallel merge topology "
            "and cannot compose with feature sharding (fp_axis) — the 2-D "
            "dp x fp mesh keeps the psum merge")
    if hist_merge == "voting" and cat_info is not None:
        raise ValueError(
            "hist_merge='voting' does not support categorical splits (the "
            "local ballot scans numeric thresholds only) — use "
            "'reduce_scatter' or 'psum'")
    score_dist = (_make_dist_scorer(axis_name, hist_merge, n_shards,
                                    num_features, ctx, cat_info, mono,
                                    voting_k, merge_chunks)
                  if dist_mode else None)

    # Split-iteration mega-kernel gate (ops.histogram_pallas
    # ._split_iter_kernel): the ~49-fusion tail of each split iteration —
    # gain scan, argmax, winner gather, three node-table row writes, and
    # the NEXT iteration's leaf pick — collapses into one pallas call.
    # Static eligibility mirrors what the kernel traces: no categorical
    # subset scan, no monotone bounds, no per-node RNG (bynode sampling /
    # extra_trees), no interaction-constraint set recurrence, and no
    # feature sharding (the winner must be globalized OUTSIDE the kernel).
    # In interpret mode numerics are bitwise identical to the XLA body by
    # construction (the shared ops.split.split_gain_scan helper + first-
    # occurrence argmax); ``fuse_split=False`` keeps the reference XLA
    # body — what tests and chip_smoke.py compare the kernel with.
    fuse_si = (fuse_split and cat_info is None and mono is None
               and not extra_trees and ic_member is None and bynode_off
               and fp_axis is None and not dist_mode and members is None)

    # per-node column subsample: the ONE shared mask-composition layer
    # (models.feature_mask, r20) — bynode draws WITHIN the tree mask,
    # which under screening is already compacted to the active set
    from .feature_mask import node_mask_fn

    node_feature_mask = node_mask_fn(key, ff_bynode, num_features,
                                     feature_mask, bynode_off)

    def node_rand_bins(node_id):
        if not extra_trees:
            return None
        return _rand_bins_for_node(key, node_id, num_features, num_bins,
                                   col_bins)

    def hist_fn(seg_id, num_segments, role):
        # custom-vmap op: under fold/config/class batching, calls sharing
        # this binned matrix collapse into ONE wide-matmul pass instead of
        # per-element skinny matmuls (memory-bound otherwise).  ``role``
        # names the kernel for the trace (HIST_ROOT / HIST_WAVE).
        from ..ops.histogram import batched_histogram_op

        op = batched_histogram_op(num_segments, num_bins, row_chunk,
                                  hist_impl, hist_dtype, role)
        h = op(bins, stats, seg_id)
        if hist_merge == "voting":
            return h       # local partials; the scorer merges candidates
        return histogram_merge(h, axis_name, mode=hist_merge,
                               n_shards=n_shards, wire_dtype=hist_wire,
                               n_chunks=merge_chunks)

    def scan_view(h):
        """``[..., F, B, 3]`` -> what the scan reads and its layout flag:
        the member view's planes for a bundled table, else as it is."""
        if members is None:
            return h, False
        with jax.named_scope("lgbtpu.wave.members"):
            return member_view(jnp.moveaxis(h, -1, -3), members), True

    # ---- root -------------------------------------------------------------
    # under rs the merged root_hist is this shard's [F_pad/D, B, 3] slice;
    # under voting the LOCAL unmerged partial
    with jax.named_scope("lgbtpu.root"):
        root_hist = hist_fn(jnp.zeros(n, jnp.int32), 1,
                            HIST_ROOT)[0]                    # [F, B, 3]
        if dist_mode:
            # global totals without the full histogram: stats rows sum to the
            # histogram totals by construction, so one [3]-element psum
            # replaces reading bins of feature 0 from a (now sliced) histogram
            root_tot = lax.psum(jnp.sum(stats, axis=0), axis_name)
        else:
            root_tot = jnp.sum(root_hist[0], axis=0)                 # (g, h, c)
        # root output: unsmoothed (no parent), but still max_delta_step-capped
        root_out = constrained_leaf_output(
            root_tot[0], root_tot[1], root_tot[2],
            ctx._replace(path_smooth=jnp.float32(0.0)),
            jnp.float32(-jnp.inf), jnp.float32(jnp.inf), jnp.float32(0.0))
        if ic_member is not None:
            ng = ic_member.shape[0]
            root_sets = jnp.ones((ng,), bool)
            root_mask = node_feature_mask(0) * _ic_allowed(root_sets, ic_member)
        else:
            root_mask = node_feature_mask(0)
        # LightGBM convention: max_depth <= 0 means unlimited, so the root
        # (depth 0) is always splittable — if a limit exists it is >= 1.
        if dist_mode:
            rb0 = node_rand_bins(0)
            root_best = jax.tree.map(lambda x: x[0], score_dist(
                root_hist[None], root_mask[None], jnp.ones((1,), bool),
                jnp.full((1,), -jnp.inf, jnp.float32),
                jnp.full((1,), jnp.inf, jnp.float32), root_out[None],
                None if rb0 is None else rb0[None]))
        else:
            root_view, minor = scan_view(root_hist)
            root_best = find_best_split(root_view, ctx, root_mask,
                                        jnp.bool_(True), cat_info, mono=mono,
                                        parent_out=root_out,
                                        rand_bins=node_rand_bins(0),
                                        bins_minor=minor)
        if fp_axis is not None:
            root_best = _fp_reduce_best(root_best, fp_axis, num_features)

    K = _PK
    st = _GrowState(
        nodes=_packed_root_table(capacity, root_out, root_tot, root_best,
                                 cat_info),
        row_leaf=jnp.zeros(n, jnp.int32),
        n_nodes=jnp.int32(1),
        n_leaves=jnp.int32(1),
        done=jnp.bool_(False),
        cand_catmask=(None if cat_info is None else
                      jnp.zeros((capacity, num_bins), jnp.bool_)
                      .at[0].set(root_best.cat_mask)),
        ic_sets=(None if ic_member is None else
                 jnp.zeros((capacity, ic_member.shape[0]), bool)
                 .at[0].set(True)),
    )

    bins_i32 = bins.astype(jnp.int32)

    if fuse_si:
        from ..ops.histogram_pallas import split_iter_pallas  # noqa: F401

        f32 = jnp.float32
        zero = jnp.float32(0.0)
        # aux carries the pick the NEXT iteration acts on; the root pick
        # reproduces iteration 0's argmax (only node 0 is a leaf, so the
        # picked leaf is 0 and its gain is the root candidate's)
        aux0 = jnp.stack([
            zero, root_best.feature.astype(f32), root_best.bin.astype(f32),
            jnp.isfinite(root_best.gain).astype(f32),
            zero, zero, zero, zero]).reshape(1, 8)
        fmask_row = feature_mask.astype(f32).reshape(1, num_features)
        md_f = max_depth.astype(f32)

        def body_f(_, carry):
            P, row_leaf_c, n_nodes, n_leaves, aux = carry
            leaf = aux[0, 0].astype(jnp.int32)
            feat = aux[0, 1].astype(jnp.int32)
            thr = aux[0, 2].astype(jnp.int32)
            active = aux[0, 3] > 0
            with jax.named_scope("lgbtpu.wave.hist"):
                nl, nr = n_nodes, n_nodes + 1
                # partition + segment select stay in XLA (they touch the [n]
                # row axis); everything table-sized moves into the kernel
                col = jnp.take(bins_i32, feat, axis=1)
                go_left = col <= thr
                new_rl = jnp.where(row_leaf_c == leaf,
                                   jnp.where(go_left, nl, nr), row_leaf_c)
                row_leaf2 = jnp.where(active, new_rl, row_leaf_c)
                seg = jnp.where(row_leaf2 == nl, 0,
                                jnp.where(row_leaf2 == nr, 1, 2)).astype(
                                    jnp.int32)
                hist2 = hist_fn(seg, 2, HIST_WAVE)           # [2, F, B, 3]
            with jax.named_scope("lgbtpu.wave.scan"):
                scal = jnp.stack([
                    jnp.asarray(ctx.lambda_l1, f32),
                    jnp.asarray(ctx.lambda_l2, f32),
                    jnp.asarray(ctx.min_data_in_leaf, f32),
                    jnp.asarray(ctx.min_sum_hessian, f32),
                    jnp.asarray(ctx.min_gain_to_split, f32),
                    jnp.asarray(ctx.max_delta_step, f32),
                    jnp.asarray(ctx.path_smooth, f32),
                    md_f, n_nodes.astype(f32),
                    zero, zero, zero, zero, zero, zero, zero]).reshape(1, 16)
                if _SPLIT_ITER_OPCOUNT_STUB:
                    # op-count probe (tools/hlo_counts.py): swap the kernel
                    # for a pure_callback so a CPU compile shows the same
                    # launch structure a TPU build has — XLA-side fusions
                    # plus ONE custom-call (interpret mode would inline the
                    # kernel instead).  Compile-only; never executed.
                    P2, aux2 = jax.pure_callback(
                        lambda h, p, a: (p, a),
                        (jax.ShapeDtypeStruct(P.shape, P.dtype),
                         jax.ShapeDtypeStruct(aux.shape, aux.dtype)),
                        hist2.transpose(0, 1, 3, 2), P, aux,
                        vmap_method="legacy_vectorized")
                else:
                    P2, aux2 = split_iter_pallas(
                        hist2.transpose(0, 1, 3, 2), P, fmask_row, aux, scal,
                        pk=_PK)
            grew = jnp.where(active, 1, 0).astype(jnp.int32)
            return (P2, row_leaf2, n_nodes + 2 * grew, n_leaves + grew,
                    aux2)

        P_f, row_leaf_f, _, n_leaves_f, _ = lax.fori_loop(
            0, num_leaves - 1, body_f,
            (st.nodes, st.row_leaf, st.n_nodes, st.n_leaves, aux0))
        return (_tree_from_packed(P_f, n_leaves_f, None, None), row_leaf_f,
                _empty_pass_log(num_leaves))

    def body(_, st: _GrowState) -> _GrowState:
        P = st.nodes
        with jax.named_scope("lgbtpu.wave.rank"):
            # 1. pick the active leaf with the best cached gain (best-first).
            gains = jnp.where(P[:, K.IS_LEAF] > 0.5, P[:, K.CAND_GAIN], neg_inf)
            leaf = jnp.argmax(gains).astype(jnp.int32)
            gain = gains[leaf]
            active = (~st.done) & jnp.isfinite(gain)

            nl = st.n_nodes
            nr = st.n_nodes + 1
            row = P[leaf]                       # [NC] — ONE gather for every
            feat = row[K.CAND_FEAT].astype(jnp.int32)   # cached scalar below
            thr = row[K.CAND_BIN].astype(jnp.int32)

        with jax.named_scope("lgbtpu.wave.hist"):
            # 2. partition rows of the split leaf (gather, no pointer chasing).
            if fp_axis is not None:
                col = _fp_column(bins_i32, feat, fp_axis, num_features)
                below = col <= thr
            elif members is None:
                col = jnp.take(bins_i32, feat, axis=1)
                below = col <= thr
            else:
                rcol, rlo, rhi, rinv = split_route(members, feat, thr)
                col = jnp.take(bins_i32, rcol, axis=1)
                below = member_go_left(col, rlo, rhi, rinv)
            if cat_info is None:
                go_left = below
            else:
                go_left = jnp.where(row[K.CAND_CAT] > 0.5,
                                    st.cand_catmask[leaf][col], below)
            new_rl = jnp.where(
                st.row_leaf == leaf, jnp.where(go_left, nl, nr), st.row_leaf)
            row_leaf = jnp.where(active, new_rl, st.row_leaf)

            # 3. both children's histograms in one pass (others -> segment 2).
            seg = jnp.where(row_leaf == nl, 0,
                            jnp.where(row_leaf == nr, 1, 2)).astype(jnp.int32)
            hist2 = hist_fn(seg, 2, HIST_WAVE)               # [2, F, B, 3]

        with jax.named_scope("lgbtpu.wave.scan"):
            # 4. child output bounds (monotone basic method).
            wl_v, wr_v = row[K.CAND_WL], row[K.CAND_WR]
            lo, hi = row[K.BOUND_LO], row[K.BOUND_HI]
            lo_l, hi_l, lo_r, hi_r = _mono_child_bounds(mono, feat, wl_v, wr_v,
                                                        lo, hi)

            # 5. candidate splits for the children (each child samples its own
            # per-node feature subset when feature_fraction_bynode < 1).
            child_depth = row[K.DEPTH] + 1.0
            depth_ok = (max_depth <= 0) | \
                (child_depth < max_depth.astype(jnp.float32))
            child_masks = jnp.stack([node_feature_mask(nl), node_feature_mask(nr)])
            if ic_member is not None:
                child_sets = st.ic_sets[leaf] & ic_member[:, feat]   # [NG]
                child_masks = child_masks * _ic_allowed(child_sets,
                                                        ic_member)[None, :]
            child_lo = jnp.stack([lo_l, lo_r])
            child_hi = jnp.stack([hi_l, hi_r])
            child_out = jnp.stack([wl_v, wr_v])
            if dist_mode:
                child_rand = (jnp.stack([node_rand_bins(nl), node_rand_bins(nr)])
                              if extra_trees else None)
                bs = score_dist(hist2, child_masks, jnp.stack([depth_ok,
                                                               depth_ok]),
                                child_lo, child_hi, child_out, child_rand)
            elif extra_trees:
                child_rand = jnp.stack([node_rand_bins(nl), node_rand_bins(nr)])
                view2, minor = scan_view(hist2)

                def score(h, m, lo_, hi_, po, rb):
                    return find_best_split(h, ctx, m, depth_ok, cat_info, mono,
                                           lo_, hi_, po, rb, bins_minor=minor)

                bs: BestSplit = jax.vmap(score)(view2, child_masks, child_lo,
                                                child_hi, child_out, child_rand)
            else:
                view2, minor = scan_view(hist2)

                def score(h, m, lo_, hi_, po):
                    return find_best_split(h, ctx, m, depth_ok, cat_info, mono,
                                           lo_, hi_, po, bins_minor=minor)

                bs = jax.vmap(score)(view2, child_masks, child_lo, child_hi,
                                     child_out)
            if fp_axis is not None:
                bs = jax.vmap(
                    lambda b: _fp_reduce_best(b, fp_axis, num_features))(bs)

        with jax.named_scope("lgbtpu.wave.commit"):
            # 6. three packed row writes: the split leaf becomes internal, the
            # two children arrive with their cached candidate splits.
            leaf_row = row.at[jnp.array([
                K.SPLIT_FEAT, K.SPLIT_BIN, K.LEFT, K.RIGHT, K.IS_LEAF,
                K.SPLIT_GAIN])].set(jnp.stack([
                    feat.astype(jnp.float32), thr.astype(jnp.float32),
                    nl.astype(jnp.float32), nr.astype(jnp.float32),
                    jnp.float32(0.0), gain]))
            two = lambda a, b: jnp.stack([a, b])
            child_rows = jnp.stack([
                jnp.full((2,), -1.0),                        # SPLIT_FEAT
                jnp.zeros((2,)),                             # SPLIT_BIN
                jnp.full((2,), -1.0),                        # LEFT
                jnp.full((2,), -1.0),                        # RIGHT
                two(wl_v, wr_v),                             # LEAF_VALUE
                jnp.ones((2,)),                              # IS_LEAF
                two(row[K.CAND_LC], row[K.CAND_RC]),         # COUNT
                jnp.zeros((2,)),                             # SPLIT_GAIN
                jnp.full((2,), child_depth),                 # DEPTH
                bs.gain,                                     # CAND_GAIN
                bs.feature.astype(jnp.float32),              # CAND_FEAT
                bs.bin.astype(jnp.float32),                  # CAND_BIN
                bs.left_g, bs.left_h, bs.left_c,
                bs.right_g, bs.right_h, bs.right_c,
                bs.left_out,                                 # CAND_WL
                bs.right_out,                                # CAND_WR
                two(lo_l, lo_r),                             # BOUND_LO
                two(hi_l, hi_r),                             # BOUND_HI
                (bs.cat.astype(jnp.float32) if cat_info is not None
                 else jnp.zeros((2,))),                      # CAND_CAT
                jnp.minimum(row[K.PM], bs.gain),             # PM
            ], axis=-1)                                      # [2, NC]
            oob = jnp.int32(capacity)
            P = P.at[jnp.where(active, leaf, oob)].set(leaf_row, mode="drop")
            kid_idx = jnp.where(active, jnp.stack([nl, nr]), oob)
            P = P.at[kid_idx].set(child_rows, mode="drop")

        return st._replace(
            nodes=P,
            row_leaf=row_leaf,
            n_nodes=st.n_nodes + jnp.where(active, 2, 0).astype(jnp.int32),
            n_leaves=st.n_leaves + jnp.where(active, 1, 0).astype(jnp.int32),
            done=st.done | ~jnp.isfinite(gain),
            cand_catmask=(None if cat_info is None else
                          st.cand_catmask.at[kid_idx].set(
                              bs.cat_mask, mode="drop")),
            ic_sets=(None if ic_member is None else
                     st.ic_sets.at[kid_idx].set(
                         jnp.stack([child_sets, child_sets]), mode="drop")),
        )

    st = lax.fori_loop(0, num_leaves - 1, body, st)
    tree = _tree_from_packed(st.nodes, st.n_leaves, cat_info,
                             st.cand_catmask)
    return tree, st.row_leaf, _empty_pass_log(num_leaves)


def _scatter(arr, idx, val, active):
    """Masked vector scatter: arr[idx[i]] = val[i] where active[i].

    Inactive lanes are redirected to an out-of-bounds index and dropped
    (positive OOB, because negative indices wrap in JAX).
    """
    oob = arr.shape[0]
    safe = jnp.where(active, idx, oob)
    return arr.at[safe].set(val, mode="drop")


def _exact_prune(P, cand_catmask, row_leaf, num_leaves: int,
                 cat_info):
    """Replay strict best-first selection over an OVERGROWN wave tree and
    prune it back to ``num_leaves`` — LightGBM-exact split order at wave
    cost.

    Every node's candidate split (gain, feature, bin, child outputs)
    depends only on its OWN rows, so the overgrown tree's realized gains
    are exactly the gains strict growth would have scored, and strict
    best-first growth is priority-first extraction over that gain tree
    (a node becomes extractable when its parent is extracted).  The
    selection below replays the extraction literally on the packed node
    table; the pruning and row remap are vectorized.

    Coverage caveat: if strict would have split a node the overgrowth
    never expanded (an overgrown LEAF with competitive gain), that node
    stays a leaf and its budget goes to the next-best candidate — the
    only divergence from true strict order.  The overgrowth waves
    select by PATHMIN (= priority-first extraction order between
    distinct priorities) and stop as soon as :func:`_replay_certified`
    proves there is no such node, so a miss can only happen in a tree
    that reached the overgrowth CAP (``wave_overgrow``, ~2x) uncertified
    (identity with the strict grower and with the uncertified loop:
    tests/test_exact_wave.py).

    Returns (packed table [2*num_leaves-1, NC], pruned cand_catmask,
    remapped row_leaf, n_leaves).
    """
    K = _PK
    m_over = P.shape[0]
    capacity = 2 * num_leaves - 1
    ids = lax.iota(jnp.int32, m_over)
    left = P[:, K.LEFT].astype(jnp.int32)
    right = P[:, K.RIGHT].astype(jnp.int32)
    # parent pointers (root: parent = self = 0)
    parent = jnp.zeros(m_over, jnp.int32)
    parent = _scatter(parent, left, ids, left >= 0)
    parent = _scatter(parent, right, ids, right >= 0)

    expandable = left >= 0            # children exist in the overgrown tree
    # Sequential priority-first replay of strict extraction.  A single
    # (pathmin desc, id asc) sort selects the right SET between distinct
    # pathmin values, but inside a pathmin TIE GROUP (structural: every
    # chain capped by one weak ancestor shares its pm) strict extraction
    # dives into high-gain descendants while any static id order is
    # breadth-first — and the budget boundary lands exactly in the
    # low-gain region where those groups are widest.  So the selection
    # replays extraction literally: num_leaves-1 trips of (argmax over
    # available candidate gains -> keep -> activate children), all on
    # [m_over]-sized arrays (~6 tiny fused kernels per trip; a few ms per
    # round at production shapes).  Overgrown leaves with no scored
    # children (coverage misses — only in a tree that reached the cap
    # uncertified, see _replay_certified) are skipped in favor of the
    # next-best candidate.
    gain_c = P[:, K.CAND_GAIN]
    avail0 = jnp.zeros(m_over, bool).at[0].set(True)
    kept0 = jnp.zeros(m_over, bool)

    def extract(_, carry):
        avail, kept = carry
        g_av = jnp.where(avail & expandable, gain_c, -jnp.inf)
        i = jnp.argmax(g_av).astype(jnp.int32)
        ok = jnp.isfinite(g_av[i])
        oob = jnp.int32(m_over)
        kept = kept.at[jnp.where(ok, i, oob)].set(True, mode="drop")
        avail = avail.at[jnp.where(ok, i, oob)].set(False, mode="drop")
        kids = jnp.where(ok, jnp.stack([left[i], right[i]]), oob)
        avail = avail.at[kids].set(True, mode="drop")
        return avail, kept

    _, kept = lax.fori_loop(0, num_leaves - 1, extract, (avail0, kept0))
    n_kept = jnp.sum(kept.astype(jnp.int32))

    # final leaves = children of kept splits that are not themselves kept
    # (plus the root when nothing was kept at all).  Gate on REAL nodes:
    # when growth stalls below the overgrowth target, unused table slots
    # keep parent=0, and once the root is kept they would masquerade as
    # its children — ghost IS_LEAF rows in the output (code review r5).
    real = (P[:, K.IS_LEAF] > 0.5) | expandable
    final_leaf = real & (~kept) & ((kept[parent] & (ids != 0))
                                   | ((ids == 0) & (n_kept == 0)))
    surv = kept | final_leaf
    newid = jnp.cumsum(surv.astype(jnp.int32)) - 1

    # rewrite rows: kept nodes stay internal with remapped children; final
    # leaves revert to leaf sentinels (their LEAF_VALUE / COUNT were set at
    # creation from the parent's candidate — identical to strict growth)
    f32 = jnp.float32
    P_mod = P
    P_mod = P_mod.at[:, K.LEFT].set(
        jnp.where(kept, newid[jnp.maximum(left, 0)], -1).astype(f32))
    P_mod = P_mod.at[:, K.RIGHT].set(
        jnp.where(kept, newid[jnp.maximum(right, 0)], -1).astype(f32))
    P_mod = P_mod.at[:, K.IS_LEAF].set(jnp.where(kept, 0.0, 1.0))
    P_mod = P_mod.at[:, K.SPLIT_FEAT].set(
        jnp.where(kept, P[:, K.SPLIT_FEAT], -1.0))
    P_mod = P_mod.at[:, K.SPLIT_BIN].set(
        jnp.where(kept, P[:, K.SPLIT_BIN], 0.0))
    P_mod = P_mod.at[:, K.SPLIT_GAIN].set(
        jnp.where(kept, P[:, K.SPLIT_GAIN], 0.0))
    target = jnp.where(surv, newid, capacity)
    newP = _empty_packed_table(capacity).at[target].set(P_mod, mode="drop")
    new_cat = (None if cat_info is None else
               jnp.zeros((capacity, cand_catmask.shape[1]), jnp.bool_)
               .at[target].set(cand_catmask, mode="drop"))

    # rows point at overgrown leaves — map each to its unique final-leaf
    # ancestor-or-self (pointer doubling: k squarings cover chains of
    # 2^k nodes, and any ancestor chain is < m_over long), then newid
    f = jnp.where(final_leaf, ids, parent)
    for _ in range(max(4, int(m_over).bit_length())):
        f = f[f]
    node_to_new = jnp.where(final_leaf[f], newid[f], 0).astype(f32)
    row_leaf_new = lookup_values(
        row_leaf, node_to_new,
        precision=(lax.Precision.DEFAULT if capacity <= 256
                   else lax.Precision.HIGHEST)).astype(jnp.int32)
    return newP, new_cat, row_leaf_new, n_kept + 1


def _has_candidate(P):
    """bool[M]: the leaves of the packed node table that can still split."""
    return (P[:, _PK.IS_LEAF] > 0.5) & jnp.isfinite(P[:, _PK.CAND_GAIN])


def _replay_certified(P, num_leaves: int):
    """True once :func:`_exact_prune`'s replay over the packed node table
    ``P`` is provably the strict best-first tree, whatever further
    overgrowth would add.

    Strict extraction takes every node of pathmin ``p`` before any node
    of pathmin ``q < p`` (before the weakest ancestor of the second can
    be taken, an ancestor-or-self of the first, all of gain ``>= p``, is
    available and beats it).  ``T`` is the largest pathmin of a leaf that
    still has a candidate split: nothing that is not expanded yet, and
    nothing that could ever grow under it, has a pathmin above ``T``.  So
    with ``num_leaves - 1`` EXPANDED nodes strictly above ``T`` the
    replay's ``num_leaves - 1`` extractions lie inside the tree that
    exists, and more passes change neither the kept splits nor their
    statistics.  A tie with ``T`` is not counted (inside a pathmin tie
    group strict order dives by gain): the test errs towards one more
    pass, and is otherwise tight: with fewer, strict order's next pick
    IS that unexpanded leaf.  Node-table-sized reductions only.
    """
    K = _PK
    pm = P[:, K.PM]
    t = jnp.max(jnp.where(_has_candidate(P), pm, -jnp.inf))
    above = (P[:, K.LEFT] >= 0) & (pm > t)
    return jnp.sum(above.astype(jnp.int32)) >= num_leaves - 1


def _replay_needed(P, num_leaves: int):
    """How many leaves of the packed node table ``P`` the replay still
    NEEDS expanded before :func:`_replay_certified` can fire.

    ``theta`` is the ``(num_leaves - 1)``-th largest pathmin among the
    EXPANDED nodes, minus infinity while there are fewer.  A leaf whose
    pathmin is under ``theta`` cannot enter the replay's ``num_leaves -
    1`` extractions, nor can anything that grows under it (pathmin only
    falls along a path, ``theta`` only rises as nodes are added):
    expanding it changes neither the kept splits nor the certificate.  The
    others, the leaves with a candidate split and a pathmin of at least
    ``theta``, are needed; a tie with ``theta`` counts (the certificate
    does not count a tie either: both err towards the wider pass).  They
    are the first ``needed`` leaves of the exact tail's pathmin ranking, so
    a pass at least that wide expands them all.  While the tree is short
    of ``num_leaves - 1`` expanded nodes every candidate is needed; none is
    exactly when the table is certified or no leaf has a candidate left.
    Node-table-sized reductions only.
    """
    K = _PK
    pm = P[:, K.PM]
    # pathmin >= theta, without the sort: fewer than num_leaves - 1
    # expanded nodes lie strictly above it (one [M, M] compare; a sort of
    # the table costs the chip more than the rest of the loop's condition)
    above = jnp.sum(((P[:, K.LEFT] >= 0)[None, :]
                     & (pm[None, :] > pm[:, None])).astype(jnp.int32), axis=1)
    return jnp.sum((_has_candidate(P)
                    & (above < num_leaves - 1)).astype(jnp.int32))


def wave_extent(wave: WaveSchedule, num_leaves: int) -> Tuple[int, int]:
    """``(grow_leaves, w_width)`` of a wave-grown tree: the leaves it may
    reach (the exact tail's cap, else the budget) and the splits one pass
    holds.  The one check of a schedule that needs ``num_leaves``."""
    grow_leaves = num_leaves
    if wave.tail == "exact":
        if wave.cap_leaves <= num_leaves:
            raise ValueError(
                f"the exact tail overgrows past num_leaves={num_leaves} "
                f"and prunes back: its cap must exceed it, got "
                f"{wave.cap_leaves}")
        grow_leaves = wave.cap_leaves
    return grow_leaves, min(wave.width, grow_leaves - 1)


class _WaveState(NamedTuple):
    nodes: jnp.ndarray          # f32[M, _PK.NC] packed per-node table
    # frontier extras
    hist_cache: jnp.ndarray     # f32[num_leaves, 3*F*B] per-active-leaf
                                #   planes [3, F, B], flat (bins minor)
    node_slot: jnp.ndarray      # i32[M] node id -> hist_cache slot
    # dynamic growth state
    row_leaf: jnp.ndarray
    n_nodes: jnp.ndarray
    n_leaves: jnp.ndarray
    passes: jnp.ndarray         # f32[(grow_leaves - 1) * _PASS.NC] pass
                                #   log, flat, pass after pass
    n_passes: jnp.ndarray       # i32[] passes logged
    # categorical candidate split masks (None when the dataset has none)
    cand_catmask: Optional[jnp.ndarray] = None  # bool[M, B]
    # interaction constraints: surviving group set per node (None = off)
    ic_sets: Optional[jnp.ndarray] = None       # bool[M, NG]


def grow_tree_frontier(
    bins: jnp.ndarray,
    stats: jnp.ndarray,
    feature_mask: jnp.ndarray,
    ctx: SplitContext,
    num_leaves: int,
    num_bins: int,
    max_depth,
    wave: WaveSchedule,
    ff_bynode=None,
    key: Optional[jnp.ndarray] = None,
    axis_name: Optional[str] = None,
    hist_impl: str = "auto",
    row_chunk: int = 131072,
    hist_dtype: str = "f32",
    cat_info=None,
    mono=None,
    extra_trees: bool = False,
    col_bins=None,
    ic_member=None,
    fp_axis: Optional[str] = None,
    fuse_partition: bool = False,
    hist_merge: str = "psum",
    n_shards: int = 1,
    voting_k: int = 0,
    hist_wire: str = "f32",
    merge_chunks: int = 4,
    members=None,
    onehot_rows: Optional[tuple] = None,
    col_order: Optional[jnp.ndarray] = None,
) -> Tuple[Tree, jnp.ndarray, jnp.ndarray]:
    """Best-first growth in WAVES: up to ``wave.width`` splits per data pass.

    The strict grower (:func:`grow_tree`) re-scans all rows once per split —
    ``num_leaves - 1`` full-data histogram passes per tree, which caps
    large-``num_leaves`` training at Higgs scale (VERDICT r1 item 3).  This
    variant is the TPU analogue of LightGBM's histogram-subtraction trick
    (upstream ``ConstructHistogram`` computes the smaller child and derives
    the sibling as parent − child; SURVEY.md §3.1 hot-loop trace):

      * per wave, the top-``W`` active leaves by cached candidate gain are
        split TOGETHER; one histogram pass computes each split's *smaller*
        child directly (W segments folded into one one-hot matmul: in
        the full-width pass's orientation the MXU streams the one-hot's
        rows per 128-lane weight tile whatever 3W <= 128 is, so W splits
        cost what one does; a pass with at most ``wave.narrow_width``
        leaves to expand runs that narrow, through the turned dot, at 0.55
        of the price: the narrow passes below);
      * the sibling histogram is ``parent − child`` from a per-leaf
        histogram cache (f32 ``[num_leaves, 3*F*B]``: each leaf's three
        planes ``[3, F, B]``, flat, because both uses of the cache are
        matmuls over that view);
      * fresh children get their candidate splits scored from the cached
        histograms with no extra data pass.

    A balanced 127-leaf tree takes ~8 passes instead of 126.  Semantics:
    with width 1 the split order equals strict best-first; with
    larger widths the wave's split set is chosen before the wave's children
    are scored, so when the leaf budget binds mid-wave the tree can spend
    budget on wave-start leaves that strict growth would have skipped in
    favor of higher-gain fresh children.  Predictive quality is equivalent
    in practice (tests compare both modes); LightGBM-exact split order
    needs either the strict grower or the "exact" tail — overgrow
    in pathmin order until :func:`_replay_certified` proves the replay
    (at most to ``wave.cap_leaves``), then :func:`_exact_prune`
    replays strict best-first selection over the realized gains and
    prunes back to ``num_leaves`` (the budget-binding tail is the ONLY
    place wave and strict order diverge, so recovering it recovers strict
    order; a certified tree costs the larger of greedy's pass count and
    the strict tree's depth — PERF.md PR 29; PERF_HISTORY.md r4 gap
    decomposition).

    Returns ``(Tree, row_leaf, passes)``: ``passes`` logs every wave pass
    after the root's (:class:`_PASS`), so that the rows a pass streams can
    be set against the rows its splits needed.

    With ``members`` (an EFB table, :func:`grow_tree_logged`) the kernels,
    the cache and the subtraction work on the ``num_cols`` bundle columns
    and the scan on the member view of the original features; a wave's
    splits route by the ranges :func:`~lightgbm_tpu.ops.members.
    split_route` gives them.

    ``onehot_rows`` and ``col_order`` (:func:`grow_tree_logged`) order
    the partition-fused kernels' feature rows by the height of each
    column's one-hot (:func:`~lightgbm_tpu.ops.histogram_pallas.
    feature_layout`): the kernels hand the histograms back in the table's
    column order, and a split's column is mapped to its row for the
    routing.
    """
    n, num_cols = bins.shape
    num_features = num_cols if members is None else members.num_features
    exact = wave.tail == "exact"
    grow_leaves, w_width = wave_extent(wave, num_leaves)
    capacity = 2 * grow_leaves - 1

    # partition-fused wave kernel (histogram + row routing in one pallas
    # call — r5 trace: ~22 ms/wave of XLA-side partition work at 11M rows
    # reads data the kernel already holds in VMEM).  Static eligibility:
    # single-model growth (callers opt in; vmapped/batched growth keeps
    # the custom-vmap wide-segment route), no feature sharding, and a
    # pallas-routed dtype.  A categorical split routes inside the kernel
    # too, by its category set (``cat_sets``).  Since r7 the
    # feature axis may span multiple VMEM blocks — routing then reads the
    # wave-gathered split-feature code rows instead of the resident bins
    # tile (_fused_part_kernel_mb), so MSLR-class shapes (F=136) get the
    # in-kernel partition too.
    from ..ops.histogram_pallas import _vmem_blocking, feature_layout

    # more than one VMEM feature block: the kernel routes rows by wave rank
    # from the gathered code rows of the wave's split features and never
    # reads a feature id from the per-row table
    wave_f_blk, wave_f_blocks = _vmem_blocking(num_cols, num_bins,
                                               3 * w_width)[:2]
    multi_block = wave_f_blocks > 1
    exact_dtype = hist_dtype == "f32x"
    route_pallas = (hist_impl == "pallas"
                    or (hist_impl == "auto" and not exact_dtype
                        and jax.default_backend() == "tpu"))
    fuse_part = (fuse_partition and fp_axis is None
                 and hist_dtype != "int8" and route_pallas
                 and w_width > 1
                 # the per-row field lookup runs at bf16 DEFAULT
                 # precision — every table value (bin, 2*rank child
                 # offset, the feature id where one block routes by it,
                 # a category set's 0/1) must be an exact bf16 integer
                 and max(2 * w_width, num_bins) <= 256
                 and (multi_block or num_cols <= 256))
    if cat_info is not None:
        # where this grower routes category sets, noted as it is traced:
        # inside the partition-fused kernels, or in XLA
        profiling.note("train.cat_route", "fused" if fuse_part else "xla")
    # the fused kernels' feature rows, the columns by their one-hot's height
    by_height = (fuse_part and col_order is not None
                 and onehot_rows is not None
                 and len(onehot_rows) == num_cols)
    layout = feature_layout(num_cols, wave_f_blk, num_bins,
                            onehot_rows if by_height else None)
    row_of = (jnp.zeros(num_cols, jnp.int32).at[col_order].set(
        lax.iota(jnp.int32, num_cols)) if by_height else None)

    def to_row(col):
        """A split column's row of the fused kernels' codes."""
        return col if row_of is None else row_of[col]

    max_depth = jnp.asarray(max_depth, jnp.int32)
    neg_inf = jnp.float32(-jnp.inf)
    if key is None:
        key = jax.random.PRNGKey(0)
    bynode_off = ff_bynode is None   # static: skip the per-node RNG draw

    if axis_name is None:
        hist_merge = "psum"          # single-shard: nothing to merge
    dist_mode = hist_merge != "psum"
    if dist_mode and fp_axis is not None:
        raise ValueError(
            f"hist_merge={hist_merge!r} is a data-parallel merge topology "
            "and cannot compose with feature sharding (fp_axis) — the 2-D "
            "dp x fp mesh keeps the psum merge")
    if hist_merge == "voting" and cat_info is not None:
        raise ValueError(
            "hist_merge='voting' does not support categorical splits (the "
            "local ballot scans numeric thresholds only) — use "
            "'reduce_scatter' or 'psum'")
    score_dist = (_make_dist_scorer(axis_name, hist_merge, n_shards,
                                    num_features, ctx, cat_info, mono,
                                    voting_k, merge_chunks)
                  if dist_mode else None)
    # per-leaf histogram cache feature extent: the merged SLICE under
    # reduce-scatter (a D-fold cache memory drop — the subtraction trick is
    # linear, so parent - child on slices is the slice of the subtraction);
    # under voting the cache keeps LOCAL unmerged partials (additive too —
    # the candidate-union merge happens at scoring time).  The pipelined
    # mode pads to a D*chunks multiple, so the slice width comes from the
    # shared merge_slice_width helper, not ceil(F/D).
    if dist_mode and hist_merge != "voting":
        from ..ops.histogram import merge_slice_width

        f_hist = merge_slice_width(num_features, n_shards, hist_merge,
                                   merge_chunks)
    else:
        f_hist = num_cols

    # shared mask-composition layer (models.feature_mask, r20): same
    # fold_in(key, node_id)-within-tree-mask draw as the strict grower
    from .feature_mask import node_mask_fn

    node_feature_mask = node_mask_fn(key, ff_bynode, num_features,
                                     feature_mask, bynode_off)

    def node_rand_bins(node_id):
        if not extra_trees:
            return None
        return _rand_bins_for_node(key, node_id, num_features, num_bins,
                                   col_bins)

    def hist_fn(seg_id, num_segments, role):
        from ..ops.histogram import batched_histogram_op

        op = batched_histogram_op(num_segments, num_bins, row_chunk,
                                  hist_impl, hist_dtype, role)
        return to_planes(merge(op(bins, stats, seg_id)))

    def merge(h):
        """``[S, F, B, 3]`` partials -> what this shard scores."""
        if hist_merge == "voting":
            return h       # local partials; the scorer merges candidates
        return histogram_merge(h, axis_name, mode=hist_merge,
                               n_shards=n_shards, wire_dtype=hist_wire,
                               n_chunks=merge_chunks)

    # Per-node histograms live in this grower as PLANES ``[S, 3, F, B]``,
    # bins minor, from the kernel's output to the split scan: the chip
    # tiles an array's two minor axes by (8, 128) in HBM, so a 3-wide
    # minor axis is stored 128 wide (hist_partition_fused_pallas).  The
    # merges across shards and the distributed scorer keep ``[S, F, B,
    # 3]``, the layout of everything outside this grower.
    def to_planes(h):
        return jnp.moveaxis(h, -1, 1)

    def from_planes(h):
        return jnp.moveaxis(h, 1, -1)

    def scan_view(planes):
        """The planes the scan reads: the member view of a bundled table."""
        if members is None:
            return planes
        with jax.named_scope("lgbtpu.wave.members"):
            return member_view(planes, members)

    if fuse_part:
        # loop-invariant kernel operands prepared ONCE a tree (the in-call
        # pad/convert re-ran per wave, ~2.7 ms each at 11M — r5 trace); the
        # root pass reads them too, so the tree keeps one 4-byte transposed
        # copy of the codes and not one per row padding
        from ..ops.histogram_pallas import (hist_fused_prepared,
                                            hist_partition_fused_pallas,
                                            prepare_wave_operands)

        stats_prep_src = stats
        if hist_dtype == "bf16sr":
            # the opt-in SR variant must quantize here too — the fused
            # path bypasses compute_histograms where SR normally applies
            from ..ops.histogram import sr_round_bf16

            stats_prep_src = sr_round_bf16(stats)
        bins_t_prep, stats_t_prep, part_chunk = prepare_wave_operands(
            bins, stats_prep_src, num_bins, w_width,
            col_order if by_height else None)
        n_pad_rows = bins_t_prep.shape[1]
        kernel_dtype = "f32" if hist_dtype in ("f32", "f32x") else "bf16"

    # ---- root -------------------------------------------------------------
    with jax.named_scope("lgbtpu.root"):
        if fuse_part:
            # every row in segment 0 (the rows that pad carry no statistics)
            root_hist = to_planes(merge(hist_fused_prepared(
                bins_t_prep, stats_t_prep,
                jnp.zeros((1, n_pad_rows), jnp.int32), 1, num_bins,
                part_chunk, wave_f_blk, num_cols,
                hist_dtype=kernel_dtype, name=HIST_ROOT, layout=layout,
                row_of=row_of)))[0]
        else:
            root_hist = hist_fn(jnp.zeros(n, jnp.int32), 1,
                                HIST_ROOT)[0]           # [3, f_hist, B]
        if dist_mode:
            # global totals from the stats rows (they sum to the histogram
            # totals by construction) — one [3]-element psum instead of
            # reading feature 0's bins from a sliced/unmerged histogram
            root_tot = lax.psum(jnp.sum(stats, axis=0), axis_name)
        else:
            root_tot = jnp.sum(root_hist[:, 0], axis=1)              # (g, h, c)
        root_out = constrained_leaf_output(
            root_tot[0], root_tot[1], root_tot[2],
            ctx._replace(path_smooth=jnp.float32(0.0)),
            jnp.float32(-jnp.inf), jnp.float32(jnp.inf), jnp.float32(0.0))
        if ic_member is not None:
            root_mask_f = (node_feature_mask(0)
                           * _ic_allowed(jnp.ones((ic_member.shape[0],), bool),
                                         ic_member))
        else:
            root_mask_f = node_feature_mask(0)
        if dist_mode:
            rb0 = node_rand_bins(0)
            root_best = jax.tree.map(lambda x: x[0], score_dist(
                from_planes(root_hist[None]), root_mask_f[None],
                jnp.ones((1,), bool),
                jnp.full((1,), -jnp.inf, jnp.float32),
                jnp.full((1,), jnp.inf, jnp.float32), root_out[None],
                None if rb0 is None else rb0[None]))
        else:
            root_best = find_best_split(scan_view(root_hist), ctx,
                                        root_mask_f, jnp.bool_(True),
                                        cat_info, mono=mono,
                                        parent_out=root_out,
                                        rand_bins=node_rand_bins(0),
                                        bins_minor=True)
        if fp_axis is not None:
            # feature-parallel: each shard scanned its own column slice; one
            # tiny all_gather + argmax globalizes the winner (the same split
            # exchange the strict grower uses — upstream's
            # FeatureParallelTreeLearner, SURVEY.md §2C)
            root_best = _fp_reduce_best(root_best, fp_axis, num_features)

    def full(val, dtype):
        return jnp.full((capacity,), val, dtype)

    K = _PK
    fb3 = 3 * f_hist * num_bins
    st = _WaveState(
        nodes=_packed_root_table(capacity, root_out, root_tot, root_best,
                                 cat_info),
        # slot 0 holds the root; written as a select over the whole cache,
        # because a one-row update of a cache the compiler keeps leaf-minor
        # goes through a [1, 3*F*B] array whose 1-wide axis is stored 128
        # lanes wide (783 MB at 2,000 features)
        hist_cache=jnp.where(
            lax.iota(jnp.int32, grow_leaves)[:, None] == 0,
            jnp.broadcast_to(root_hist.reshape(fb3), (grow_leaves, fb3)),
            jnp.float32(0.0)),
        node_slot=full(0, jnp.int32),
        row_leaf=jnp.zeros(n, jnp.int32),
        n_nodes=jnp.int32(1),
        n_leaves=jnp.int32(1),
        passes=_empty_pass_log(grow_leaves).reshape(-1),
        n_passes=jnp.int32(0),
        cand_catmask=(None if cat_info is None else
                      jnp.zeros((capacity, num_bins), jnp.bool_)
                      .at[0].set(root_best.cat_mask)),
        ic_sets=(None if ic_member is None else
                 jnp.zeros((capacity, ic_member.shape[0]), bool)
                 .at[0].set(True)),
    )

    bins_i32 = bins.astype(jnp.int32)
    # the rows a pass's kernel reads: every row, padding included
    streamed_rows = n_pad_rows if fuse_part else n

    def wave_body(width: int, role: str):
        return functools.partial(body, width=width, role=role,
                                 iota_w=lax.iota(jnp.int32, width))

    # The exact tail's overgrowth cap is wave-aligned
    # (spec._exact_overgrow_target): full waves land on it.  A tree whose
    # replay is certified earlier (_replay_certified) stops there; one
    # that is not runs on to the cap.  A leaf of the
    # doubling waves with no split to offer leaves the count ONE short,
    # and the loop then bought that one node of a heuristic margin of
    # hundreds with a whole pass over the rows (one round in six at
    # 400,000 x 2,000: 13.08 s for 12.37; the "one more 112 ms pass" of a
    # Higgs seed).  So once the tree is past num_leaves, a pass has to
    # have room for an eighth of a wave.
    min_budget = max(1, w_width // 8) if exact else 1

    def cond(st: _WaveState, leaves=None):
        """Whether the tree runs another pass; ``leaves`` = the leaf count
        held against the cap, the tree's own unless given."""
        P = st.nodes
        gains = jnp.where(P[:, K.IS_LEAF] > 0.5, P[:, K.CAND_GAIN], neg_inf)
        leaves = st.n_leaves if leaves is None else leaves
        budget = grow_leaves - leaves
        go = (((budget >= min_budget) | (leaves <= num_leaves))
              & (budget > 0) & jnp.any(jnp.isfinite(gains)))
        return go & ~_replay_certified(P, num_leaves) if exact else go

    def body(st: _WaveState, width: int, role: str,
             iota_w: jnp.ndarray) -> _WaveState:
        """One wave pass of at most ``width`` splits (static), its kernel
        named ``role``; ``iota_w`` = ``iota(width)``, made outside the
        loop."""
        m = capacity
        P = st.nodes
        with jax.named_scope("lgbtpu.wave.rank"):
            # 1. rank active leaves by cached candidate gain (desc, stable).
            # Exact mode ranks by PATHMIN instead: priority-first extraction
            # order on a tree IS descending pathmin (see _exact_prune), so
            # pm-ordered waves expand nodes in the same order strict growth
            # would — the overgrown tree soon CONTAINS the strict selection
            # (cond stops the loop once _replay_certified proves it),
            # instead of greedy-by-gain overgrowth hoping to have covered it.
            gains = jnp.where(P[:, K.IS_LEAF] > 0.5, P[:, K.CAND_GAIN], neg_inf)
            sel_key = (jnp.where(P[:, K.IS_LEAF] > 0.5, P[:, K.PM], neg_inf)
                       if exact else gains)
            order = jnp.argsort(-sel_key, stable=True)        # [M]
            rank = jnp.zeros(m, jnp.int32).at[order].set(
                lax.iota(jnp.int32, m))
            budget = grow_leaves - st.n_leaves
            n_cand = jnp.sum(jnp.isfinite(gains)).astype(jnp.int32)
            # Wave size: a pass of a given static width costs the same
            # however many of its segments carry a leaf (113 ms at 10.5M x
            # 28 at width 42, 63 ms at the narrow phase's 16: v5e, PR 32),
            # so the count of passes at each width IS tree cost.  Greedy (s =
            # min(budget, W)) closes a 127-leaf tree in 8 passes;
            # spending at most HALF the remaining budget per wave
            # allocates the tail splits near-strict-best-first at ~5 extra
            # passes.  The tail refinement is what preserves strict-growth
            # quality when the leaf budget nearly saturates the data (small-n /
            # large-num_leaves); ``wave.tail`` picks the tradeoff.  "exact"
            # overgrows with the greedy schedule (the post-hoc replay, not the
            # wave order, is what restores strict allocation).
            if wave.tail == "half":
                alloc = jnp.maximum(jnp.int32(1), budget // 2)
            else:  # "greedy" / "exact"
                alloc = budget
            s = jnp.minimum(jnp.minimum(n_cand, alloc),
                            jnp.int32(width))       # splits this wave
            sel = jnp.isfinite(gains) & (rank < s)            # [M]

        with jax.named_scope("lgbtpu.wave.hist"):
            # 2. partition rows of all splitting leaves at once.  Per-row state
            # comes from ONE one-hot-matmul table lookup (ops.lookup): XLA's
            # native [n]-from-[capacity] gathers cost ~7 ms each at 1M rows on
            # TPU, and this block needs six of them — more than the histogram
            # kernel itself.
            parent_r = order[:width]                          # [W] node ids
            active_r = iota_w < s
            prow = P[parent_r]            # [W, NC] — ONE gather for all the
            direct_left = prow[:, K.CAND_LC] <= prow[:, K.CAND_RC]  # per-parent
            nl_r = st.n_nodes + 2 * iota_w                          # scalars
            nr_r = nl_r + 1
            dl_of = _scatter(full(m, jnp.bool_), parent_r, direct_left,
                             active_r)                        # node -> direct side
            p = st.row_leaf
            f32 = jnp.float32
            if fuse_part:
                # 2+3 FUSED: one transposed per-row lookup of the wave's node
                # fields, then the pallas kernel routes rows AND builds the
                # direct-child histograms in a single pass (phase-1 feature
                # select + phase-2 folded dots — _fused_part_kernel).  The
                # one-hot compares against the W SPLITTING PARENTS only, not
                # the full node table (rows in any other leaf produce an
                # all-zero column = sel 0, exactly the wanted semantics) —
                # the full-table compare was ~6 ms/wave at 11M rows.  Table
                # values (sel/feat/thr/rank2/dl) are all <= 256 under the
                # single-f-block gate, so the dot stays bf16-exact.
                # a wave split's column and range (ops.members): rows 5-6
                # carry lo and inv, zero for a plain column's ``v <= thr``;
                # row 7 whether the split is a category set's
                zw = jnp.zeros(width)
                cat_w = zw if cat_info is None else prow[:, K.CAND_CAT]
                if members is None:
                    tbl_w = jnp.stack([active_r.astype(f32),
                                       (zw if multi_block
                                        else prow[:, K.CAND_FEAT]
                                        if row_of is None
                                        else to_row(prow[:, K.CAND_FEAT]
                                                    .astype(jnp.int32))
                                        .astype(f32)),
                                       prow[:, K.CAND_BIN],
                                       (2 * iota_w).astype(f32),
                                       direct_left.astype(f32), zw, zw,
                                       cat_w], axis=1)           # [W, 8]
                else:
                    wcol, wlo, whi, winv = split_route(
                        members, prow[:, K.CAND_FEAT].astype(jnp.int32),
                        prow[:, K.CAND_BIN])
                    tbl_w = jnp.stack([active_r.astype(f32),
                                       (zw if multi_block
                                        else to_row(wcol).astype(f32)),
                                       whi, (2 * iota_w).astype(f32),
                                       direct_left.astype(f32), wlo,
                                       winv.astype(f32), cat_w], axis=1)
                oh_w = (parent_r[:, None] == p[None, :])         # [W, n]
                pv_t = lax.dot_general(
                    tbl_w.astype(f32).T, oh_w.astype(f32),
                    dimension_numbers=(((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=lax.Precision.DEFAULT)             # [8, n]
                if n_pad_rows != n:
                    pv_t = jnp.pad(pv_t, ((0, 0), (0, n_pad_rows - n)))
                direct_hist, enc = hist_partition_fused_pallas(
                    bins_t_prep, stats_t_prep, pv_t, width, num_bins,
                    part_chunk, hist_dtype=kernel_dtype,
                    # multi-f-block routing gathers the wave split features'
                    # code rows; ignored on single-block shapes
                    wfeat=to_row(prow[:, K.CAND_FEAT].astype(jnp.int32)
                                 if members is None else wcol),
                    num_features=num_cols, name=role, f_blk=wave_f_blk,
                    layout=layout, row_of=row_of,
                    # the wave's category sets, 0/1 by bin
                    cat_sets=(None if cat_info is None else
                              st.cand_catmask[parent_r].astype(f32)))
                # the kernel's direct_hist is the LOCAL pre-merge partial,
                # planes [W, 3, F, B]: every merge topology applies after it
                # unchanged (voting keeps it unmerged for the scorer's
                # candidate union), in the layout the merges keep
                if axis_name is not None and hist_merge != "voting":
                    direct_hist = to_planes(merge(from_planes(direct_hist)))
                enc = enc[:n]
                row_leaf = jnp.where(enc > 0, st.n_nodes + enc - 1, p)
            else:
                # child ids ride as WAVE-RELATIVE offsets (2*rank <= 2W <=
                # 256), not absolute node ids: absolute ids exceed 256
                # whenever the (overgrown) capacity does, which would force
                # the HIGHEST-precision dot below.  child = n_nodes + offset
                # reconstructs the absolute id after the lookup.
                if members is None:
                    cols = [sel.astype(f32), P[:, K.CAND_FEAT],
                            P[:, K.CAND_BIN], (2 * rank).astype(f32),
                            dl_of.astype(f32)]
                else:
                    ncol, nlo, nhi, ninv = split_route(
                        members, P[:, K.CAND_FEAT].astype(jnp.int32),
                        P[:, K.CAND_BIN])
                    cols = [sel.astype(f32), ncol.astype(f32), nhi,
                            (2 * rank).astype(f32), dl_of.astype(f32)]
                if cat_info is not None:
                    cols.append(P[:, K.CAND_CAT])
                if members is not None:
                    cols += [nlo, ninv.astype(f32)]
                # DEFAULT precision (native-rate bf16 dot) is exact only while
                # every table value is an integer <= 256 (bf16 has an 8-bit
                # significand); feature ids beyond 256 need the full-precision
                # dot or rows partition on corrupted ids.  (The one-hot INDEX
                # side is exact at any capacity — only table VALUES are
                # constrained.)  Under feature sharding the table carries
                # GLOBAL feature ids whose range this shard cannot bound
                # statically — always exact there.
                exact_in_bf16 = (fp_axis is None
                                 and max(num_cols, 2 * width,
                                         num_bins) <= 256)
                pv = lookup_rows(p, jnp.stack(cols, axis=1),
                                 precision=(lax.Precision.DEFAULT
                                            if exact_in_bf16
                                            else lax.Precision.HIGHEST))
                psel = pv[:, 0] > 0
                feat_r = pv[:, 1].astype(jnp.int32)
                thr_r = pv[:, 2]
                # per-row split value WITHOUT take_along_axis (same gather
                # problem): masked lane-reduction over the feature axis.
                # Under feature sharding the ids are global: match against
                # this shard's global column range and psum — the owning
                # shard contributes the codes (the [n] bitmap exchange of
                # upstream's feature-parallel split, batched over the wave)
                if fp_axis is not None:
                    gids = (lax.axis_index(fp_axis) * num_features
                            + lax.iota(jnp.int32, num_features))
                    fmatch = feat_r[:, None] == gids[None, :]
                    v = lax.psum(
                        jnp.sum(jnp.where(fmatch, bins_i32, 0), axis=1),
                        fp_axis)
                else:
                    fmatch = (feat_r[:, None]
                              == lax.iota(jnp.int32, num_cols)[None, :])
                    v = jnp.sum(jnp.where(fmatch, bins_i32, 0), axis=1)
                below = (v.astype(f32) <= thr_r if members is None
                         else member_go_left(v.astype(f32), pv[:, -2],
                                             thr_r, pv[:, -1] > 0))
                if cat_info is None:
                    go_left = below
                else:
                    # category-subset membership: one-hot lookup of the row's
                    # mask row, then select bit v — both stay fused
                    mrow = lookup_rows(p, st.cand_catmask.astype(f32),
                                       precision=lax.Precision.DEFAULT)
                    bit = jnp.sum(
                        jnp.where(v[:, None]
                                  == lax.iota(jnp.int32, num_bins)[None, :],
                                  mrow, 0.0), axis=1)
                    go_left = jnp.where(pv[:, 5] > 0, bit > 0, below)
                rank2_r = pv[:, 3].astype(jnp.int32)
                child = st.n_nodes + rank2_r + jnp.where(go_left, 0, 1)
                row_leaf = jnp.where(psel, child, p)

                # 3. one histogram pass over the SMALLER child of every
                # split: a row participates iff its leaf splits this wave AND
                # it went to the direct (smaller) side; its segment is the
                # leaf's wave rank.
                to_direct = psel & (go_left == (pv[:, 4] > 0))
                seg_id = jnp.where(to_direct, rank2_r >> 1, width)
                direct_hist = hist_fn(seg_id, width, role)  # [W, 3, F, B]

        with jax.named_scope("lgbtpu.wave.sibling"):
            # 4. sibling = parent - child (the subtraction trick).  The cache
            # gather and update are ONE-HOT MATMULS, not gather/scatter ops:
            # the r5 trace showed XLA materializing wholesale copies of the
            # [grow_leaves, F, B, 3] cache around the scatter (two ~59 ms
            # async copies per wave at the 11M o2.0 shape, co-critical with
            # the kernel stream), while the matmul form reads the cache once
            # and commits a pure += the while-carry can alias in place.
            # Exactness: one-hot factors are exact at every precision and
            # HIGHEST keeps the f32 cache values bit-exact.
            parent_slot = st.node_slot[parent_r]              # [W]
            oh_p = (parent_slot[:, None]
                    == lax.iota(jnp.int32, grow_leaves)[None, :])
            parent_hist = lax.dot_general(
                oh_p.astype(f32), st.hist_cache,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=lax.Precision.HIGHEST,
            ).reshape(width, 3, f_hist, num_bins)
            other_hist = parent_hist - direct_hist
            dl = direct_left[:, None, None, None]
            left_hist = jnp.where(dl, direct_hist, other_hist)
            right_hist = jnp.where(dl, other_hist, direct_hist)

            left_slot = parent_slot                           # reuse parent slot
            right_slot = st.n_leaves + iota_w
            # mask-and-add: zero the overwritten rows, matmul-add the EXACT
            # new values (a delta formulation would set left = parent +
            # (left - parent), off by ~ulp(parent) in f32 — an error the old
            # scatter never had, compounding through future subtractions)
            slot2 = jnp.concatenate([left_slot, right_slot])  # [2W]
            act2w = jnp.concatenate([active_r, active_r])
            slot2m = jnp.where(act2w, slot2, -1)
            q = (lax.iota(jnp.int32, grow_leaves)[:, None]
                 == slot2m[None, :])                          # [L, 2W]
            keep = 1.0 - jnp.any(q, axis=1).astype(f32)       # [L]
            newvals = jnp.concatenate([left_hist, right_hist])
            cache = st.hist_cache * keep[:, None] + lax.dot_general(
                q.astype(f32), newvals.reshape(2 * width, fb3),
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=lax.Precision.HIGHEST)
            node_slot = _scatter(st.node_slot, nl_r, left_slot, active_r)
            node_slot = _scatter(node_slot, nr_r, right_slot, active_r)

        with jax.named_scope("lgbtpu.wave.scan"):
            # 5. child output bounds (monotone basic method, per splitting leaf).
            pf = prow[:, K.CAND_FEAT].astype(jnp.int32)
            wl_w, wr_w = prow[:, K.CAND_WL], prow[:, K.CAND_WR]       # [W]
            lo_w, hi_w = prow[:, K.BOUND_LO], prow[:, K.BOUND_HI]
            lo_l, hi_l, lo_r, hi_r = _mono_child_bounds(mono, pf, wl_w, wr_w,
                                                        lo_w, hi_w)

            # 6. score candidates for all 2W fresh children from the cache.
            child_nodes = jnp.concatenate([nl_r, nr_r])       # [2W]
            child_hists = jnp.concatenate([left_hist, right_hist])
            child_depth1 = prow[:, K.DEPTH] + 1.0             # [W]
            child_depth = jnp.concatenate([child_depth1, child_depth1])
            depth_ok = (max_depth <= 0) | \
                (child_depth < max_depth.astype(jnp.float32))
            child_masks = jax.vmap(node_feature_mask)(child_nodes)
            if ic_member is not None:
                child_sets = (st.ic_sets[parent_r]
                              & ic_member[:, pf].T)              # [W, NG]
                allowed_w = _ic_allowed(child_sets, ic_member)   # [W, F]
                child_masks = child_masks * jnp.concatenate(
                    [allowed_w, allowed_w])
            child_lo = jnp.concatenate([lo_l, lo_r])
            child_hi = jnp.concatenate([hi_l, hi_r])
            child_vals = jnp.concatenate([wl_w, wr_w])        # actual outputs
            if dist_mode:
                child_rand = (jax.vmap(node_rand_bins)(child_nodes)
                              if extra_trees else None)
                bs = score_dist(from_planes(child_hists), child_masks,
                                depth_ok, child_lo, child_hi, child_vals,
                                child_rand)
            elif extra_trees:
                child_rand = jax.vmap(node_rand_bins)(child_nodes)

                def score(h, m, d, lo_, hi_, po, rb):
                    return find_best_split(h, ctx, m, d, cat_info, mono,
                                           lo_, hi_, po, rb, bins_minor=True)

                bs: BestSplit = jax.vmap(score)(
                    scan_view(child_hists), child_masks, depth_ok, child_lo,
                    child_hi, child_vals, child_rand)
            else:

                def score(h, m, d, lo_, hi_, po):
                    return find_best_split(h, ctx, m, d, cat_info, mono,
                                           lo_, hi_, po, bins_minor=True)

                bs = jax.vmap(score)(scan_view(child_hists), child_masks,
                                     depth_ok, child_lo, child_hi, child_vals)
            if fp_axis is not None:
                # globalize all 2W child winners in one batched all_gather
                bs = jax.vmap(
                    lambda b: _fp_reduce_best(b, fp_axis, num_features))(bs)
            active_2 = jnp.concatenate([active_r, active_r])

        with jax.named_scope("lgbtpu.wave.commit"):
            # 7. commit with TWO packed row scatters: the W split parents
            # become internal (their rows keep every cached field and gain
            # the split bookkeeping), the 2W fresh children arrive with
            # their scored candidate splits.
            parent_rows = prow.at[:, jnp.array([
                K.SPLIT_FEAT, K.SPLIT_BIN, K.LEFT, K.RIGHT, K.IS_LEAF,
                K.SPLIT_GAIN])].set(jnp.stack([
                    prow[:, K.CAND_FEAT], prow[:, K.CAND_BIN],
                    nl_r.astype(jnp.float32), nr_r.astype(jnp.float32),
                    jnp.zeros(width), gains[parent_r]], axis=-1))
            child_rows = jnp.stack([
                jnp.full((2 * width,), -1.0),                # SPLIT_FEAT
                jnp.zeros((2 * width,)),                     # SPLIT_BIN
                jnp.full((2 * width,), -1.0),                # LEFT
                jnp.full((2 * width,), -1.0),                # RIGHT
                child_vals,                                  # LEAF_VALUE
                jnp.ones((2 * width,)),                      # IS_LEAF
                jnp.concatenate([prow[:, K.CAND_LC],
                                 prow[:, K.CAND_RC]]),       # COUNT
                jnp.zeros((2 * width,)),                     # SPLIT_GAIN
                child_depth,                                 # DEPTH
                bs.gain,                                     # CAND_GAIN
                bs.feature.astype(jnp.float32),              # CAND_FEAT
                bs.bin.astype(jnp.float32),                  # CAND_BIN
                bs.left_g, bs.left_h, bs.left_c,
                bs.right_g, bs.right_h, bs.right_c,
                bs.left_out,                                 # CAND_WL
                bs.right_out,                                # CAND_WR
                child_lo,                                    # BOUND_LO
                child_hi,                                    # BOUND_HI
                (bs.cat.astype(jnp.float32) if cat_info is not None
                 else jnp.zeros((2 * width,))),              # CAND_CAT
                jnp.minimum(jnp.concatenate([prow[:, K.PM], prow[:, K.PM]]),
                            bs.gain),                        # PM
            ], axis=-1)                                      # [2W, NC]
            oob = jnp.int32(capacity)
            P2 = P.at[jnp.where(active_r, parent_r, oob)].set(
                parent_rows, mode="drop")
            kid_idx = jnp.where(active_2, child_nodes, oob)
            P2 = P2.at[kid_idx].set(child_rows, mode="drop")

            # 8. the pass log: this pass's column (_PASS)
            lc_w, rc_w = prow[:, K.CAND_LC], prow[:, K.CAND_RC]
            logged = jnp.stack([
                jnp.float32(role != HIST_NARROW), s.astype(f32),
                jnp.float32(streamed_rows),
                jnp.sum(jnp.where(active_r, lc_w + rc_w, 0.0)),
                jnp.sum(jnp.where(active_r,
                                  jnp.where(direct_left, lc_w, rc_w), 0.0))])
            passes = lax.dynamic_update_slice(
                st.passes, logged, (_PASS.NC * st.n_passes,))

        return st._replace(
            nodes=P2,
            hist_cache=cache,
            node_slot=node_slot,
            row_leaf=row_leaf,
            n_nodes=st.n_nodes + 2 * s,
            n_leaves=st.n_leaves + s,
            passes=passes,
            n_passes=st.n_passes + 1,
            cand_catmask=(None if cat_info is None else
                          st.cand_catmask.at[kid_idx].set(
                              bs.cat_mask, mode="drop")),
            ic_sets=(None if ic_member is None else
                     st.ic_sets.at[kid_idx].set(
                         jnp.concatenate([child_sets, child_sets]),
                         mode="drop")),
        )

    # The narrow passes.  While the tree has at most ``narrow_width`` leaves
    # every leaf it has fits a wave of that width (n_cand <= n_leaves), so
    # a pass of ``narrow_width`` selects the leaves, node ids, cache slots
    # and order that a pass of ``w_width`` would: the same splits by
    # construction, through a kernel whose turned dot does not pay for the
    # columns that carry no leaf (_accumulate_wave), and through stages
    # (the per-row lookup, the cache gather and update, the 2W children's
    # scan) that are ``narrow_width`` wide.  ``n_leaves`` only grows, so
    # the two loops in sequence are the one loop.  Taken exactly where the
    # partition-fused kernel is: every other path keeps its one loop.
    narrow_width = wave.narrow_width if fuse_part else 0
    if 0 < narrow_width < w_width and exact:
        # The exact tail knows more than the leaf count: a pass has to
        # expand only the leaves its replay still NEEDS (_replay_needed),
        # and they head the pathmin ranking, so while they fit the narrow
        # width the narrow pass expands them all (its other slots go to the
        # next leaves of the ranking, as the wide pass fills its own).
        # What the wide pass would have expanded besides lies under
        # ``theta`` for good: the kept splits, the certificate's verdict
        # after every pass and _exact_prune's extractions are the
        # full-width schedule's.  While the tree is short of ``num_leaves
        # - 1`` splits every candidate is needed: the rule CONTAINS the
        # narrow phase above and runs the growth passes at full width.
        # Past that, a certification pass (2-4 a tree at 10.5M x 28, most
        # on a chain that needs one to five leaves) runs narrow.
        # ``needed`` is not monotone (both children of a needed leaf can
        # be needed), so the width is chosen before every pass: an outer
        # loop over the two inner loops, every carry aliased in place.
        # The cap bounds the passes as it did: it is held against
        # ``sched``, the leaves the FULL-WIDTH schedule would have by now
        # (a narrow pass adds 16 leaves at most: held against the tree's
        # own count, a tree that never certifies would run 16 more
        # passes); the two counts part only once a certification pass
        # has run narrow.
        def plan(st, sched):
            """After a pass: whether the tree runs another, and whether
            narrow.  Carried, so the loops' conditions read two flags and
            the table's reductions run once a pass."""
            needed = _replay_needed(st.nodes, num_leaves)
            return (cond(st, sched),
                    (needed > 0) & (needed <= narrow_width))

        def passes(width: int, role: str, narrow: bool):
            one_pass = wave_body(width, role)

            def step(carry):
                st, sched = carry[:2]
                n_cand = jnp.sum(_has_candidate(st.nodes).astype(jnp.int32))
                sched = sched + jnp.minimum(
                    jnp.minimum(n_cand, grow_leaves - sched), w_width)
                st = one_pass(st)
                return (st, sched) + plan(st, sched)

            return lambda carry: lax.while_loop(
                lambda c: c[2] & (c[3] == narrow), step, carry)

        narrow_passes = passes(narrow_width, HIST_NARROW, True)
        wide_passes = passes(w_width, HIST_WAVE, False)
        st = lax.while_loop(
            lambda c: c[2], lambda c: wide_passes(narrow_passes(c)),
            (st, st.n_leaves) + plan(st, st.n_leaves))[0]
    else:
        if 0 < narrow_width < w_width:
            st = lax.while_loop(
                lambda st: cond(st) & (st.n_leaves <= narrow_width),
                wave_body(narrow_width, HIST_NARROW), st)
        st = lax.while_loop(cond, wave_body(w_width, HIST_WAVE), st)
    if exact:
        with jax.named_scope("lgbtpu.replay"):
            newP, new_cat, row_leaf_new, n_leaves_f = _exact_prune(
                st.nodes, st.cand_catmask, st.row_leaf, num_leaves, cat_info)
        return (_tree_from_packed(newP, n_leaves_f, cat_info, new_cat),
                row_leaf_new, st.passes.reshape(-1, _PASS.NC).T)
    tree = _tree_from_packed(st.nodes, st.n_leaves, cat_info,
                             st.cand_catmask)
    return tree, st.row_leaf, st.passes.reshape(-1, _PASS.NC).T


# ---------------------------------------------------------------------------
# Streamed (out-of-core) grower helpers — ISSUE 7.
#
# The in-memory growers trace the whole tree as ONE device program (fori/
# while loops over a resident [n, F] matrix).  Under out-of-core training
# the matrix lives host-side in a data.BlockStore and each histogram pass
# is a HOST loop over prefetched blocks, so the growers decompose into
# jitted pieces: per-block partition+histogram kernels (row-axis work,
# called once per block) and per-iteration table updates (node-table-sized
# work, called once per split/wave).  Every piece replicates the
# corresponding in-memory computation VERBATIM on the plain numeric path
# (no categorical/monotone/extra-trees/interaction/bynode/distributed) —
# combined with the BlockStore's chunk-replicating layout rules, streamed
# trees are BIT-IDENTICAL to `grow_tree(..., row_chunk=block_rows)`
# (tests/test_streaming.py).  The host drivers live in data/stream_grow.py.
# ---------------------------------------------------------------------------


def _stream_root_core(root_hist, ctx, feature_mask):
    """Root output + candidate from an accumulated [F, B, 3] histogram
    (the streamed analogue of the growers' shared root block)."""
    root_tot = jnp.sum(root_hist[0], axis=0)                 # (g, h, c)
    root_out = constrained_leaf_output(
        root_tot[0], root_tot[1], root_tot[2],
        ctx._replace(path_smooth=jnp.float32(0.0)),
        jnp.float32(-jnp.inf), jnp.float32(jnp.inf), jnp.float32(0.0))
    root_best = find_best_split(root_hist, ctx, feature_mask,
                                jnp.bool_(True), None, mono=None,
                                parent_out=root_out, rand_bins=None)
    return root_out, root_tot, root_best


@functools.partial(jax.jit, static_argnames=("capacity",))
def stream_strict_init(root_hist, ctx, feature_mask, capacity):
    """Packed root table + the fused strict grower's aux pick row."""
    root_out, root_tot, root_best = _stream_root_core(root_hist, ctx,
                                                      feature_mask)
    P0 = _packed_root_table(capacity, root_out, root_tot, root_best, None)
    f32 = jnp.float32
    zero = jnp.float32(0.0)
    aux0 = jnp.stack([
        zero, root_best.feature.astype(f32), root_best.bin.astype(f32),
        jnp.isfinite(root_best.gain).astype(f32),
        zero, zero, zero, zero]).reshape(1, 8)
    return P0, aux0


@functools.partial(jax.jit, static_argnames=("capacity", "grow_leaves"))
def stream_wave_init(root_hist, ctx, feature_mask, capacity, grow_leaves):
    """Packed root table + per-leaf histogram cache for the wave grower."""
    root_out, root_tot, root_best = _stream_root_core(root_hist, ctx,
                                                      feature_mask)
    P0 = _packed_root_table(capacity, root_out, root_tot, root_best, None)
    cache0 = jnp.zeros((grow_leaves,) + root_hist.shape,
                       jnp.float32).at[0].set(root_hist)
    slot0 = jnp.full((capacity,), 0, jnp.int32)
    return P0, cache0, slot0


@functools.lru_cache(maxsize=None)
def _stream_root_block_fn(num_bins: int, block_rows: int, hist_impl: str,
                          hist_dtype: str):
    """Per-block root histogram partial [1, F, B, 3].

    ``row_chunk`` is pinned to ``block_rows`` so each block takes the
    single-chunk direct path of ``_hist_from_segstats`` — the SAME dot the
    in-memory op's scan body runs per chunk, which is what makes the
    block-wise partial sum bit-identical to the in-memory accumulation.
    """
    from ..ops.histogram import batched_histogram_op

    op = batched_histogram_op(1, num_bins, block_rows, hist_impl,
                              hist_dtype)

    @jax.jit
    def blk(bins_b, stats_full, off):
        nb = bins_b.shape[0]
        stats_b = lax.dynamic_slice(stats_full, (off, jnp.int32(0)),
                                    (nb, 3))
        return op(bins_b, stats_b, jnp.zeros((nb,), jnp.int32))

    return blk


@functools.lru_cache(maxsize=None)
def _stream_strict_block_fn(num_bins: int, block_rows: int, hist_impl: str,
                            hist_dtype: str):
    """One strict split iteration's ROW-AXIS work for one block: partition
    the split leaf's rows and build the {left, right, other} histogram
    partial — a verbatim per-block restatement of the fused strict body's
    XLA prologue (grow_tree's ``body_f``)."""
    from ..ops.histogram import batched_histogram_op

    op = batched_histogram_op(2, num_bins, block_rows, hist_impl,
                              hist_dtype)

    @jax.jit
    def blk(bins_b, stats_full, row_leaf_full, off, aux, n_nodes):
        nb = bins_b.shape[0]
        leaf = aux[0, 0].astype(jnp.int32)
        feat = aux[0, 1].astype(jnp.int32)
        thr = aux[0, 2].astype(jnp.int32)
        active = aux[0, 3] > 0
        nl, nr = n_nodes, n_nodes + 1
        rl_b = lax.dynamic_slice(row_leaf_full, (off,), (nb,))
        stats_b = lax.dynamic_slice(stats_full, (off, jnp.int32(0)),
                                    (nb, 3))
        col = jnp.take(bins_b.astype(jnp.int32), feat, axis=1)
        go_left = col <= thr
        new_rl = jnp.where(rl_b == leaf,
                           jnp.where(go_left, nl, nr), rl_b)
        rl2 = jnp.where(active, new_rl, rl_b)
        seg = jnp.where(rl2 == nl, 0,
                        jnp.where(rl2 == nr, 1, 2)).astype(jnp.int32)
        h = op(bins_b, stats_b, seg)                     # [2, F, B, 3]
        return lax.dynamic_update_slice(row_leaf_full, rl2, (off,)), h

    return blk


@jax.jit
def stream_strict_update(hist2, P, aux, feature_mask, ctx, max_depth,
                         n_nodes, n_leaves):
    """One strict split iteration's TABLE work: the split-iteration
    mega-kernel on the block-accumulated histogram (same call the fused
    in-memory body makes)."""
    from ..ops.histogram_pallas import split_iter_pallas

    f32 = jnp.float32
    zero = jnp.float32(0.0)
    num_features = feature_mask.shape[0]
    fmask_row = feature_mask.astype(f32).reshape(1, num_features)
    md_f = jnp.asarray(max_depth, jnp.int32).astype(f32)
    scal = jnp.stack([
        jnp.asarray(ctx.lambda_l1, f32),
        jnp.asarray(ctx.lambda_l2, f32),
        jnp.asarray(ctx.min_data_in_leaf, f32),
        jnp.asarray(ctx.min_sum_hessian, f32),
        jnp.asarray(ctx.min_gain_to_split, f32),
        jnp.asarray(ctx.max_delta_step, f32),
        jnp.asarray(ctx.path_smooth, f32),
        md_f, n_nodes.astype(f32),
        zero, zero, zero, zero, zero, zero, zero]).reshape(1, 16)
    P2, aux2 = split_iter_pallas(hist2.transpose(0, 1, 3, 2), P, fmask_row,
                                 aux, scal, pk=_PK)
    grew = jnp.where(aux[0, 3] > 0, 1, 0).astype(jnp.int32)
    return P2, aux2, n_nodes + 2 * grew, n_leaves + grew


@functools.lru_cache(maxsize=None)
def _stream_wave_block_fn(w_width: int, num_bins: int, num_features: int,
                          block_rows: int, hist_impl: str, hist_dtype: str):
    """One wave's ROW-AXIS work for one block: table-lookup routing of the
    wave's splitting leaves + the direct-child histogram partial — the
    non-fused wave body's steps 2–3 restated per block."""
    from ..ops.histogram import batched_histogram_op

    op = batched_histogram_op(w_width, num_bins, block_rows, hist_impl,
                              hist_dtype)
    # same gate as the in-memory wave body (fp_axis is None here):
    # DEFAULT-precision (bf16) lookups are exact only while every table
    # value is an integer <= 256
    exact_in_bf16 = max(num_features, 2 * w_width, num_bins) <= 256

    @jax.jit
    def blk(bins_b, stats_full, row_leaf_full, off, tbl, n_nodes):
        f32 = jnp.float32
        nb = bins_b.shape[0]
        p = lax.dynamic_slice(row_leaf_full, (off,), (nb,))
        stats_b = lax.dynamic_slice(stats_full, (off, jnp.int32(0)),
                                    (nb, 3))
        bins_i32 = bins_b.astype(jnp.int32)
        pv = lookup_rows(p, tbl,
                         precision=(lax.Precision.DEFAULT if exact_in_bf16
                                    else lax.Precision.HIGHEST))
        psel = pv[:, 0] > 0
        feat_r = pv[:, 1].astype(jnp.int32)
        thr_r = pv[:, 2]
        fmatch = (feat_r[:, None]
                  == lax.iota(jnp.int32, num_features)[None, :])
        v = jnp.sum(jnp.where(fmatch, bins_i32, 0), axis=1)
        go_left = v.astype(f32) <= thr_r
        rank2_r = pv[:, 3].astype(jnp.int32)
        child = n_nodes + rank2_r + jnp.where(go_left, 0, 1)
        row_leaf = jnp.where(psel, child, p)
        to_direct = psel & (go_left == (pv[:, 4] > 0))
        seg_id = jnp.where(to_direct, rank2_r >> 1, w_width)
        h = op(bins_b, stats_b, seg_id)                  # [W, F, B, 3]
        return lax.dynamic_update_slice(row_leaf_full, row_leaf, (off,)), h

    return blk


@functools.lru_cache(maxsize=None)
def _stream_wave_fns(capacity: int, w_width: int, grow_leaves: int,
                     num_features: int, num_bins: int, wave_tail: str):
    """(plan, update, cond) for the streamed wave grower.

    ``plan`` emits the [capacity, 5] routing table the per-block kernel
    consumes; ``update`` re-derives the wave plan from the SAME packed
    table (deterministic — identical jitted ops on identical inputs) and
    then runs the in-memory wave body's steps 4–7 verbatim; ``cond`` is
    the while-loop predicate, synced to host once per wave by the driver.
    """
    exact = wave_tail == "exact"
    neg_inf = jnp.float32(-jnp.inf)
    m = capacity
    iota_w = lax.iota(jnp.int32, w_width)
    K = _PK

    def _plan(P, n_leaves):
        gains = jnp.where(P[:, K.IS_LEAF] > 0.5, P[:, K.CAND_GAIN], neg_inf)
        sel_key = (jnp.where(P[:, K.IS_LEAF] > 0.5, P[:, K.PM], neg_inf)
                   if exact else gains)
        order = jnp.argsort(-sel_key, stable=True)
        rank = jnp.zeros(m, jnp.int32).at[order].set(lax.iota(jnp.int32, m))
        budget = grow_leaves - n_leaves
        n_cand = jnp.sum(jnp.isfinite(gains)).astype(jnp.int32)
        if wave_tail == "half":
            alloc = jnp.maximum(jnp.int32(1), budget // 2)
        else:  # "greedy" / "exact"
            alloc = budget
        s = jnp.minimum(jnp.minimum(n_cand, alloc), jnp.int32(w_width))
        sel = jnp.isfinite(gains) & (rank < s)
        parent_r = order[:w_width]
        active_r = iota_w < s
        prow = P[parent_r]
        direct_left = prow[:, K.CAND_LC] <= prow[:, K.CAND_RC]
        dl_of = _scatter(jnp.full((m,), True), parent_r, direct_left,
                         active_r)
        return (gains, rank, s, sel, parent_r, active_r, prow, direct_left,
                dl_of)

    @jax.jit
    def plan(P, n_leaves):
        f32 = jnp.float32
        _, rank, _, sel, _, _, _, _, dl_of = _plan(P, n_leaves)
        return jnp.stack([sel.astype(f32), P[:, K.CAND_FEAT],
                          P[:, K.CAND_BIN], (2 * rank).astype(f32),
                          dl_of.astype(f32)], axis=1)       # [M, 5]

    @jax.jit
    def update(P, hist_cache, node_slot, n_nodes, n_leaves, direct_hist,
               feature_mask, ctx, max_depth):
        f32 = jnp.float32
        (gains, _, s, _, parent_r, active_r, prow, direct_left,
         _) = _plan(P, n_leaves)
        nl_r = n_nodes + 2 * iota_w
        nr_r = nl_r + 1

        # step 4: sibling = parent - child from the per-leaf cache
        fb3 = num_features * num_bins * 3
        cache_flat = hist_cache.reshape(grow_leaves, fb3)
        parent_slot = node_slot[parent_r]
        oh_p = (parent_slot[:, None]
                == lax.iota(jnp.int32, grow_leaves)[None, :])
        parent_hist = lax.dot_general(
            oh_p.astype(f32), cache_flat,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=lax.Precision.HIGHEST,
        ).reshape(w_width, num_features, num_bins, 3)
        other_hist = parent_hist - direct_hist
        dl = direct_left[:, None, None, None]
        left_hist = jnp.where(dl, direct_hist, other_hist)
        right_hist = jnp.where(dl, other_hist, direct_hist)
        left_slot = parent_slot
        right_slot = n_leaves + iota_w
        slot2 = jnp.concatenate([left_slot, right_slot])
        act2w = jnp.concatenate([active_r, active_r])
        slot2m = jnp.where(act2w, slot2, -1)
        q = (lax.iota(jnp.int32, grow_leaves)[:, None] == slot2m[None, :])
        keep = 1.0 - jnp.any(q, axis=1).astype(f32)
        newvals = jnp.concatenate([left_hist, right_hist])
        cache = (cache_flat * keep[:, None] + lax.dot_general(
            q.astype(f32), newvals.reshape(2 * w_width, fb3),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=lax.Precision.HIGHEST,
        )).reshape(hist_cache.shape)
        node_slot2 = _scatter(node_slot, nl_r, left_slot, active_r)
        node_slot2 = _scatter(node_slot2, nr_r, right_slot, active_r)

        # step 5: child bounds (plain path: mono is None -> pass-through)
        wl_w, wr_w = prow[:, K.CAND_WL], prow[:, K.CAND_WR]
        lo_w, hi_w = prow[:, K.BOUND_LO], prow[:, K.BOUND_HI]
        lo_l, hi_l, lo_r, hi_r = lo_w, hi_w, lo_w, hi_w

        # step 6: score the 2W fresh children from the cache
        child_nodes = jnp.concatenate([nl_r, nr_r])
        child_hists = jnp.concatenate([left_hist, right_hist])
        child_depth1 = prow[:, K.DEPTH] + 1.0
        child_depth = jnp.concatenate([child_depth1, child_depth1])
        md = jnp.asarray(max_depth, jnp.int32)
        depth_ok = (md <= 0) | (child_depth < md.astype(f32))
        child_masks = jnp.broadcast_to(feature_mask,
                                       (2 * w_width, num_features))
        child_lo = jnp.concatenate([lo_l, lo_r])
        child_hi = jnp.concatenate([hi_l, hi_r])
        child_vals = jnp.concatenate([wl_w, wr_w])

        def score(h, mm, d, lo_, hi_, po):
            return find_best_split(h, ctx, mm, d, None, None, lo_, hi_, po)

        bs = jax.vmap(score)(child_hists, child_masks, depth_ok, child_lo,
                             child_hi, child_vals)
        active_2 = jnp.concatenate([active_r, active_r])

        # step 7: commit (two packed row scatters)
        parent_rows = prow.at[:, jnp.array([
            K.SPLIT_FEAT, K.SPLIT_BIN, K.LEFT, K.RIGHT, K.IS_LEAF,
            K.SPLIT_GAIN])].set(jnp.stack([
                prow[:, K.CAND_FEAT], prow[:, K.CAND_BIN],
                nl_r.astype(f32), nr_r.astype(f32),
                jnp.zeros(w_width), gains[parent_r]], axis=-1))
        child_rows = jnp.stack([
            jnp.full((2 * w_width,), -1.0),              # SPLIT_FEAT
            jnp.zeros((2 * w_width,)),                   # SPLIT_BIN
            jnp.full((2 * w_width,), -1.0),              # LEFT
            jnp.full((2 * w_width,), -1.0),              # RIGHT
            child_vals,                                  # LEAF_VALUE
            jnp.ones((2 * w_width,)),                    # IS_LEAF
            jnp.concatenate([prow[:, K.CAND_LC],
                             prow[:, K.CAND_RC]]),       # COUNT
            jnp.zeros((2 * w_width,)),                   # SPLIT_GAIN
            child_depth,                                 # DEPTH
            bs.gain,                                     # CAND_GAIN
            bs.feature.astype(f32),                      # CAND_FEAT
            bs.bin.astype(f32),                          # CAND_BIN
            bs.left_g, bs.left_h, bs.left_c,
            bs.right_g, bs.right_h, bs.right_c,
            bs.left_out,                                 # CAND_WL
            bs.right_out,                                # CAND_WR
            child_lo,                                    # BOUND_LO
            child_hi,                                    # BOUND_HI
            jnp.zeros((2 * w_width,)),                   # CAND_CAT
            jnp.minimum(jnp.concatenate([prow[:, K.PM], prow[:, K.PM]]),
                        bs.gain),                        # PM
        ], axis=-1)                                      # [2W, NC]
        oob = jnp.int32(capacity)
        P2 = P.at[jnp.where(active_r, parent_r, oob)].set(
            parent_rows, mode="drop")
        kid_idx = jnp.where(active_2, child_nodes, oob)
        P2 = P2.at[kid_idx].set(child_rows, mode="drop")
        return (P2, cache, node_slot2, n_nodes + 2 * s, n_leaves + s)

    @functools.partial(jax.jit, static_argnames=("num_leaves",))
    def cond(P, n_leaves, num_leaves):
        gains = jnp.where(P[:, K.IS_LEAF] > 0.5, P[:, K.CAND_GAIN], neg_inf)
        go = (n_leaves < grow_leaves) & jnp.any(jnp.isfinite(gains))
        # exact tail: a saved pass is a saved re-stream of the store
        return go & ~_replay_certified(P, num_leaves) if exact else go

    return plan, update, cond


@functools.partial(jax.jit, static_argnames=("num_leaves",))
def stream_exact_prune(P, row_leaf, num_leaves):
    """Exact-tail replay for the streamed wave grower (plain numeric path:
    no categorical masks)."""
    newP, _, row_leaf_new, n_leaves_f = _exact_prune(P, None, row_leaf,
                                                     num_leaves, None)
    return newP, row_leaf_new, n_leaves_f


def empty_forest(num_trees: int, num_leaves: int) -> Tree:
    """Stacked all-stump forest used as a fixed-capacity accumulator."""
    capacity = 2 * num_leaves - 1

    def full(val, dtype):
        return jnp.full((num_trees, capacity), val, dtype)

    return Tree(
        split_feature=full(-1, jnp.int32),
        split_bin=full(0, jnp.int32),
        left=full(-1, jnp.int32),
        right=full(-1, jnp.int32),
        leaf_value=full(0.0, jnp.float32),
        is_leaf=full(False, jnp.bool_).at[:, 0].set(True),
        count=full(0.0, jnp.float32),
        split_gain=full(0.0, jnp.float32),
        num_leaves=jnp.ones((num_trees,), jnp.int32),
    )


def fit_linear_leaves(tree: Tree, row_leaf: jnp.ndarray, xraw: jnp.ndarray,
                      g: jnp.ndarray, h: jnp.ndarray, bag: jnp.ndarray,
                      linear_lambda, k_feats: int,
                      row_chunk: int = 131072,
                      axis_name: Optional[str] = None
                      ) -> Tuple[Tree, jnp.ndarray]:
    """Fit ridge-regularized linear models in every leaf (upstream
    ``linear_tree``, src/treelearner/linear_tree_learner.cpp re-derived
    tensor-first).

    Upstream solves one small normal-equations system per leaf over the
    leaf's path features, serially with Eigen.  Here all leaves solve at
    once: per-leaf path feature lists come from one structure sweep, the
    per-leaf Gram matrices ``A_l = Z^T H Z`` and moments ``b_l = Z^T g``
    accumulate via a one-hot matmul over row chunks (the histogram trick,
    MXU-friendly), and a single batched ``jnp.linalg.solve`` finishes.
    The Newton objective ``sum_i [g_i f(x_i) + 0.5 h_i f(x_i)^2]`` with
    ridge ``linear_lambda`` gives ``(Z^T H Z + lam I) beta = -Z^T g``.

    Leaves where the solve is singular/non-finite or with fewer than
    ``k_feats + 2`` rows keep their constant Newton value (upstream's
    fallback).  The first ``k_feats`` distinct path features participate
    (upstream uses all; deep paths truncate — documented divergence).
    NaN raw values impute 0 for both fit and predict.

    Returns (tree with linear_feat/linear_coef/leaf_value set,
    per-row prediction delta f(x_i) of THIS tree).
    """
    n, num_features = xraw.shape
    capacity = tree.capacity
    kp1 = k_feats + 1
    lam = jnp.asarray(linear_lambda, jnp.float32)

    # 1. per-leaf path feature lists: one forward sweep (children are
    # created after parents, so parents resolve first).
    flist0 = jnp.full((capacity, k_feats), -1, jnp.int32)
    fcnt0 = jnp.zeros((capacity,), jnp.int32)

    def sweep(i, carry):
        flist, fcnt = carry
        internal = (~tree.is_leaf[i]) & (tree.left[i] >= 0)
        f = tree.split_feature[i]
        present = jnp.any(flist[i] == f)
        can_add = (~present) & (fcnt[i] < k_feats)
        child_list = jnp.where(
            can_add,
            flist[i].at[jnp.clip(fcnt[i], 0, k_feats - 1)].set(f),
            flist[i])
        child_cnt = fcnt[i] + can_add.astype(jnp.int32)

        def put(dst_l, dst_c, child):
            ok = internal & (child >= 0)
            safe = jnp.where(ok, child, capacity)
            return (dst_l.at[safe].set(child_list, mode="drop"),
                    dst_c.at[safe].set(child_cnt, mode="drop"))

        flist, fcnt = put(flist, fcnt, tree.left[i])
        flist, fcnt = put(flist, fcnt, tree.right[i])
        return flist, fcnt

    flist, _ = lax.fori_loop(0, capacity, sweep, (flist0, fcnt0))

    # 2. per-row design Z = [x_pathfeats, 1] with NaN->0 and pad-slot->0.
    feats = flist[row_leaf]                              # [n, K]
    xg = jnp.take_along_axis(xraw, jnp.maximum(feats, 0), axis=1)
    xg = jnp.where((feats >= 0) & jnp.isfinite(xg), xg, 0.0)
    z = jnp.concatenate([xg, jnp.ones((n, 1), jnp.float32)], axis=1)

    # 3. accumulate A = Z^T H Z and b = Z^T g per leaf, chunked one-hot
    # matmuls (histogram formulation).  Rows are padded up to a chunk
    # multiple with zero g/h so every chunk slice is in-bounds and padded
    # rows contribute exactly nothing (code-review r2: a clamped
    # dynamic_slice double-counts the tail).
    gb = g * bag
    hb = h * bag
    n_chunks = max(-(-n // row_chunk), 1)
    n_fit = n_chunks * row_chunk if n > row_chunk else n
    if n_fit != n:
        pad = n_fit - n
        z = jnp.pad(z, ((0, pad), (0, 0)))
        row_leaf_f = jnp.pad(row_leaf, (0, pad))
        gb = jnp.pad(gb, (0, pad))
        hb = jnp.pad(hb, (0, pad))
    else:
        row_leaf_f = row_leaf

    def chunk(ci, acc):
        A, bvec = acc
        s = ci * (row_chunk if n > row_chunk else n)
        c = row_chunk if n > row_chunk else n
        zc = lax.dynamic_slice_in_dim(z, s, c, 0)
        rlc = lax.dynamic_slice_in_dim(row_leaf_f, s, c, 0)
        gc = lax.dynamic_slice_in_dim(gb, s, c, 0)
        hc = lax.dynamic_slice_in_dim(hb, s, c, 0)
        onehot = (rlc[:, None]
                  == lax.iota(jnp.int32, capacity)[None]).astype(jnp.float32)
        zz = zc[:, :, None] * zc[:, None, :]             # [c, K+1, K+1]
        A = A + jnp.einsum("cm,cij,c->mij", onehot, zz, hc)
        bvec = bvec + jnp.einsum("cm,ci,c->mi", onehot, zc, gc)
        return A, bvec

    A0 = jnp.zeros((capacity, kp1, kp1), jnp.float32)
    b0 = jnp.zeros((capacity, kp1), jnp.float32)
    if n <= row_chunk:
        A, bvec = chunk(0, (A0, b0))
    else:
        A, bvec = lax.fori_loop(0, n_chunks, chunk, (A0, b0))
    if axis_name is not None:
        # data-parallel linear leaves: per-shard Gram/moment partials
        # merge with one psum (the same allreduce shape as the histogram
        # merge), then every shard solves the identical batched system —
        # coefficients replicated by construction
        A = lax.psum(A, axis_name)
        bvec = lax.psum(bvec, axis_name)

    eye = jnp.eye(kp1, dtype=jnp.float32)
    beta = jnp.linalg.solve(A + (lam + 1e-6) * eye[None],
                            -bvec[..., None])[..., 0]    # [M, K+1]

    ok = (tree.is_leaf
          & jnp.all(jnp.isfinite(beta), axis=-1)
          & (tree.count >= kp1 + 1))
    coef = jnp.where(ok[:, None], beta[:, :k_feats], 0.0)
    intercept = jnp.where(ok, beta[:, k_feats], tree.leaf_value)
    new_tree = tree._replace(leaf_value=intercept, linear_feat=flist,
                             linear_coef=coef)
    delta = intercept[row_leaf] + jnp.sum(coef[row_leaf] * xg, axis=1)
    return new_tree, delta


# ---------------------------------------------------------------------------
# Checkpoint codec (r13): a Tree as a flat dict of host arrays and back.
# Unlike the JSON model format (utils/serialize.py) this is BIT-EXACT —
# float fields round-trip as raw f32 buffers, never through decimal — so
# resumed training replays the identical forest the interrupted run held.
# Handles single-class [M] and stacked multiclass [K, M] field layouts
# uniformly (np.asarray carries whatever rank the field has).
# ---------------------------------------------------------------------------

_TREE_OPTIONAL_FIELDS = ("is_cat_split", "cat_mask", "linear_feat",
                         "linear_coef")


def tree_to_arrays(tree: Tree) -> dict:
    """Tree -> ``{field: np.ndarray}`` (optional None fields omitted)."""
    import numpy as np

    out = {}
    for name, val in zip(Tree._fields, tree):
        if val is None:
            continue
        out[name] = np.asarray(val)
    return out


def tree_from_arrays(arrays: dict) -> Tree:
    """Inverse of :func:`tree_to_arrays` (device arrays, lazily put)."""
    kw = {}
    for name in Tree._fields:
        if name in arrays:
            kw[name] = jnp.asarray(arrays[name])
        elif name in _TREE_OPTIONAL_FIELDS:
            kw[name] = None
        else:
            raise KeyError(f"tree checkpoint missing field {name!r}")
    return Tree(**kw)
