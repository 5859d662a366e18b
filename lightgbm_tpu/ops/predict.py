"""Forest prediction over binned inputs.

TPU-native replacement for LightGBM's per-row per-tree pointer-chasing
``Predictor`` (SURVEY.md §3.1 bottom frame).  Trees are tensors (struct-of-
arrays), so traversal is a fixed-trip gather loop: every row steps one level
per iteration; rows already at a leaf stay put (self-loop), making the loop a
fixpoint after ``depth`` iterations.

The TREE axis is vmapped, not scanned: a forest of T trees traverses in
``depth_cap`` sequential steps of [chunk, n]-wide gathers instead of
``T * depth_cap`` skinny steps — two orders of magnitude fewer device ops
for reference-sized forests.  Trees are processed in chunks (default 32) so
the [chunk, n] node state stays bounded for million-row batches, and a
traced round mask gives staged prediction (``ntree_limit``/``num_iteration``
truncation — the xgb staged-predict contract of bagging_boosting.ipynb:136,
SURVEY.md §3.4) with no recompilation.

r18 gives the SERVING hot path its own mega-kernel (ROADMAP item 3, the
r7 treatment): :func:`predict_forest_pallas` fuses level-synchronous
traversal of every tree with leaf-value accumulation into ONE Pallas
kernel over :class:`ForestSoA` — depth-major SoA node tables padded to
(sublane, 128)-lane tiles that keep the COMPACT quantized dtypes
resident (uint8 thresholds, int16 indices, int8/bf16 leaves; no
dequantize pass, no f32 node table in HBM).  Thresholds compare as the
stored bin codes; the per-tree dequant scale folds into the traced
round mask so leaf contributions accumulate in f32 with the scale
applied once per tree.  The chunked scan path above remains the
training-side predictor and the semantics oracle.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .members import go_left, split_route

DEFAULT_TREE_CHUNK = 32

# --- fused predict mega-kernel (r18) constants ------------------------------
# Node slots pad to a full lane so every one-hot contraction runs on
# (sublane, 128)-aligned tiles; rows ride the 128-lane minor axis.
PREDICT_NODE_PAD = 128
PREDICT_ROW_BLOCK = 128
# Tree-chunk (sublane) grouping per precision: the compact dtypes set the
# minimum legal sublane tile — uint8 thresholds / int8 leaves need 32,
# an all-i32/f32 forest needs only 8 (pallas_guide.md tiling table).
PREDICT_TREE_CHUNKS = {"f32": 8, "bf16": 32, "int8": 32}
# In-kernel working set: a tree chunk is traversed PREDICT_SUB_TREES (one
# f32 sublane tile) trees at a time and every one-hot gather covers at
# most PREDICT_NODE_CHUNK node lanes, so the [sub, chunk, R] f32 one-hot
# is 1 MiB whatever the forest's chunk or node capacity.  The chip's
# compiler keeps about four such buffers live; unbounded, the int8
# layout's [32, 256, 128] one-hots alone overran the 16 MiB scoped-VMEM
# limit (analysis.vmem.predict_forest_bytes models the bounded version).
PREDICT_SUB_TREES = 8
PREDICT_NODE_CHUNK = 256

# tools/hlo_counts.py + analysis.budgets flip this to compile the serving
# predict program with the mega-kernel replaced by a pure_callback, so a
# CPU HLO shows the same launch structure a TPU build has — XLA-side
# fusions plus ONE custom-call per class (interpret mode would inline the
# kernel instead).  Never set in production.
_PREDICT_OPCOUNT_STUB = False


class ForestSoA(NamedTuple):
    """Depth-major SoA node tables — the fused kernel's residency format.

    All arrays carry a leading padded tree axis ``Tp`` (multiple of the
    precision's sublane chunk) and a node axis ``Mp`` (multiple of 128
    lanes).  Dtypes are the COMPACT storage dtypes of the quantized
    layout contract (``ops.quantize.PACKED_NODE_BYTES``): these buffers
    are what stays resident in HBM; the kernel widens per-block tiles to
    f32 transiently in VMEM.  Leaves and dead slots self-loop
    (``left == right == self``), so traversal needs no ``is_leaf``
    lookup — the array is kept purely as the residency-parity byte of
    the layout contract and for host-side audits.
    """

    split_feature: jnp.ndarray   # [Tp, Mp] i16 (quantized) / i32 (f32)
    split_bin: jnp.ndarray       # [Tp, Mp] u8 (quantized) / i32 (f32)
    left: jnp.ndarray            # [Tp, Mp] i16 / i32 — self-loop at leaves
    right: jnp.ndarray           # [Tp, Mp] i16 / i32 — self-loop at leaves
    leaf: jnp.ndarray            # [Tp, Mp] i8 / bf16 / f32 quantized leaves
    is_leaf: jnp.ndarray         # [Tp, Mp] bool (residency parity only)
    scale: jnp.ndarray           # [Tp] f32 per-tree dequant scale (1.0s
    #                              for f32/bf16 — applied once at the end)


def soa_tree_chunk(soa: ForestSoA) -> int:
    """Sublane tree-chunk this SoA's dtypes require (8 or 32)."""
    narrow = min(soa.split_bin.dtype.itemsize, soa.leaf.dtype.itemsize)
    return 8 if narrow >= 4 else 32


def _depth_major_order(left_t: np.ndarray, right_t: np.ndarray,
                       is_leaf_t: np.ndarray) -> np.ndarray:
    """BFS node permutation for one tree: every level's nodes contiguous
    (depth-major), unreachable slots appended last.  Terminates for any
    input because each frontier only admits unseen nodes."""
    m = left_t.shape[0]
    seen = np.zeros(m, bool)
    seen[0] = True
    frontier = np.array([0], np.int64)
    levels = []
    while frontier.size:
        levels.append(frontier)
        internal = frontier[~is_leaf_t[frontier]]
        kids = np.concatenate([left_t[internal], right_t[internal]])
        kids = np.unique(kids[(kids >= 0) & (kids < m)])
        kids = kids[~seen[kids]]
        seen[kids] = True
        frontier = kids
    dead = np.flatnonzero(~seen)
    return np.concatenate(levels + [dead]).astype(np.int64)


def pack_forest_soa(split_feature, split_bin, left, right, leaf_value,
                    is_leaf, *, precision: str = "f32",
                    leaf_scale=None, node_pad: int = PREDICT_NODE_PAD,
                    tree_multiple: Optional[int] = None) -> ForestSoA:
    """Host-side layout specialization: per-node arrays -> ForestSoA.

    Reorders every tree depth-major (BFS), folds leaves and dead slots
    into self-loops, pads nodes to a 128-lane multiple and trees to the
    precision's sublane chunk, and PRESERVES the compact storage dtypes
    — for int8/bf16 forests no f32 (or even i32) node table is ever
    built; the quantized arrays go to the device as stored.  Thresholds
    stay the exact uint8 bin codes (``ops.quantize`` already refused any
    forest where they would not fit exactly), so the kernel's
    ``code <= threshold`` comparison in f32 lanes is the SAME integer
    comparison the f32 path makes: quantized-space routing is exact, not
    a tolerance (PARITY.md).

    Args are host numpy arrays shaped ``[T, M]`` (one class);
    ``leaf_value`` is the precision's storage representation (i8 codes
    for int8, bf16-rounded values for bf16, plain f32 otherwise) and
    ``leaf_scale`` the int8 per-tree dequant scale.
    """
    if precision not in PREDICT_TREE_CHUNKS:
        raise ValueError(f"precision must be one of "
                         f"{tuple(PREDICT_TREE_CHUNKS)}, got {precision!r}")
    feat = np.asarray(split_feature)
    thr = np.asarray(split_bin)
    left = np.asarray(left)
    right = np.asarray(right)
    leaf = np.asarray(leaf_value)
    is_leaf = np.asarray(is_leaf, bool)
    t, m = feat.shape
    if t and m > (1 << 24):
        raise ValueError("node capacity exceeds the f32-exact integer "
                         "range the one-hot gathers rely on")

    mp = max(node_pad, -(-m // node_pad) * node_pad)
    chunk = PREDICT_TREE_CHUNKS[precision]
    if tree_multiple is not None:
        chunk = max(chunk, int(tree_multiple))
    tp = max(chunk, -(-t // chunk) * chunk)

    if precision == "f32":
        idx_t, thr_t, leaf_t = np.int32, np.int32, np.float32
    else:
        idx_t, thr_t = np.int16, np.uint8
        leaf_t = np.int8 if precision == "int8" else np.float32

    self_loop = np.arange(mp)
    o_feat = np.zeros((tp, mp), idx_t)
    o_thr = np.zeros((tp, mp), thr_t)
    o_left = np.broadcast_to(self_loop, (tp, mp)).astype(idx_t)
    o_right = o_left.copy()
    o_left = o_left.copy()
    o_leaf = np.zeros((tp, mp), leaf_t)
    o_isleaf = np.ones((tp, mp), bool)

    for ti in range(t):
        perm = _depth_major_order(left[ti], right[ti], is_leaf[ti])
        inv = np.empty(m, np.int64)
        inv[perm] = np.arange(m)
        lf, at_leaf = leaf[ti][perm], is_leaf[ti][perm]
        l_old, r_old = left[ti][perm], right[ti][perm]
        internal = ~at_leaf & (l_old >= 0) & (r_old >= 0)
        new_i = np.arange(m)
        o_feat[ti, :m] = np.where(internal, feat[ti][perm], 0)
        o_thr[ti, :m] = np.where(internal, thr[ti][perm], 0)
        o_left[ti, :m] = np.where(internal, inv[np.clip(l_old, 0, m - 1)],
                                  new_i)
        o_right[ti, :m] = np.where(internal, inv[np.clip(r_old, 0, m - 1)],
                                   new_i)
        # dead slots are self-loops with a zero leaf — grower sentinels
        # in unreachable slots must never leak into the leaf table
        o_leaf[ti, :m] = np.where(at_leaf, lf, 0)
        o_isleaf[ti, :m] = ~internal

    scale = np.ones(tp, np.float32)
    if leaf_scale is not None:
        scale[:t] = np.asarray(leaf_scale, np.float32)

    leaf_dev = (jnp.asarray(o_leaf, jnp.bfloat16) if precision == "bf16"
                else jnp.asarray(o_leaf))
    return ForestSoA(
        split_feature=jnp.asarray(o_feat), split_bin=jnp.asarray(o_thr),
        left=jnp.asarray(o_left), right=jnp.asarray(o_right),
        leaf=leaf_dev, is_leaf=jnp.asarray(o_isleaf),
        scale=jnp.asarray(scale))


def predict_node_chunk(mp: int) -> int:
    """Node lanes per one-hot gather for a table of ``mp`` padded slots:
    the largest of 256/128 that divides ``mp`` (always a 128 multiple)."""
    return PREDICT_NODE_CHUNK if mp % PREDICT_NODE_CHUNK == 0 \
        else PREDICT_NODE_PAD


def _forest_kernel(bins_ref, feat_ref, thr_ref, left_ref, right_ref,
                   leaf_ref, sm_ref, out_ref, tab_ref, lv_ref, *,
                   depth_cap: int):
    """One (row-block, tree-chunk) grid step of the fused mega-kernel.

    Level-synchronous traversal: every row advances one level per
    iteration across a sub-chunk of trees at once; leaves self-loop so
    after ``depth_cap`` steps every lane sits on its leaf.  All gathers
    are one-hot contractions over exact small integers held in f32
    lanes (the repo's histogram-kernel idiom — TPU has no VMEM gather),
    so routing is exact; only the leaf-value accumulation is real f32
    arithmetic.  The tree-chunk grid axis revisits the output block and
    accumulates (``@pl.when`` zero-init on the first chunk).

    The compact tables widen ONCE per grid step into the f32 scratch
    ``tab_ref`` ``[5, Tc/8, 8, Mp]`` (integers go through int32: Mosaic
    has no direct u8/i16/i8 -> f32 cast); the traversal then loops over
    8-tree sub-chunks and, inside every gather, over node chunks, which
    bounds the one-hot working set (module constants above).  Leaf
    values land in ``lv_ref`` ``[Tc/8, 8, R]`` and reduce over the whole
    chunk in one sum, the same reduction the unchunked kernel made."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    sub = PREDICT_SUB_TREES
    tc, mp = feat_ref.shape
    fp, r = bins_ref.shape
    n_sub = tc // sub
    nch = predict_node_chunk(mp)

    for k, ref in enumerate((feat_ref, thr_ref, left_ref, right_ref,
                             leaf_ref)):
        x = ref[:]                                # [Tc, Mp] storage dtype
        if np.issubdtype(ref.dtype, np.integer):  # static: storage dtype
            x = x.astype(jnp.int32)
        x = x.astype(jnp.float32)
        for j in range(n_sub):
            tab_ref[k, j] = x[j * sub:(j + 1) * sub]

    bins = bins_ref[:]                            # [Fp, R] f32 bin codes
    iota_n = lax.broadcasted_iota(jnp.int32, (sub, nch, r), 1)
    iota_f = lax.broadcasted_iota(jnp.int32, (sub, fp, r), 1)

    def gather(j, node, tables):
        """Values of ``tables`` (indices into tab_ref) at ``node``
        ``[sub, R]`` for sub-chunk ``j``: one shared one-hot per node
        chunk, summed over chunks (exactly one chunk holds the hit)."""
        def chunk(c, accs):
            base = pl.multiple_of(c * nch, nch)
            oh = ((node - base)[:, None, :] == iota_n).astype(jnp.float32)
            return tuple(
                a + jnp.sum(
                    oh * tab_ref[k, j, :, pl.ds(base, nch)][:, :, None],
                    axis=1)
                for a, k in zip(accs, tables))

        zero = jnp.zeros((sub, r), jnp.float32)
        return lax.fori_loop(0, mp // nch, chunk, (zero,) * len(tables))

    def traverse(j, _):
        def step(_, node):
            f_g, t_g, l_g, r_g = gather(j, node, (0, 1, 2, 3))
            code = jnp.sum(
                (f_g.astype(jnp.int32)[:, None, :] == iota_f)
                .astype(jnp.float32) * bins[None, :, :], axis=1)
            # quantized-space routing: code and threshold are both exact
            # integers in f32 lanes, so <= is the stored-bin comparison
            return jnp.where(code <= t_g, l_g, r_g).astype(jnp.int32)

        node = lax.fori_loop(0, depth_cap, step,
                             jnp.zeros((sub, r), jnp.int32))
        (lv_ref[j],) = gather(j, node, (4,))
        return _

    lax.fori_loop(0, n_sub, traverse, 0)
    lv = lv_ref[:].reshape(tc, r)                 # [Tc, R]
    out_ref[...] += jnp.sum(lv * sm_ref[:], axis=0)[None, :]


def predict_forest_pallas(
    soa: ForestSoA,
    bins: jnp.ndarray,
    learning_rate,
    init_score,
    num_iteration: jnp.ndarray,
    depth_cap: int,
    start_iteration: jnp.ndarray = 0,
    row_block: int = PREDICT_ROW_BLOCK,
    interpret: Optional[bool] = None,
    name: str = "lgbtpu_predict_forest",
) -> jnp.ndarray:
    """Fused forest predict: ONE Pallas kernel launch per forest.

    Replaces the chunked scan-of-scans device path (``T/chunk *
    depth_cap`` skinny launches) with a single kernel whose grid tiles
    (row-block x tree-chunk); traversal + leaf accumulation fuse, the
    quantized node tables are read directly in storage dtype, and the
    per-tree dequant scale folds into the traced round mask so it is
    applied exactly once per tree at the end.  The staged-prediction
    contract holds: ``num_iteration``/``start_iteration`` are traced
    operands of the scale*mask vector, never compile-time constants.

    Returns ``init_score + learning_rate * sum(masked leaf values)`` as
    f32 ``[n]`` — same contract as :func:`predict_forest_binned`.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    import functools

    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    n, f = bins.shape
    tp, mp = soa.split_feature.shape
    tc = soa_tree_chunk(soa)
    if tp % tc:
        raise ValueError(f"SoA tree axis {tp} is not a multiple of its "
                         f"sublane chunk {tc} — use pack_forest_soa")
    n_tc = tp // tc
    rb = row_block          # static python int — part of the compile key
    n_pad = max(rb, -(-n // rb) * rb)
    n_rb = n_pad // rb
    fp = max(8, -(-f // 8) * 8)

    # rows ride the 128-lane minor axis: [Fp, n_pad] f32 (bin codes are
    # exact small integers; padded rows traverse on zero codes and are
    # sliced off, padded features are never referenced)
    bins_t = jnp.pad(bins.astype(jnp.float32).T,
                     ((0, fp - f), (0, n_pad - n)))
    start = jnp.asarray(start_iteration, jnp.int32)
    num_it = jnp.asarray(num_iteration, jnp.int32)
    t_idx = jnp.arange(tp, dtype=jnp.int32)
    use = (t_idx >= start) & (t_idx < start + num_it)
    sm = (use.astype(jnp.float32) * soa.scale)[:, None]     # [Tp, 1]

    if _PREDICT_OPCOUNT_STUB:
        # op-count probe: swap the kernel for a pure_callback so a CPU
        # compile shows the TPU launch structure (one custom-call per
        # forest).  Compile-only; never executed.
        out = jax.pure_callback(
            lambda b, s: np.zeros((1, b.shape[1]), np.float32),
            jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
            bins_t, sm, vmap_method="legacy_vectorized")
    else:
        kernel = functools.partial(_forest_kernel, depth_cap=depth_cap)
        tbl_spec = pl.BlockSpec((tc, mp), lambda r_, c: (c, 0))
        n_sub = tc // PREDICT_SUB_TREES
        out = pl.pallas_call(
            kernel,
            grid=(n_rb, n_tc),
            in_specs=[
                pl.BlockSpec((fp, rb), lambda r_, c: (0, r_)),
                tbl_spec, tbl_spec, tbl_spec, tbl_spec, tbl_spec,
                pl.BlockSpec((tc, 1), lambda r_, c: (c, 0)),
            ],
            out_specs=pl.BlockSpec((1, rb), lambda r_, c: (0, r_)),
            out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
            scratch_shapes=[
                pltpu.VMEM((5, n_sub, PREDICT_SUB_TREES, mp), jnp.float32),
                pltpu.VMEM((n_sub, PREDICT_SUB_TREES, rb), jnp.float32),
            ],
            interpret=interpret,
            name=name,
        )(bins_t, soa.split_feature, soa.split_bin, soa.left,
          soa.right, soa.leaf, sm)

    return init_score + learning_rate * out[0, :n]


def _walk_step(tree, bins, members):
    """``node -> next node`` of one tree over binned rows: a split on
    feature f at bin t reads the row's code of f and goes left iff it is
    ``<= t``; over an EFB table's bundle columns (``members``) it reads
    f's column and applies the split's range (``ops.members``)."""
    if members is None:
        col = tree.split_feature
    else:
        col, lo, hi, inv = split_route(members, tree.split_feature,
                                       tree.split_bin)

    def advance(node):
        code = jnp.take_along_axis(bins, col[node][:, None], axis=1)[:, 0]
        if members is None:
            left = code <= tree.split_bin[node]
        else:
            left = go_left(code, lo[node], hi[node], inv[node])
        if tree.is_cat_split is not None:
            left = jnp.where(tree.is_cat_split[node],
                             tree.cat_mask[node, code], left)
        nxt = jnp.where(left, tree.left[node], tree.right[node])
        return jnp.where(tree.is_leaf[node], node, nxt)

    return advance


def predict_tree_binned(tree, bins: jnp.ndarray,
                        max_depth_cap=None, members=None) -> jnp.ndarray:
    """Leaf value per row for one tensorized tree.

    Args:
      tree: Tree namedtuple of arrays (see models.tree.Tree).
      bins: uint8/int32 [n, F] binned features (an EFB table's bundle
        columns with ``members``, ``ops.members.Members``).
      max_depth_cap: static traversal depth bound (num_leaves is always
        safe; ``forest_depth_cap`` gives the tight bound).  ``None`` runs
        a convergence-checked ``while_loop`` instead — it iterates
        exactly the tree's ACTUAL depth (wave-grown trees are usually
        ~10 deep where num_leaves-1 would be 126 scan steps; an
        optimistic static bound is UNSOUND because wave growth can stall
        to one split per wave — code review r5).  The convergence loop is
        additionally bounded by node capacity: any valid path visits each
        node at most once, so a tree that has not converged after
        ``capacity`` steps is malformed (cycle / dangling children — e.g.
        an untrusted loaded model) and traversal stops instead of hanging
        (ADVICE r5; the serving ingest validator rejects such trees with
        an error before they ever reach traversal).

    Returns f32 [n] raw leaf values (no shrinkage applied).
    """
    n = bins.shape[0]
    bins = bins.astype(jnp.int32)
    advance = _walk_step(tree, bins, members)
    node0 = jnp.zeros(n, dtype=jnp.int32)
    if max_depth_cap is None:
        capacity = tree.is_leaf.shape[-1]
        node, _ = lax.while_loop(
            lambda c: jnp.any(~tree.is_leaf[c[0]]) & (c[1] < capacity),
            lambda c: (advance(c[0]), c[1] + 1),
            (node0, jnp.int32(0)))
    else:
        node, _ = lax.scan(lambda nd, _: (advance(nd), None), node0, None,
                           length=max_depth_cap)
    return tree.leaf_value[node]


def forest_depth_cap(forest) -> int:
    """Tight traversal bound: 1 + the deepest internal path in the forest.

    Host-side BFS over the (tiny) node arrays; grown trees are usually far
    shallower than the worst-case ``num_leaves`` bound, and the traversal
    cost is linear in this cap.
    """
    left = np.asarray(forest.left)
    right = np.asarray(forest.right)
    left = left.reshape(-1, left.shape[-1])
    right = right.reshape(-1, right.shape[-1])
    t, m = left.shape
    # node depth by propagation: children are always created after their
    # parent (higher node id), so one ascending id sweep settles all depths
    depth = np.zeros((t, m), np.int64)
    rows = np.arange(t)
    for node in range(m):
        l, r = left[:, node], right[:, node]
        has = l >= 0
        d = depth[rows, node] + 1
        depth[rows[has], l[has]] = d[has]
        has_r = r >= 0
        depth[rows[has_r], r[has_r]] = d[has_r]
    return int(depth.max()) + 1


def predict_forest_binned(
    forest,
    bins: jnp.ndarray,
    learning_rate,
    init_score,
    num_iteration: jnp.ndarray,
    max_depth_cap: int,
    start_iteration: jnp.ndarray = 0,
    tree_chunk: int = DEFAULT_TREE_CHUNK,
    members=None,
) -> jnp.ndarray:
    """Sum of trees [start_iteration, start_iteration + num_iteration) —
    traced truncation, so staged prediction needs no recompilation.

    forest: Tree namedtuple whose arrays have a leading [T] tree axis;
    ``members`` as for :func:`predict_tree_binned`.
    """
    n = bins.shape[0]
    num_trees = forest.leaf_value.shape[0]
    start_iteration = jnp.asarray(start_iteration, jnp.int32)
    bins = bins.astype(jnp.int32)

    chunk = min(tree_chunk, num_trees)
    n_chunks = -(-num_trees // chunk)
    pad = n_chunks * chunk - num_trees
    if pad:
        # zero-padded trees: node 0 self-loops with leaf_value 0 and the
        # round mask excludes them anyway
        forest = jax.tree.map(
            lambda a: jnp.concatenate(
                [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)]), forest)
    chunked = jax.tree.map(
        lambda a: a.reshape((n_chunks, chunk) + a.shape[1:]), forest)

    def traverse_one(tree):
        advance = _walk_step(tree, bins, members)
        node, _ = lax.scan(lambda nd, _: (advance(nd), None),
                           jnp.zeros(n, jnp.int32), None,
                           length=max_depth_cap)
        return tree.leaf_value[node]

    def chunk_body(acc, xs):
        tree_chunked, c = xs
        vals = jax.vmap(traverse_one)(tree_chunked)          # [chunk, n]
        t_idx = c * chunk + jnp.arange(chunk)
        use = ((t_idx >= start_iteration)
               & (t_idx < start_iteration + num_iteration))
        return acc + jnp.sum(vals * use[:, None], axis=0), None

    acc0 = jnp.zeros(n, jnp.float32)
    acc, _ = lax.scan(chunk_body, acc0, (chunked, jnp.arange(n_chunks)))
    return init_score + learning_rate * acc
