"""Pallas TPU kernel for histogram construction.

Same contract as ``histogram.compute_histograms`` (the GBDT hot loop —
LightGBM's OpenMP ConstructHistogram, SURVEY.md §2C) but with the one-hot
matmul staged through VMEM instead of materializing [rows, bins] one-hots in
HBM:

  grid = (row_chunks,); each program
    - loads a [CHUNK, F] tile of bin codes and a [CHUNK, K*S] tile of
      segment-weighted statistics into VMEM,
    - for each feature, builds the [CHUNK, B] one-hot ON-CHIP and contracts
      it against the stats tile on the MXU,
    - accumulates into the full [F, B, K*S] histogram, which stays resident
      in VMEM across all row chunks (classic reduction-grid pattern).

HBM traffic drops from O(n*B) (materialized one-hot) to O(n*(F + K*S)) —
the data is read once.

F is the caller's column space: r20 feature screening hands this kernel a
compacted ``[N, F_active]`` view, shrinking both the VMEM-resident
``[F, B, K*S]`` accumulator and the per-tile contraction work; exactly two
program shapes exist per config (full F and the static F_active).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 2048

# int8 histogram mode quantizes stats to [-127, 127] and accumulates in
# int32: a (segment, bin) cell holding more than 2^31/127 rows of the
# channel-max value wraps SILENTLY.  Total rows per shard bounds any
# cell's count, so callers guard n against this limit (exact, not the
# old conservative 16M figure).
INT8_ACC_ROW_LIMIT = (1 << 31) // 127          # 16,909,320

# The partition-fused pass turns its dot (_accumulate_wave) up to this many
# statistics columns (3 a segment).  Measured on a v5e, a pass over
# 10,500,096 x 28 (bfloat16) and over 400,128 x 2,000 (hi/lo, two calls), ms:
#   K          3      6      12     24     48     96     126
#   unturned   109.1  109.2  109.4  109.3  110.1  111.8  113.1
#   turned     60.0   60.0   60.4   61.0   62.6   88.7   113.6
#   unturned   612.7  612.8  615.4  617.1  622.7  636.3  645.2
#   turned     339.9  339.8  343.5  348.5  360.2  509.9  648.2
# Turned, something other than the MXU (the one-hot's construction) sets a
# floor of 0.53 of the full-width pass up to K = 48; past it the streamed
# rows do, and at K = 126 the orientations cost the same
# (tools/sweep_narrow_pass.py; PERF.md section 6, PR 32).
TURNED_MAX_K = 48


def _hist_kernel(bins_ref, segstats_ref, out_ref, *, num_features: int,
                 num_bins: int, hist_dtype: str = "f32"):
    """One row-chunk: accumulate every feature's histogram tile."""

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    compute_t = jnp.bfloat16 if hist_dtype == "bf16" else jnp.float32
    segstats = segstats_ref[:].astype(compute_t)      # [CHUNK, K*S]
    chunk = bins_ref.shape[0]
    iota_bt = lax.broadcasted_iota(jnp.int32, (num_bins, chunk), 0)
    for f in range(num_features):                     # static unroll
        codes_t = bins_ref[:, f].reshape(1, chunk)    # [1, CHUNK]
        # one-hot built ALREADY TRANSPOSED [B, CHUNK] so the dot contracts
        # over the minor (lane) axis — no in-kernel relayout (the n-major
        # construction forced a chunk x B transpose per feature, which
        # dominated the kernel's runtime)
        onehot_t = (iota_bt == codes_t).astype(compute_t)
        # [B, CHUNK] @ [CHUNK, K*S] on the MXU, f32 accumulation either way;
        # f32 inputs get HIGHEST (true-f32) passes, bf16 runs at native rate
        tile = lax.dot_general(
            onehot_t, segstats,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=(lax.Precision.DEFAULT if hist_dtype == "bf16"
                       else lax.Precision.HIGHEST))
        out_ref[f, :, :] += tile


def hist_from_segstats_pallas(
    bins: jnp.ndarray,
    segstats: jnp.ndarray,
    num_bins: int,
    chunk: Optional[int] = None,
    interpret: bool | None = None,
    hist_dtype: str = "f32",
    name: str = "lgbtpu_hist_segstats",
) -> jnp.ndarray:
    """Kernel core: bins [n,F] x segstats [n,K] -> f32 [F, num_bins, K].

    The [F, B, K] accumulator stays resident in VMEM across row chunks; the
    chunk size adapts to K so accumulator + tiles fit the ~16 MB budget.
    """
    if hist_dtype == "int8":
        # this kernel has no quantization path (scales live in
        # hist_fused_pallas); before r9 it silently ran full precision,
        # which masked the caller's intent — refuse instead and let
        # compute_histograms_batched route int8 to the XLA segstats path
        raise ValueError(
            "hist_from_segstats_pallas does not implement hist_dtype="
            "'int8'; use hist_fused_pallas (quantized) or the XLA "
            "segstats path (full precision).")
    n, num_features = bins.shape
    k = segstats.shape[1]
    if chunk is None:
        # VMEM budget: out F*B*K*4 + segstats chunk*K*4 + onehot chunk*B*4,
        # with 4x headroom for the HIGHEST-precision matmul decomposition's
        # temporaries (empirically needed to stay under the 16 MB scope).
        out_bytes = num_features * num_bins * k * 4
        budget = 10 * 1024 * 1024 - out_bytes
        per_row = (k + num_bins + num_features) * 4 * 4
        chunk = max(256, min(DEFAULT_CHUNK, budget // max(per_row, 1)))
        chunk = int(chunk) // 256 * 256 or 256
    bins = bins.astype(jnp.int32)

    n_chunks = -(-n // chunk)
    pad = n_chunks * chunk - n
    if pad:
        bins = jnp.pad(bins, ((0, pad), (0, 0)))
        segstats = jnp.pad(segstats, ((0, pad), (0, 0)))

    if interpret is None:
        # the kernel targets TPU; interpret elsewhere (CPU tests)
        interpret = jax.default_backend() == "cpu"

    return pl.pallas_call(
        functools.partial(_hist_kernel, num_features=num_features,
                          num_bins=num_bins, hist_dtype=hist_dtype),
        grid=(n_chunks,),
        in_specs=[
            pl.BlockSpec((chunk, num_features), lambda c: (c, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((chunk, k), lambda c: (c, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((num_features, num_bins, k),
                               lambda c: (0, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((num_features, num_bins, k),
                                       jnp.float32),
        interpret=interpret,
        name=name,
    )(bins, segstats)


def compute_histograms_pallas(
    bins: jnp.ndarray,
    stats: jnp.ndarray,
    seg_id: jnp.ndarray,
    num_segments: int,
    num_bins: int,
    chunk: Optional[int] = DEFAULT_CHUNK,
    interpret: bool | None = None,
    hist_dtype: str = "f32",
) -> jnp.ndarray:
    """Drop-in for ``histogram.compute_histograms`` (f32 [K, F, B, S])."""
    n, num_features = bins.shape
    s = stats.shape[1]
    k = num_segments * s

    seg_onehot = (seg_id[:, None] == lax.iota(jnp.int32, num_segments)[None, :])
    segstats = (seg_onehot.astype(stats.dtype)[:, :, None] * stats[:, None, :])
    segstats = segstats.reshape(n, k)
    out = hist_from_segstats_pallas(bins, segstats, num_bins, chunk=chunk,
                                    interpret=interpret,
                                    hist_dtype=hist_dtype)
    return out.reshape(num_features, num_bins, num_segments, s).transpose(
        2, 0, 1, 3)


# ---------------------------------------------------------------------------
# Fused segment-histogram kernel — the round-3 hot-loop engine.
#
# The wave grower's histogram pass is MXU-FLOP-bound: per wave it pays
# F x 2 x B x (W*S) x n one-hot-matmul FLOPs (~1.8 TFLOP at the Higgs shape
# F=28, B=256, W=42, n=1M).  The r2 XLA path additionally materialized the
# [n, W*S] segment-folded stats in HBM and re-read it once per feature
# (~14 GB/wave), which pushed a wave from the ~9 ms bf16 FLOP floor to
# ~70 ms.  This kernel fuses the whole pass:
#
#   * the [chunk, W*S] segment-folded stats tile is built IN VMEM from the
#     raw [chunk, S] stats + [chunk] seg ids (never touches HBM);
#   * per feature, the [B, chunk] transposed one-hot is built in VMEM and
#     contracted on the MXU into the VMEM-resident [F, B, W*S] accumulator;
#   * HBM traffic per wave is just bins + stats + seg read ONCE:
#     n*(F + 4*S + 4) bytes (~45 MB at the Higgs shape vs 14 GB before).
#
# Precision modes (hist_dtype):
#   "bf16"  one native-rate pass; one-hot is exact in bf16, g/h quantize to
#           8 mantissa bits (relative histogram error ~2e-3; AUC-parity
#           validated by the Higgs bench and tests).
#   "f32"   TWO native-rate passes via a hi/lo bfloat16 split of the stats
#           (split_hi_lo: stats = hi + lo exactly, hi exact in bfloat16, lo
#           rounded to it in the kernel: ~15 mantissa bits; one-hot exact),
#           f32 accumulation — ~3e-5 relative error at half the cost of the
#           6-pass HIGHEST decomposition the XLA path uses.
# ---------------------------------------------------------------------------


def split_hi_lo(x: jnp.ndarray):
    """``(hi, lo)`` with ``x == hi + lo`` exactly, ``hi`` representable in
    bfloat16 and ``|lo| < 2**-7 |x|``: the two operands of the "f32"
    mode's two kernel passes.

    ``hi`` is ``x`` with the low 16 bits of its pattern cleared: an
    integer mask, not ``x.astype(bfloat16).astype(float32)``.  That round
    trip is one the compiler may take out (XLA's excess-precision rule),
    and it did in some passes and not in others: a kernel called by itself
    read the same as bf16 to every digit (``lo`` all zero), and in a round
    the root's and the waves' passes disagreed, which ``parent - child``
    hands to one sibling whole, so "f32" read WORSE than bf16 (PERF.md,
    PR 28).  A masked ``hi`` is exact in bfloat16 whatever rounds it
    afterwards, and no rule removes it."""
    bits = lax.bitcast_convert_type(x, jnp.uint32) & jnp.uint32(0xFFFF0000)
    hi = lax.bitcast_convert_type(bits, jnp.float32)
    return hi, x - hi


def _fused_kernel(bins_ref, stats_ref, seg_ref, out_ref, *, runs: tuple,
                  num_bins: int, num_segments: int, hist_dtype: str,
                  chunk_dim: int = 1, bins_minor: bool = False):
    @pl.when(pl.program_id(chunk_dim) == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    chunk = bins_ref.shape[1]                              # bins [F, chunk]
    s = stats_ref.shape[0]
    w = num_segments
    # ALL row-axis operands arrive TRANSPOSED ([S, chunk] stats,
    # [1, chunk] seg): rows must be the 128-lane MINOR dim, because XLA
    # stages pallas operands into (8, 128)-tiled HBM layouts and a
    # row-major [n, 1]/[n, 3] operand pads its 1-3 lanes to 128 — a
    # 42-128x HBM blowup that OOM'd the 11M-row north star (r4: 15.75 GB
    # chip, 18.4 GB demanded, ~16 GB of it this padding).
    stats = stats_ref[:]                                   # [S, chunk] f32
    seg = seg_ref[:]                                       # [1, chunk] i32
    # 2-D-only fold (Mosaic cannot collapse a non-lane-aligned minor dim,
    # and lane-tiling ops like jnp.tile pad each S-lane segment to a full
    # 128-lane tile — measured 19-43 MB of scoped VMEM): row k of the
    # folded tile is stats[k % S, :] masked to seg == k // S, built as a
    # tiny [W*S, S] selection matmul + a 2-D mask.
    iota_r = lax.broadcasted_iota(jnp.int32, (w * s, chunk), 0)
    seg_match = seg == iota_r // s                          # [W*S, chunk]
    proj_t = (lax.broadcasted_iota(jnp.int32, (w * s, s), 0) % s
              == lax.broadcasted_iota(jnp.int32, (w * s, s), 1))

    def fold(st, out_t):
        spread = lax.dot_general(
            proj_t.astype(jnp.float32), st.astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [W*S, chunk]
        return jnp.where(seg_match, spread, 0.0).astype(out_t)

    # ONE folded operand and ONE dot per feature — the kernel is the same
    # program for every mode ("f32" is realized as two whole-kernel passes
    # over a hi/lo split of the stats, summed by the caller: a two-dot
    # kernel body variant crashed the TPU runtime intermittently)
    if hist_dtype == "int8":
        # stats arrive PRE-QUANTIZED to integers in [-127, 127] (stored as
        # f32, exactly representable) — the dot runs at the MXU's
        # double-rate int8 path with EXACT int32 accumulation
        operand = fold(stats, jnp.int8)
        oh_t, acc_t = jnp.int8, jnp.int32
    else:
        operand = fold(stats, jnp.bfloat16)
        oh_t, acc_t = jnp.bfloat16, jnp.float32

    # features iterate via fori_loop (NOT a static unroll: compile time must
    # stay flat in F — MSLR has 136 features); bins arrive TRANSPOSED
    # [F_blk, chunk] so the dynamic per-feature slice is on the major dim
    # ``bins_minor``: the same NT dot with its operands exchanged, so the
    # accumulator is [F_blk, K, B] and the K statistics lie on sublanes (K
    # <= 8: one tile) with the bins on the lanes.  A narrow pass (the root's
    # K = 3) otherwise leaves the chip an [F, B, 3] array that HBM stores
    # 128 lanes wide: 264 MB at 2,000 features.
    _feature_loop(_onehot_dots(bins_ref, out_ref, operand, oh_t, acc_t,
                               bins_minor, num_bins),
                  runs, chunk_dim - 1)


def _onehot_dots(bins_ref, out_ref, operand, oh_t, acc_t, bins_minor: bool,
                 num_bins: int):
    """``body_at(height)`` of :func:`_feature_loop` for the fused kernels:
    row ``f``'s one-hot ``[height, chunk]`` (built transposed, so the dot
    contracts over the chunk, the minor axis of both operands) against the
    folded statistics ``operand`` ``[K, chunk]``, added into the bins
    below ``height`` of the accumulator row ``f``."""
    chunk = bins_ref.shape[1]

    def body_at(height):
        iota_bt = lax.broadcasted_iota(jnp.int32, (height, chunk), 0)
        bins = slice(None) if height == num_bins else slice(0, height)
        at = ((slice(None), bins) if bins_minor else (bins, slice(None)))

        def body(f, _):
            codes_t = bins_ref[pl.dslice(f, 1), :]             # [1, chunk]
            onehot_t = (iota_bt == codes_t).astype(oh_t)
            lhs, rhs = ((operand, onehot_t) if bins_minor
                        else (onehot_t, operand))
            tile = lax.dot_general(
                lhs, rhs,
                dimension_numbers=(((1,), (1,)), ((), ())),     # NT: both on
                preferred_element_type=acc_t)                   # the chunk dim
            out_ref[(pl.dslice(f, 1),) + at] += tile[None]
            return _

        return body

    return body_at


# A one-hot's rows are made 16 at a time: a bfloat16 vreg packs 16 sublanes.
ONEHOT_ROW_ALIGN = 16


def onehot_heights(col_bins, num_bins: int) -> Optional[tuple]:
    """Per column of the table, the height of its one-hot in the fused
    kernels: the bins the column uses (its codes lie below them) rounded
    up to ``ONEHOT_ROW_ALIGN``, at most ``num_bins``.  ``None`` where every
    column is ``num_bins`` tall: the kernels' one height, and program."""
    heights = tuple(
        min(num_bins, -(-max(int(b), 1) // ONEHOT_ROW_ALIGN)
            * ONEHOT_ROW_ALIGN) for b in col_bins)
    return None if all(h == num_bins for h in heights) else heights


def onehot_order(heights) -> np.ndarray:
    """``i32[C]``: the column each feature row of the fused kernels holds,
    the columns by their one-hot's height, shortest first (equal heights
    in the table's order).  Row ``r``'s height is ``sorted(heights)[r]``,
    whatever the order of the table's columns: the kernels' program reads
    the sorted heights alone (:func:`feature_layout`) and this order comes
    as an operand (:func:`prepare_wave_operands`)."""
    return np.argsort(np.asarray(heights), kind="stable").astype(np.int32)


class FeatureLayout(NamedTuple):
    """The loops of the fused kernels' feature blocks.

    ``runs[b]`` is block ``b``'s loops, ``((rows, height), ...)`` over its
    rows in turn: each row's one-hot is ``height`` bins tall, and the
    block's padding rows are in no run (:func:`_feature_loop`)."""

    f_blk: int
    runs: tuple

    @property
    def rows_looped(self) -> int:
        """The feature rows a pass's loops run over: the columns."""
        return sum(rows for block in self.runs for rows, _ in block)

    @property
    def onehot_rows(self) -> int:
        """Σ over the looped rows of their one-hot's height."""
        return sum(rows * h for block in self.runs for rows, h in block)


def feature_layout(num_features: int, f_blk: int, num_bins: int,
                   row_heights: Optional[tuple] = None) -> FeatureLayout:
    """The :class:`FeatureLayout` of ``num_features`` feature rows in
    blocks of ``f_blk`` (``_vmem_blocking``), row ``r``'s one-hot
    ``row_heights[r]`` tall, ascending (``sorted`` of
    :func:`onehot_heights`, the rows holding the columns in
    :func:`onehot_order`); ``None``, or the heights of another table (a
    screened round's compacted columns), makes every row ``num_bins``
    tall.  A block's rows of one height are one loop; blocks fill as
    before, the last padded, so a table of one height loops once a
    block."""
    if row_heights is None or len(row_heights) != num_features:
        row_heights = (num_bins,) * num_features
    runs = []
    for start in range(0, num_features, f_blk):
        block = []
        for h in row_heights[start:start + f_blk]:
            if block and block[-1][1] == h:
                block[-1][0] += 1
            else:
                block.append([1, h])
        runs.append(tuple((rows, h) for rows, h in block))
    return FeatureLayout(f_blk, tuple(runs))


def _in_blocks(fb, first: int, last: int):
    """``pl.when``'s predicate: grid block ``fb`` lies in ``[first, last]``."""
    if first == last:
        return fb == first
    return fb < last + 1 if first == 0 else (fb >= first) & (fb <= last)


def _feature_loop(body_at, runs, fblock_axis):
    """The per-feature loops of a fused kernel over its block's rows that
    hold a column: ``body_at(height)`` makes the ``lax.fori_loop`` body of
    the rows whose one-hot is ``height`` bins tall, ``runs`` is
    :attr:`FeatureLayout.runs`, the block ``pl.program_id(fblock_axis)``.

    A block loops over its runs in turn, each at its own height: rows of a
    shorter column compare their codes with fewer bins and their dot
    writes only the bins below the height, where the one-hot of the table's
    ``num_bins`` would add zeros (the accumulator's other bins keep the
    zeros of ``_init``).  Rows that pad the last block (``_vmem_blocking``:
    MSLR's 136 features in 5 blocks of 32 = 160 rows) are in no run: their
    accumulator rows keep the zeros and the wrapper drops them.  Every
    trip count and height is static; the kernel branches once per distinct
    list of runs, not once per block, and a table of one height makes its
    body once, outside the branches: with no padding, one loop a block,
    the program of a kernel with no layout."""
    heights = {h for block in runs for _, h in block}
    one_body = (body_at(heights.pop()) if len(heights) == 1 else None)

    def loops(block):
        start = 0
        for rows, h in block:
            lax.fori_loop(start, start + rows,
                          one_body or body_at(h), 0)
            start += rows

    # the rows run by height, so the blocks of one list of runs are
    # contiguous: one branch each, over their range of the grid
    blocks = {}
    for b, block in enumerate(runs):
        blocks.setdefault(block, []).append(b)
    if len(blocks) == 1:
        loops(runs[0])
        return
    fb = pl.program_id(fblock_axis)
    for block, which in blocks.items():
        assert which == list(range(which[0], which[-1] + 1)), which
        pl.when(_in_blocks(fb, which[0], which[-1]))(
            functools.partial(loops, block))


def _by_column(out, row_of, num_features: int):
    """A kernel's accumulator ``[F_rows, ...]``, one row a feature row, as
    ``[num_features, ...]`` in the table's column order: the padding
    dropped and, where ``row_of`` (``i32[C]``, the row of each column:
    the inverse of :func:`onehot_order`) is given, one gather of whole
    rows."""
    if row_of is None:
        return out[:num_features]
    return jnp.take(out, row_of, axis=0)


def _vmem_blocking(num_features: int, num_bins: int, k: int,
                   chunk_align: int = 512):
    """Shared VMEM sizing for the fused kernels: (f_blk, n_fblk, f_pad,
    chunk).

    The [F_blk, B, K] f32 accumulator stays VMEM-resident; when the full
    feature axis does not fit (MSLR's 136 features x 128 lanes ~= 18 MB),
    features split into grid-major blocks — stats/seg tiles are re-read
    once per block, a negligible cost next to the matmul.  All budgets
    use the LANE-PADDED k: VMEM tiles are (8, 128), so a k=3 root pass
    occupies 128 lanes per bin — at Criteo's 413 raw features that is a
    54 MB accumulator if sized from the nominal k (the r3 criteo
    efb_off OOM).

    Everything is sized at ``num_bins``, the table's tallest column: a
    block's rows whose one-hot is shorter (:func:`feature_layout`) build
    smaller tiles and write fewer bins of the same accumulator, so the
    blocking of a table of mixed heights is that of one height.
    """
    k_pad = -(-k // 128) * 128
    f_blk = num_features
    while f_blk > 1 and f_blk * num_bins * k_pad * 4 > 6 * 1024 * 1024:
        f_blk = -(-f_blk // 2)
    if f_blk != num_features:
        # blocked second-to-last dims must be multiples of 8 (Mosaic
        # tiling); round DOWN so the VMEM budget the loop just enforced
        # cannot be re-violated (rounding up re-grew a 34-feature block
        # to 40 and overflowed the 16 MB scope at the MSLR shape)
        f_blk = max(8, f_blk // 8 * 8)
    n_fblk = -(-num_features // f_blk)
    f_pad = n_fblk * f_blk - num_features
    # per-chunk tiles (one-hot B*chunk*2, folded stats chunk*K*2 + f32
    # spread temporary chunk*K*4, bins chunk*F_blk*4 staged, masks) with
    # input double-buffering.  The r3 estimate (4B + 20k + 8f + 64) was
    # ~2x too fat: it drove the MSLR-shape chunk to 1536 and the pass to
    # 61-64% of the bf16 FLOP model, where a measured chunk sweep peaks
    # at ~4096 (75%; flat beyond).  The trimmed estimate plus the raised
    # 4096 cap lands within ~3% of the measured optimum at the Higgs,
    # MSLR, and Criteo-root shapes (chunk-sweep table in PERF_HISTORY.md, "r4
    # session 2 kernel chunk sweep"); still conservative enough that no
    # shape re-approaches the 16 MB scope.
    out_bytes = f_blk * num_bins * k_pad * 4
    budget = 11 * 1024 * 1024 - out_bytes
    per_row = 2 * num_bins + 10 * k + 8 * f_blk + 128
    chunk = max(chunk_align, min(4096, budget // max(per_row, 1)))
    chunk = int(chunk) // chunk_align * chunk_align or chunk_align
    return f_blk, n_fblk, f_pad, chunk


def hist_fused_pallas(
    bins: jnp.ndarray,
    stats: jnp.ndarray,
    seg_id: jnp.ndarray,
    num_segments: int,
    num_bins: int,
    chunk: Optional[int] = None,
    interpret: bool | None = None,
    hist_dtype: str = "f32",
    name: str = "lgbtpu_hist_fused",
) -> jnp.ndarray:
    """Fused drop-in for ``histogram.compute_histograms``:
    bins u8/i32 [n, F] x stats f32 [n, S] x seg_id i32 [n]
    -> f32 [num_segments, F, num_bins, S].  ``name`` is the kernel's name
    in the compiled program and the device trace (letters, digits and
    ``_``): a grower passes the ROLE the pass plays for it."""
    n, num_features = bins.shape
    s = stats.shape[1]
    k = num_segments * s
    if hist_dtype == "f32x":     # explicit-f32 token (resolve_hist_dtype);
        hist_dtype = "f32"       # forced-pallas callers get the hi/lo split
    if hist_dtype == "bf16sr":   # opt-in SR variant (histogram.sr_round_bf16
        from .histogram import sr_round_bf16   # — measured ~3e-4 WORSE than
        hist_dtype = "bf16"                    # round-to-nearest on Higgs;
        stats = sr_round_bf16(stats)           # kept for other workloads)
    if hist_dtype == "int8" and n > INT8_ACC_ROW_LIMIT:
        # int32 accumulation wraps once 2^31/127 = 16,909,320 rows land in
        # one (segment, bin) cell — beyond that, corrupt histograms would
        # be silent (ADVICE r3).  n rows total bounds any single cell's
        # count, so n <= limit is a proof of no overflow; past it we
        # refuse rather than wrap.  Shard rows (dp mesh) or use bf16.
        raise ValueError(
            f"hist_dtype='int8' is limited to {INT8_ACC_ROW_LIMIT:,} rows "
            f"per device shard (got n={n:,}): quantized values reach "
            f"|q|=127 and the int32 bin accumulator wraps past 2^31/127. "
            f"Use hist_dtype='bf16' or shard rows across more devices.")
    f_blk, n_fblk, f_pad, auto_chunk = _vmem_blocking(
        num_features, num_bins, k, chunk_align=512)
    if chunk is None:
        chunk = auto_chunk
        if hist_dtype == "int8":
            # Mosaic widens the int8 one-hot/relayout intermediates ~3x
            # beyond the f32 per_row model (~43 MB scoped VMEM at
            # chunk=2048 vs the ~16 MB scope, measured r3) — the retuned
            # estimate above models only the bf16/f32 paths, so auto
            # chunks above 512 fail to compile at production widths
            # (ADVICE r4).  Explicit ``chunk=`` still overrides.
            chunk = min(chunk, 512)
    # transposed [F, n] i32 layout: the kernel's per-feature dynamic slice
    # must be on the MAJOR dim.  This is loop-invariant across the grower's
    # waves, so XLA hoists the transpose out of the growth while_loop.
    bins_t = bins.astype(jnp.int32).T
    seg_id = seg_id.astype(jnp.int32)
    # out-of-range segments contribute nothing: send them to a bin that the
    # one-hot comparison can never match
    seg_id = jnp.where((seg_id >= 0) & (seg_id < num_segments), seg_id, -1)

    n_chunks = -(-n // chunk)
    pad = n_chunks * chunk - n
    if pad or f_pad:
        bins_t = jnp.pad(bins_t, ((0, f_pad), (0, pad)))
        stats = jnp.pad(stats, ((0, pad), (0, 0)))
        seg_id = jnp.pad(seg_id, ((0, pad),), constant_values=-1)

    scales = None
    if hist_dtype == "int8":
        # per-channel symmetric quantization to [-127, 127] with
        # deterministic per-row stochastic rounding (the TPU analogue of
        # LightGBM's ``use_quantized_grad`` gradient discretization):
        # unbiased E[q] = x/scale, exact int32 accumulation on the MXU at
        # double the bf16 rate
        scales = jnp.maximum(jnp.max(jnp.abs(stats), axis=0),
                             1e-30) / 127.0                 # [S]
        idx = lax.iota(jnp.uint32, stats.shape[0])
        r = (((idx * jnp.uint32(2654435761) + jnp.uint32(974711))
              >> jnp.uint32(9)).astype(jnp.float32)
             / jnp.float32(1 << 23))                        # U[0,1) per row
        # clip: the channel-max row has x/scale ~= 127 + ulp noise, and
        # with r -> 1 the floor can land on +128 — out of int8 range.
        # int32 accumulation overflow bound: a (segment, bin) cell wraps
        # past 2^31 / 127 ~= 16.9M rows; fine for the 11M north star, a
        # documented cliff beyond.
        stats = jnp.clip(jnp.floor(stats / scales[None, :] + r[:, None]),
                         -127.0, 127.0)

    # row axis on the 128-lane MINOR dim (see _fused_kernel layout note)
    out = hist_fused_prepared(
        bins_t, stats.T, seg_id.reshape(1, -1), num_segments, num_bins,
        chunk, f_blk, num_features, interpret=interpret,
        hist_dtype=hist_dtype, name=name)
    if scales is not None:
        out = out * scales[None, None, None, :]
    return out


def hist_fused_prepared(
    bins_t: jnp.ndarray,         # [n_fblk * f_blk, n_pad] i32 codes
    stats_t: jnp.ndarray,        # [S, n_pad] f32
    seg_row: jnp.ndarray,        # [1, n_pad] i32, -1 = in no segment
    num_segments: int,
    num_bins: int,
    chunk: int,
    f_blk: int,
    num_features: int,
    interpret: bool | None = None,
    hist_dtype: str = "bf16",
    name: str = "lgbtpu_hist_fused",
    layout: Optional[FeatureLayout] = None,
    row_of: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """:func:`hist_fused_pallas` on operands that are already transposed,
    widened and padded to whole blocks (its own preparation, or
    :func:`prepare_wave_operands`: the frontier grower's root pass reads
    the table its wave passes read, so a tree keeps ONE 4-byte transposed
    copy of the codes, 3.2 GB at 400,000 x 2,000, and not one per
    padding).  ``hist_dtype``: "bf16", "f32" (hi/lo) or "int8" (statistics
    quantized by the caller, who also applies the scales).  ``layout``:
    the rows' :class:`FeatureLayout` (``None``: every one-hot ``num_bins``
    tall); ``row_of``: the row of each column where the rows hold the
    columns in :func:`onehot_order` (``None``: row ``c`` holds column
    ``c``).  Returns f32 ``[num_segments, F, num_bins, S]``, the columns
    in the table's order."""
    f_rows, n_pad = bins_t.shape
    s = stats_t.shape[0]
    k = num_segments * s
    n_fblk, n_chunks = f_rows // f_blk, n_pad // chunk
    assert (n_fblk * f_blk, n_chunks * chunk) == (f_rows, n_pad)
    if layout is None:
        layout = feature_layout(num_features, f_blk, num_bins)
    assert layout.f_blk == f_blk, (layout.f_blk, f_blk)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    # a pass of at most 8 statistics columns (the root: 3) writes its
    # accumulator bins-minor (see _fused_kernel); int8 keeps [F, B, K]
    bins_minor = k <= 8 and hist_dtype != "int8"
    acc_dims = (k, num_bins) if bins_minor else (num_bins, k)

    def one_pass(stats_arr, mode):
        return pl.pallas_call(
            functools.partial(_fused_kernel, runs=layout.runs,
                              num_bins=num_bins, num_segments=num_segments,
                              hist_dtype=mode, bins_minor=bins_minor),
            grid=(n_fblk, n_chunks),
            in_specs=[
                pl.BlockSpec((f_blk, chunk), lambda fb, c: (fb, c),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((s, chunk), lambda fb, c: (0, c),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, chunk), lambda fb, c: (0, c),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((f_blk,) + acc_dims,
                                   lambda fb, c: (fb, 0, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct(
                (n_fblk * f_blk,) + acc_dims,
                jnp.int32 if mode == "int8" else jnp.float32),
            interpret=interpret,
            name=name,
        )(bins_t, stats_arr, seg_row)

    if hist_dtype == "f32":
        # the hi/lo split (split_hi_lo) realized as TWO whole-kernel
        # passes over the identical single-dot program (a two-dot kernel
        # body crashed the TPU runtime intermittently)
        hi, lo = split_hi_lo(stats_t)
        out = one_pass(hi, "bf16") + one_pass(lo, "bf16")
    else:
        out = one_pass(stats_t, hist_dtype)
    out = _by_column(out, row_of, num_features).astype(jnp.float32)
    if bins_minor:
        out = out.reshape(num_features, num_segments, s, num_bins)
        return out.transpose(1, 0, 3, 2)
    out = out.reshape(num_features, num_bins, num_segments, s)
    return out.transpose(2, 0, 1, 3)


# ---------------------------------------------------------------------------
# Split-iteration mega-kernel — the r7 kernel-count attack.
#
# PERF_HISTORY.md r4/r5: at fused-cv scale the strict grower's per-split
# iteration lowered to ~49 XLA fusions + 1 custom-call, and with ~1,500
# launches per round at ~9 us each the sweep's floor is DISPATCH, not
# FLOPs.  Everything between the histogram pass and the next iteration's
# partition is pure VPU work over VMEM-sized operands ([2, F, 3, B]
# histograms + the packed
# [capacity, _PK.NC] node table), so the whole tail of the iteration fuses
# into ONE pallas call:
#
#   * cumsum gain scan over both children (shared numeric helper
#     ``ops.split.split_gain_scan`` — in interpret mode bitwise identical
#     to find_best_split's XLA scan by construction; see the kernel's
#     docstring for the chip);
#   * regularized-gain argmax (first-occurrence, matching jnp.argmax's
#     row-major tie-break) + winner gather, per child;
#   * the one-row-gather / three-row-scatter node-table update;
#   * the NEXT iteration's best-leaf pick over the just-updated table,
#     emitted as a tiny aux row [leaf', feat', thr', active'] so the XLA
#     side of the loop shrinks to: partition gathers, seg select, the
#     histogram kernel, and this call.
#
# The E-config batch axis of the fused-cv sweep maps onto the kernel grid
# via jax.vmap of the pallas_call (leading grid dimension), exactly like
# the batched histogram kernel.
#
# Histogram layout: [2, F, 3, B] with BINS on the 128-lane minor dim — the
# natural [2, F, B, 3] would pad its 3 stat lanes to 128 (a ~42x VMEM
# blowup, same failure mode as the r4 transposed-stats note above); the
# 3-channel axis pads 3 -> 8 sublanes instead (2*F*8*B*4 ~= 2.2 MB at the
# MSLR F=136 / B=256 shape).
# ---------------------------------------------------------------------------


def _prefix_sum_lanes(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum along the minor (lane) axis, as log2(width)
    shifted adds — Mosaic lowers no ``cumsum``.  The width must be a
    128 multiple (``split_iter_pallas`` pads the bin axis)."""
    axis = x.ndim - 1
    lane = lax.broadcasted_iota(jnp.int32, x.shape, axis)
    shift = 1
    while shift < x.shape[axis]:
        x = x + jnp.where(lane >= shift, pltpu.roll(x, shift, axis), 0.0)
        shift *= 2
    return x


def _split_iter_kernel(hist_ref, tab_ref, fmask_ref, aux_ref, scal_ref,
                       out_tab_ref, out_aux_ref, *, K, num_features: int,
                       num_bins: int, capacity: int, interpret: bool):
    """One whole strict split iteration in VMEM (see block comment above).

    Operands:
      hist_ref  f32 [2, F, 3, B]   both children's histograms, bins minor;
      tab_ref   f32 [capacity, NC] packed node table (models.tree._PK);
      fmask_ref f32 [1, F]         tree-level feature mask (bynode off
                                   under the eligibility gate);
      aux_ref   f32 [1, 8]         [leaf, feat, thr, active, 0...] — the
                                   pick this iteration acts on;
      scal_ref  f32 [1, 16]        [l1, l2, min_data, min_hess, min_gain,
                                   max_delta_step, path_smooth, max_depth,
                                   n_nodes, 0...] (all exact in f32).
    Outputs: updated table + the next iteration's aux row.

    Compiled for the chip, ``hist_ref``'s bin axis arrives zero-padded to
    a 128-lane multiple (``num_bins`` stays the real count): the prefix
    sums run as shifted adds and the padded lanes are masked out of the
    argmax.  Interpret mode keeps ``jnp.cumsum`` on the unpadded axis,
    the op ``find_best_split`` uses, which is what makes the two paths
    agree bitwise on the CPU.
    """
    from .split import SplitContext, split_gain_scan, split_stats_valid

    cumsum = (functools.partial(jnp.cumsum, axis=-1) if interpret
              else _prefix_sum_lanes)
    lanes = hist_ref.shape[-1]                         # >= num_bins

    neg_inf = jnp.float32(-jnp.inf)
    sc = scal_ref[0, :]
    ctx = SplitContext(
        lambda_l1=sc[0], lambda_l2=sc[1], min_data_in_leaf=sc[2],
        min_sum_hessian=sc[3], min_gain_to_split=sc[4],
        max_delta_step=sc[5], path_smooth=sc[6])
    max_depth = sc[7]
    n_nodes = sc[8].astype(jnp.int32)

    aux = aux_ref[0, :]
    leaf = aux[0].astype(jnp.int32)
    active = aux[3] > 0.0

    row2 = tab_ref[pl.dslice(leaf, 1), :]             # [1, NC] — ONE gather
    row = row2[0, :]
    feat_p, thr_p = row[K.CAND_FEAT], row[K.CAND_BIN]
    gain_p = row[K.CAND_GAIN]
    wl_v, wr_v = row[K.CAND_WL], row[K.CAND_WR]
    lo, hi = row[K.BOUND_LO], row[K.BOUND_HI]
    child_depth = row[K.DEPTH] + 1.0
    # mono is None under the gate, so both children inherit (lo, hi) as-is
    depth_ok = (max_depth <= 0.0) | (child_depth < max_depth)
    fmask = fmask_ref[0:1, :]                          # [1, F]

    big = jnp.int32(num_features * num_bins)

    def score(c, p_out):
        """find_best_split's numeric path for one child (shared helper)."""
        lg = cumsum(hist_ref[c, :, 0, :])                    # [F, B]
        lh = cumsum(hist_ref[c, :, 1, :])
        lc = cumsum(hist_ref[c, :, 2, :])
        tg, th, tc = lg[:, -1:], lh[:, -1:], lc[:, -1:]      # [F, 1]
        rg, rh, rc = tg - lg, th - lh, tc - lc
        gain, wl, wr = split_gain_scan(lg, lh, lc, rg, rh, rc, tg, th,
                                       ctx, lo, hi, p_out)
        valid = (split_stats_valid(lc, rc, lh, rh, gain, ctx)
                 & (fmask.reshape(num_features, 1) > 0) & depth_ok)
        if lanes > num_bins:                               # padded bins
            valid &= lax.broadcasted_iota(jnp.int32, gain.shape,
                                          1) < num_bins
        gain = jnp.where(valid, gain, neg_inf)
        best = jnp.max(gain)
        # first-occurrence flat argmax: min flat index among the maxima
        # (ties and the all--inf case resolve exactly like jnp.argmax's
        # row-major scan in the XLA path)
        flat = (lax.broadcasted_iota(jnp.int32, gain.shape, 0) * num_bins
                + lax.broadcasted_iota(jnp.int32, gain.shape, 1))
        idx = jnp.min(jnp.where(gain == best, flat, big))
        hit = flat == idx

        def pick(x):
            return jnp.sum(jnp.where(hit, x, 0.0))

        return (best, (idx // num_bins).astype(jnp.float32),
                (idx % num_bins).astype(jnp.float32),
                pick(lg), pick(lh), pick(lc), pick(rg), pick(rh), pick(rc),
                pick(wl), pick(wr))

    bl = score(0, wl_v)
    br = score(1, wr_v)

    nc = K.NC
    iota_nc = lax.broadcasted_iota(jnp.int32, (1, nc), 1)

    def make_row(pairs, base=None):
        out = jnp.zeros((1, nc), jnp.float32) if base is None else base
        for col, val in pairs:
            out = jnp.where(iota_nc == col, val, out)
        return out

    nl_f = n_nodes.astype(jnp.float32)
    nr_f = nl_f + 1.0
    leaf_row = make_row([
        (K.SPLIT_FEAT, feat_p), (K.SPLIT_BIN, thr_p), (K.LEFT, nl_f),
        (K.RIGHT, nr_f), (K.IS_LEAF, 0.0), (K.SPLIT_GAIN, gain_p)],
        base=row2)
    pm = row[K.PM]

    def child_row(b, leaf_val, count):
        (bg, bf, bb, blg, blh, blc, brg, brh, brc, bwl, bwr) = b
        return make_row([
            (K.SPLIT_FEAT, -1.0), (K.LEFT, -1.0), (K.RIGHT, -1.0),
            (K.LEAF_VALUE, leaf_val), (K.IS_LEAF, 1.0), (K.COUNT, count),
            (K.DEPTH, child_depth), (K.CAND_GAIN, bg), (K.CAND_FEAT, bf),
            (K.CAND_BIN, bb), (K.CAND_LG, blg), (K.CAND_LH, blh),
            (K.CAND_LC, blc), (K.CAND_RG, brg), (K.CAND_RH, brh),
            (K.CAND_RC, brc), (K.CAND_WL, bwl), (K.CAND_WR, bwr),
            (K.BOUND_LO, lo), (K.BOUND_HI, hi),
            (K.PM, jnp.minimum(pm, bg))])

    lrow = child_row(bl, wl_v, row[K.CAND_LC])
    rrow = child_row(br, wr_v, row[K.CAND_RC])

    out_tab_ref[:] = tab_ref[:]

    @pl.when(active)
    def _commit():
        out_tab_ref[pl.dslice(leaf, 1), :] = leaf_row
        out_tab_ref[pl.dslice(n_nodes, 1), :] = lrow
        out_tab_ref[pl.dslice(n_nodes + 1, 1), :] = rrow

    # next iteration's best-first pick over the UPDATED table — what the
    # XLA body recomputed at the top of every trip
    newtab = out_tab_ref[:]
    g2 = jnp.where(newtab[:, K.IS_LEAF] > 0.5, newtab[:, K.CAND_GAIN],
                   neg_inf).reshape(1, capacity)
    iota_cap = lax.broadcasted_iota(jnp.int32, (1, capacity), 1)
    best_g = jnp.max(g2)
    leaf_n = jnp.min(jnp.where(g2 == best_g, iota_cap, capacity))
    sel_l = iota_cap == leaf_n
    feat_n = jnp.sum(jnp.where(sel_l, newtab[:, K.CAND_FEAT]
                               .reshape(1, capacity), 0.0))
    thr_n = jnp.sum(jnp.where(sel_l, newtab[:, K.CAND_BIN]
                              .reshape(1, capacity), 0.0))
    active_n = active & jnp.isfinite(best_g)
    iota8 = lax.broadcasted_iota(jnp.int32, (1, 8), 1)
    out_aux_ref[:] = jnp.where(
        iota8 == 0, leaf_n.astype(jnp.float32),
        jnp.where(iota8 == 1, feat_n,
                  jnp.where(iota8 == 2, thr_n,
                            jnp.where(iota8 == 3,
                                      active_n.astype(jnp.float32), 0.0))))


def split_iter_pallas(hist2_t: jnp.ndarray, table: jnp.ndarray,
                      fmask: jnp.ndarray, aux: jnp.ndarray,
                      scal: jnp.ndarray, *, pk,
                      interpret: bool | None = None,
                      name: str = "lgbtpu_split_iter"):
    """One strict split iteration in one pallas call (_split_iter_kernel).

    Args:
      hist2_t: f32 ``[2, F, 3, B]`` both children's histograms (bins
        minor — transpose of the ``[2, F, B, 3]`` hist_fn output).
      table: f32 ``[capacity, NC]`` packed node table.
      fmask: f32 ``[1, F]`` tree-level feature mask.
      aux: f32 ``[1, 8]`` current pick ``[leaf, feat, thr, active, 0...]``.
      scal: f32 ``[1, 16]`` traced scalars (see kernel docstring).
      pk: the static column-layout class (``models.tree._PK``).

    Returns (table', aux').  vmap maps batch axes onto leading grid dims.
    """
    capacity, nc = table.shape
    _, num_features, _, num_bins = hist2_t.shape
    if max(capacity, num_bins) > 1 << 24:
        # the packed table and the aux pick carry node ids / feature ids /
        # bin thresholds as f32 lanes — exact only below 2^24 (checked
        # rather than silently rounding the tree structure)
        raise ValueError(
            f"split_iter_pallas packs indices into f32 lanes; capacity="
            f"{capacity} / num_bins={num_bins} exceeds the f32-exact "
            f"integer range (2^24)")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if not interpret:
        hist2_t = jnp.pad(hist2_t, ((0, 0),) * 3
                          + ((0, -num_bins % 128),))
    return pl.pallas_call(
        functools.partial(_split_iter_kernel, K=pk,
                          num_features=num_features, num_bins=num_bins,
                          capacity=capacity, interpret=interpret),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 5,
        out_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 2,
        out_shape=[
            jax.ShapeDtypeStruct((capacity, nc), jnp.float32),
            jax.ShapeDtypeStruct((1, 8), jnp.float32),
        ],
        interpret=interpret,
        name=name,
    )(hist2_t, table, fmask, aux, scal)


def _accumulate_wave(bins_ref, stats_ref, seg, out_ref, *, runs: tuple,
                     num_bins: int, num_segments: int, bins_minor: bool,
                     fblock_axis=None):
    """Phase 2 of the partition-fused kernels: the segment-folded one-hot
    dots of :func:`_fused_kernel` over this block's rows that hold a
    column, each row's one-hot as tall as its column's bins
    (:func:`_feature_loop` over ``runs``, the block at grid axis
    ``fblock_axis``), with ``seg`` ``[1, chunk]`` produced in-register by
    the routing phase.

    ``bins_minor`` turns the dot as ``_fused_kernel`` does for the root:
    ``operand [K, chunk] x onehot [H, chunk]^T`` into an ``[F_blk, K, B]``
    accumulator.  Unturned, the MXU streams the one-hot's ``H`` rows (255
    in a column of the table's bins) through every 128-column weight tile
    whatever ``K <= 128`` is, 3 useful columns or 126; turned it streams
    ``K`` rows, and a narrow pass (the doubling passes of a tree) stops
    paying for the full width: the table at ``TURNED_MAX_K``."""
    chunk = bins_ref.shape[1]
    s = stats_ref.shape[0]
    w = num_segments
    stats = stats_ref[:]
    iota_r = lax.broadcasted_iota(jnp.int32, (w * s, chunk), 0)
    seg_match = seg == iota_r // s
    proj_t = (lax.broadcasted_iota(jnp.int32, (w * s, s), 0) % s
              == lax.broadcasted_iota(jnp.int32, (w * s, s), 1))
    spread = lax.dot_general(
        proj_t.astype(jnp.float32), stats.astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    operand = jnp.where(seg_match, spread, 0.0).astype(jnp.bfloat16)
    _feature_loop(_onehot_dots(bins_ref, out_ref, operand, jnp.bfloat16,
                               jnp.float32, bins_minor, num_bins),
                  runs, fblock_axis)


def _route(v, pv_ref, thr, rank2, cset_ref=None):
    """Whether each row's code ``v`` goes left: inside ``[lo, thr]``
    (``pv`` row 5), flipped where ``inv`` (row 6) — ``ops.members.go_left``
    in the kernels' lanes; with ``cset_ref``, where ``pv`` row 7 marks a
    category set's split, whether ``v`` is in the set
    (:func:`_in_category_set`)."""
    inside = (v >= pv_ref[5, :]) & (v <= thr)
    go_left = inside != (pv_ref[6, :] > 0.0)
    if cset_ref is None:
        return go_left
    # logic, not a select of booleans (which Mosaic does not lower)
    cat = pv_ref[7, :] > 0.0
    return (cat & _in_category_set(v, rank2, cset_ref)) | (~cat & go_left)


def _in_category_set(v, rank2, cset_ref):
    """Whether each row's code ``v`` is in the category set of its leaf's
    split: ``cset_ref [B, W_pad]`` holds the wave's sets as 0/1 columns, one
    a wave rank; a one-hot dot of the rows' ranks (``rank2`` = 2 x rank)
    lays each row's set down its lane (exact: 0/1 products, one term), and
    the bit at the row's code is read off along the sublanes."""
    num_bins, w_pad = cset_ref.shape
    chunk = v.shape[0]
    oh_rank = jnp.where(
        (2 * lax.broadcasted_iota(jnp.int32, (w_pad, chunk), 0)
         ).astype(jnp.float32) == rank2.reshape(1, chunk), 1.0, 0.0)
    sets = lax.dot_general(
        cset_ref[:], oh_rank,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                   # [B, chunk]
    hit = (lax.broadcasted_iota(jnp.int32, (num_bins, chunk), 0)
           .astype(jnp.float32) == v.reshape(1, chunk))
    bit = jnp.sum(jnp.where(hit, sets, 0.0), axis=0, keepdims=True)
    return bit[0, :] > 0.5


def _fused_part_kernel(bins_ref, stats_ref, pv_ref, *refs, runs: tuple,
                       num_bins: int, num_segments: int,
                       bins_minor: bool = False, cat_sets: bool = False):
    """Wave histogram + ROW PARTITION in one kernel (single f-block).

    Accumulation is ALWAYS bf16-dot into f32 here; f32-exact callers get
    it via the caller-side hi/lo split (two whole-kernel passes over this
    same single-dot body — see hist_partition_fused_pallas).  There is
    deliberately no in-kernel dtype knob (ADVICE r5: the old dead
    ``hist_dtype`` parameter implied one existed).

    The r5 trace at Higgs-11M showed ~22 ms/wave of XLA-side partition
    work around a ~117 ms kernel: an [n, F] lane-reduction to pick each
    row's split-feature code, a 128-lane-padded [n, 5] lookup
    materialization, and a per-wave re-pad of the bins operand.  All of
    it reads data this kernel already holds in VMEM, so the wave's
    routing moves in here:

      pv_ref [8, chunk] f32 — per-row node fields from ONE transposed
        lookup (rows: sel, feat, thr, rank2, direct-left, lo, inv; 1 zero
        pad): the split's column (its row of ``bins``, where
        ``prepare_wave_operands`` ordered them), and the range of its
        codes that goes left, inverted where ``inv`` (``ops.members``: an
        EFB member's split; a plain column's is lo 0, not inverted);
      phase 1: v = bins[feat] via a fori_loop feature select (VMEM reads,
        no HBM); go_left = ((v >= lo) & (v <= thr)) != inv (:func:`_route`);
        seg = wave rank where the row moves to its split's DIRECT
        (smaller) child, else num_segments;
      enc_ref [1, chunk] i32 — 1 + rank2 + went-right for moved rows,
        0 otherwise (the caller adds the wave's traced node base);
      phase 2: the standard segment-folded one-hot dots, with seg now
        produced in-register.

    With ``cat_sets`` an operand ``cset_ref [B, W_pad]`` follows ``pv_ref``
    (the wave's category sets, :func:`_in_category_set`), and ``pv`` row 7
    marks the splits that route by them.
    """
    cset_ref = refs[0] if cat_sets else None
    out_ref, enc_ref = refs[-2:]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    chunk = bins_ref.shape[1]
    w = num_segments

    sel = pv_ref[0, :]
    feat = pv_ref[1, :]
    thr = pv_ref[2, :]
    rank2 = pv_ref[3, :]
    dl = pv_ref[4, :]

    # phase 1: per-row split value (the row's code at its leaf's split
    # feature) — F VMEM-resident selects, no extra HBM traffic
    def vbody(f, v):
        code = bins_ref[pl.dslice(f, 1), :].astype(jnp.float32)  # [1, chunk]
        return jnp.where(feat == f, code[0, :], v)

    v = lax.fori_loop(0, bins_ref.shape[0], vbody,
                      jnp.zeros((chunk,), jnp.float32))
    psel = sel > 0.0
    go_left = _route(v, pv_ref, thr, rank2, cset_ref)
    to_direct = psel & (go_left == (dl > 0.0))
    seg = jnp.where(to_direct, (rank2 * 0.5).astype(jnp.int32),
                    jnp.int32(w)).reshape(1, chunk)
    enc_ref[:] = jnp.where(
        psel, rank2.astype(jnp.int32) + jnp.where(go_left, 0, 1) + 1,
        0).reshape(1, chunk)

    # phase 2: standard segment-folded accumulation (see _fused_kernel)
    _accumulate_wave(bins_ref, stats_ref, seg, out_ref, runs=runs,
                     num_bins=num_bins, num_segments=w,
                     bins_minor=bins_minor)


def _fused_part_kernel_mb(bins_ref, stats_ref, pv_ref, wbins_ref, *refs,
                          runs: tuple, num_bins: int, num_segments: int,
                          bins_minor: bool = False, cat_sets: bool = False):
    """Multi-feature-block variant of :func:`_fused_part_kernel`.

    When the feature axis needs more than one VMEM block (MSLR's 136
    features at 128 lanes), phase 1 cannot select the row's split value
    from the RESIDENT bins tile — the split feature may live in another
    block.  Instead the caller gathers the W wave split features' code
    rows once per wave (``wbins`` [W_pad, n]) and every block routes
    from that operand, keyed on the row's WAVE RANK rather than its
    feature id.  Each (f-block, chunk) grid step computes the identical
    routing in-register — the "cross-block winner select" is thereby a
    replicated select, not an inter-block reduction — and rewrites the
    same ``enc`` block with the same value.  Phase 2 is byte-identical
    to the single-block kernel over this block's features; a category
    set's split routes as there (``cat_sets``: ``cset_ref`` after
    ``wbins_ref``).
    """
    cset_ref = refs[0] if cat_sets else None
    out_ref, enc_ref = refs[-2:]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    chunk = bins_ref.shape[1]
    w = num_segments

    sel = pv_ref[0, :]
    thr = pv_ref[2, :]
    rank2 = pv_ref[3, :]
    dl = pv_ref[4, :]

    # phase 1: per-row split value from the wave-gathered code rows —
    # W VMEM selects keyed on the row's wave rank (2*rank is what the
    # lookup table carries; see tree.py's tbl_w)
    def vbody(i, v):
        code = wbins_ref[pl.dslice(i, 1), :].astype(jnp.float32)
        return jnp.where(rank2 == (2 * i).astype(jnp.float32),
                         code[0, :], v)

    v = lax.fori_loop(0, w, vbody, jnp.zeros((chunk,), jnp.float32))
    psel = sel > 0.0
    go_left = _route(v, pv_ref, thr, rank2, cset_ref)
    to_direct = psel & (go_left == (dl > 0.0))
    seg = jnp.where(to_direct, (rank2 * 0.5).astype(jnp.int32),
                    jnp.int32(w)).reshape(1, chunk)
    enc_ref[:] = jnp.where(
        psel, rank2.astype(jnp.int32) + jnp.where(go_left, 0, 1) + 1,
        0).reshape(1, chunk)

    # phase 2: standard segment-folded accumulation over THIS block's
    # features (see _fused_part_kernel)
    _accumulate_wave(bins_ref, stats_ref, seg, out_ref, runs=runs,
                     num_bins=num_bins, num_segments=w,
                     bins_minor=bins_minor, fblock_axis=0)


def prepare_wave_operands(bins: jnp.ndarray, stats: jnp.ndarray,
                          num_bins: int, num_segments: int,
                          col_order: Optional[jnp.ndarray] = None):
    """One-time (per tree) prep for :func:`hist_partition_fused_pallas`:
    transpose + row-pad the loop-invariant operands OUTSIDE the growth
    while_loop (the in-call pad/convert re-ran per wave — ~2.7 ms each at
    11M rows, r5 trace).  When the feature axis needs multiple VMEM
    blocks (F > ~45; MSLR), the feature axis is zero-padded to a whole
    number of blocks here — the kernels' feature loops skip the padded
    rows (:func:`_feature_loop`) and the wrapper trims their histogram
    rows on the way out.  ``col_order`` (:func:`onehot_order`, an operand
    of the caller's program) puts the columns in the rows by the height of
    their one-hot: one gather of whole columns of the codes."""
    n, num_features = bins.shape
    s = stats.shape[1]
    k = num_segments * s
    f_blk, n_fblk, f_pad, chunk = _vmem_blocking(num_features, num_bins, k,
                                                 chunk_align=512)
    n_chunks = -(-n // chunk)
    pad = n_chunks * chunk - n
    if col_order is not None:
        # in the table's own dtype, before the 4-byte copy: a gather of
        # the transposed copy held two of them (5.2 GB at 1M x 660)
        bins = jnp.take(bins, col_order, axis=1)
    bins_t = bins.astype(jnp.int32).T
    stats_t = stats.T
    if pad or f_pad:
        bins_t = jnp.pad(bins_t, ((0, f_pad), (0, pad)))
        stats_t = jnp.pad(stats_t, ((0, 0), (0, pad)))
    return bins_t, stats_t, chunk


def hist_partition_fused_pallas(
    bins_t: jnp.ndarray,         # [F_pad, n_pad] i32 (prepare_wave_operands)
    stats_t: jnp.ndarray,        # [S, n_pad] f32 (prepare_wave_operands)
    pv_t: jnp.ndarray,           # [8, n_pad] f32 per-row node fields
    num_segments: int,
    num_bins: int,
    chunk: int,
    interpret: bool | None = None,
    hist_dtype: str = "bf16",
    wfeat: jnp.ndarray | None = None,   # [W] i32 wave split features
    num_features: int | None = None,    # nominal F (bins_t may be f-padded)
    name: str = "lgbtpu_hist_partition_fused",
    f_blk: int | None = None,           # feature block bins_t is padded for
    bins_minor: bool | None = None,     # None: from the width (TURNED_MAX_K)
    layout: Optional[FeatureLayout] = None,   # the rows' loops
    row_of: jnp.ndarray | None = None,        # [F] i32 row of each column
    cat_sets: jnp.ndarray | None = None,      # [W, B] 0/1 category sets
):
    """Fused wave pass: histogram over the direct children PLUS the row
    partition (see _fused_part_kernel).  Returns
    (hist f32 [num_segments, S, F, num_bins], enc i32 [n_pad]), the
    histograms' columns in the table's order.

    ``layout`` is the :class:`FeatureLayout` of ``bins_t``'s rows
    (``None``: every one-hot ``num_bins`` tall) and ``row_of`` the row of
    each column where :func:`prepare_wave_operands` ordered them
    (``None``: row ``c`` holds column ``c``).  ``wfeat`` and the split's
    column in ``pv_t`` (row 1) name ROWS of ``bins_t``: ``row_of[column]``
    where the rows are ordered.

    ``f_blk`` is the feature block ``bins_t`` was padded for, where that
    was another width's (a tree's narrow passes read the operands
    :func:`prepare_wave_operands` prepared at its full width, so the tree
    keeps one transposed copy of the codes); by default this width's own.
    The dot's orientation follows the width (:func:`_accumulate_wave`):
    turned up to ``TURNED_MAX_K`` statistics columns.  Either way the
    routing, the precision (bfloat16 operands, float32 accumulation in
    chunk order) and the returned planes are the same; only the float32
    summation order inside one MXU contraction may differ.

    The histograms leave as PLANES, bins minor: the kernel's ``[F, B,
    W*S]`` accumulator is turned once, as a 2-D transpose (the turned
    dot's ``[F, W*S, B]`` needs none), and nothing
    after it has the S-wide statistics axis as its minor one.  In HBM the
    chip tiles the two minor axes by (8, 128), so ``[W, F, B, 3]`` is
    stored as ``[W, F, B, 128]``: 42.7x its size, 22 GB for the 2W children
    of one wave at 2,000 features (and 308 MB a copy at 28, which was most
    of that round's temporaries).

    Single VMEM feature block: the r5 kernel routes from the resident
    bins tile.  Multiple blocks (F > ~45, r7): the W wave split
    features' code rows are gathered once (``wfeat`` required) and the
    multi-block kernel routes every block from that [W_pad, n] operand
    — see :func:`_fused_part_kernel_mb`.

    ``cat_sets`` (``None`` for a table with no categorical column: the
    kernels are then built without it) holds each wave split's category
    set, its bins 0/1; a row of a split that ``pv_t`` row 7 marks goes
    left iff its code's bit is set (:func:`_in_category_set`).
    """
    f_rows, n_pad = bins_t.shape
    if num_features is None:
        num_features = f_rows
    if num_bins > 1 << 24:
        # the routing phase widens i32 bin codes to f32 for the in-VMEM
        # threshold compare (codes live on the 128-lane minor axis, where
        # Mosaic has no i32 select) — exact only while codes < 2^24, so
        # the widening is CHECKED here instead of silently lossy
        raise ValueError(
            f"num_bins={num_bins} exceeds the f32-exact integer range "
            f"(2^24) used by the fused partition routing")
    s = stats_t.shape[0]
    k = num_segments * s
    n_chunks = n_pad // chunk
    if f_blk is None:
        f_blk = _vmem_blocking(num_features, num_bins, k,
                               chunk_align=512)[0]
    n_fblk = f_rows // f_blk
    assert f_rows == n_fblk * f_blk, (f_rows, n_fblk, f_blk)
    if layout is None:
        layout = feature_layout(num_features, f_blk, num_bins)
    assert layout.f_blk == f_blk, (layout.f_blk, f_blk)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if bins_minor is None:
        bins_minor = k <= TURNED_MAX_K
    acc_dims = (k, num_bins) if bins_minor else (num_bins, k)
    w_pad = -(-num_segments // 8) * 8
    cats = []
    if cat_sets is not None:
        # [B, W_pad]: a wave rank's set down a column
        cats = [jnp.pad(cat_sets.astype(jnp.float32),
                        ((0, w_pad - num_segments), (0, 0))).T]

    if n_fblk == 1:
        def one_pass(stats_arr):
            return pl.pallas_call(
                functools.partial(_fused_part_kernel,
                                  runs=layout.runs,
                                  num_bins=num_bins,
                                  num_segments=num_segments,
                                  bins_minor=bins_minor,
                                  cat_sets=bool(cats)),
                grid=(n_chunks,),
                in_specs=[
                    pl.BlockSpec((num_features, chunk), lambda c: (0, c),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((s, chunk), lambda c: (0, c),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((8, chunk), lambda c: (0, c),
                                 memory_space=pltpu.VMEM),
                ] + [pl.BlockSpec((num_bins, w_pad), lambda c: (0, 0),
                                  memory_space=pltpu.VMEM)
                     for _ in cats],
                out_specs=[
                    pl.BlockSpec((num_features,) + acc_dims,
                                 lambda c: (0, 0, 0),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((1, chunk), lambda c: (0, c),
                                 memory_space=pltpu.VMEM),
                ],
                out_shape=[
                    jax.ShapeDtypeStruct((num_features,) + acc_dims,
                                         jnp.float32),
                    jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
                ],
                interpret=interpret,
                name=name,
            )(bins_t, stats_arr, pv_t, *cats)
    else:
        if wfeat is None:
            raise ValueError(
                "multi-block partition fusion needs the wave split "
                "features (wfeat) to gather the routing code rows")
        wf = jnp.clip(wfeat.astype(jnp.int32), 0, num_features - 1)
        if w_pad != num_segments:
            wf = jnp.pad(wf, (0, w_pad - num_segments))
        wbins_t = jnp.take(bins_t, wf, axis=0)           # [W_pad, n_pad]

        def one_pass(stats_arr):
            return pl.pallas_call(
                functools.partial(_fused_part_kernel_mb,
                                  runs=layout.runs,
                                  num_bins=num_bins,
                                  num_segments=num_segments,
                                  bins_minor=bins_minor,
                                  cat_sets=bool(cats)),
                grid=(n_fblk, n_chunks),
                in_specs=[
                    pl.BlockSpec((f_blk, chunk), lambda f, c: (f, c),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((s, chunk), lambda f, c: (0, c),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((8, chunk), lambda f, c: (0, c),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((w_pad, chunk), lambda f, c: (0, c),
                                 memory_space=pltpu.VMEM),
                ] + [pl.BlockSpec((num_bins, w_pad), lambda f, c: (0, 0),
                                  memory_space=pltpu.VMEM)
                     for _ in cats],
                out_specs=[
                    pl.BlockSpec((f_blk,) + acc_dims,
                                 lambda f, c: (f, 0, 0),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((1, chunk), lambda f, c: (0, c),
                                 memory_space=pltpu.VMEM),
                ],
                out_shape=[
                    jax.ShapeDtypeStruct((f_rows,) + acc_dims,
                                         jnp.float32),
                    jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
                ],
                interpret=interpret,
                name=name,
            )(bins_t, stats_arr, pv_t, wbins_t, *cats)

    if hist_dtype in ("f32", "f32x"):
        hi, lo = split_hi_lo(stats_t)
        h1, enc = one_pass(hi)
        h2, _ = one_pass(lo)
        out = h1 + h2
    else:
        out, enc = one_pass(stats_t)
    out = _by_column(out, row_of, num_features)
    if bins_minor:                       # [F, W*S, B]: major axes only
        out = out.reshape(num_features, num_segments, s, num_bins)
        return out.transpose(1, 2, 0, 3), enc[0]
    out = out.transpose(2, 0, 1)                         # [W*S, F, B]
    return out.reshape(num_segments, s, num_features, num_bins), enc[0]


def hist_fused_pallas_batched(
    bins: jnp.ndarray,           # [n, F] shared bin codes
    stats: jnp.ndarray,          # [E, n, S] per-element statistics
    seg_id: jnp.ndarray,         # [E, n] per-element row segments
    num_segments: int,
    num_bins: int,
    chunk: Optional[int] = None,
    interpret: bool | None = None,
    hist_dtype: str = "f32",
    name: str = "lgbtpu_hist_fused",
) -> jnp.ndarray:
    """Batched fused histograms: -> f32 [E, num_segments, F, num_bins, S].

    The element axis (configs x folds of the fused cv trainer, classes of
    multiclass) becomes a GRID dimension over the same single-dot kernel:
    each (element, feature-block, chunk) step folds that element's
    segment one-hot with its stats entirely in VMEM and contracts on the
    MXU.  This replaces the segstats route, which materialized a
    [n, E*num_segments*S] operand in HBM — ~700 MB per wave at the
    108-config sweep's shape (E=30, W=42) and the measured reason
    fused-cv rounds cost ~100x their FLOPs.  Per-element tiles are small
    (the fold is [chunk, K]), so the only re-read across elements is the
    bins block — negligible next to the matmul.

    int8 is not supported here (per-element quantization scales would be
    needed); callers route that mode to the segstats/XLA path.
    """
    e, n, s = stats.shape
    num_features = bins.shape[1]
    k = num_segments * s
    if hist_dtype == "f32x":
        hist_dtype = "f32"
    if hist_dtype == "int8":
        raise ValueError("hist_fused_pallas_batched does not support int8")

    f_blk, n_fblk, f_pad, auto_chunk = _vmem_blocking(
        num_features, num_bins, k, chunk_align=256)
    if chunk is None:
        chunk = auto_chunk

    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    bins_t = bins.astype(jnp.int32).T                       # [F, n]
    seg_id = seg_id.astype(jnp.int32)
    seg_id = jnp.where((seg_id >= 0) & (seg_id < num_segments), seg_id, -1)

    n_chunks = -(-n // chunk)
    pad = n_chunks * chunk - n
    if pad or f_pad:
        bins_t = jnp.pad(bins_t, ((0, f_pad), (0, pad)))
        stats = jnp.pad(stats, ((0, 0), (0, pad), (0, 0)))
        seg_id = jnp.pad(seg_id, ((0, 0), (0, pad)), constant_values=-1)
    n_pad_rows = n_chunks * chunk

    # flat [. , E*n] layouts with rows on the 128-lane minor dim (see
    # _fused_kernel layout note); the index maps pick the element via
    # block-column arithmetic
    stats_flat = stats.transpose(2, 0, 1).reshape(s, e * n_pad_rows)
    seg_flat = seg_id.reshape(1, e * n_pad_rows)

    def one_pass(stats_arr, mode):
        return pl.pallas_call(
            functools.partial(_fused_kernel, runs=feature_layout(
                num_features, f_blk, num_bins).runs, num_bins=num_bins,
                num_segments=num_segments, hist_dtype=mode, chunk_dim=2),
            grid=(e, n_fblk, n_chunks),
            in_specs=[
                pl.BlockSpec((f_blk, chunk), lambda el, fb, c: (fb, c),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((s, chunk),
                             lambda el, fb, c, nc=n_chunks: (0, el * nc + c),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, chunk),
                             lambda el, fb, c, nc=n_chunks: (0, el * nc + c),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec(
                (f_blk, num_bins, k),
                lambda el, fb, c, nf=n_fblk: (el * nf + fb, 0, 0),
                memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct(
                (e * n_fblk * f_blk, num_bins, k), jnp.float32),
            interpret=interpret,
            name=name,
        )(bins_t, stats_arr, seg_flat)

    if hist_dtype == "f32":
        hi, lo = split_hi_lo(stats_flat)
        out = one_pass(hi, "bf16") + one_pass(lo, "bf16")
    else:
        out = one_pass(stats_flat, hist_dtype)
    out = out.reshape(e, n_fblk * f_blk, num_bins, k)[:, :num_features]
    out = out.reshape(e, num_features, num_bins, num_segments, s)
    return out.transpose(0, 3, 1, 2, 4)
