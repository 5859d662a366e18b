"""Gradient/hessian histogram construction — the GBDT hot loop.

This is the TPU-native replacement for LightGBM's OpenMP histogram
construction (upstream ``src/treelearner/``, exercised by every ``lgb.train`` /
``lgb.cv`` call in the reference — SURVEY.md §2C row "Histogram construction
hot loop").

Formulation: scatter-add is slow on TPU, so the histogram is computed as a
one-hot **matmul** that runs on the MXU:

    hist[b, k] = sum_n  onehot(bin[n] == b) * segstats[n, k]

where ``segstats`` folds the (segment × statistic) axes together; segments are
tree leaves (or CV folds × leaves later).  Features are processed by a
``lax.scan`` so only one [rows, bins] one-hot is live at a time, and rows are
chunked so peak memory stays bounded for multi-million-row data.

A Pallas kernel with the same signature (one-hot built tile-by-tile in VMEM,
never materialized in HBM) lives in ``histogram_pallas.py`` and is selected
via ``ops.histogram.compute_histograms(..., impl=...)``.

The feature axis F here is the CALLER's column space: under r20 feature
screening the grower passes a gathered ``[N, F_active]`` bin view, so the
scan length, the merge payloads below, and the per-chunk one-hot work all
shrink to the active set with no screening logic in this module.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

DEFAULT_ROW_CHUNK = 131072


def sr_round_bf16(x: jnp.ndarray) -> jnp.ndarray:
    """Stochastically round f32 values to bf16-REPRESENTABLE f32.

    Hypothesis: round-to-nearest bf16 BIASES histogram sums when gradient
    values cluster on few distinct magnitudes (early binary-logloss
    rounds take ~2 distinct g values across a million rows, so per-value
    rounding error correlates across rows).  Unbiased stochastic
    rounding replaces that bias with O(ulp*sqrt(count)) zero-mean noise
    per cell: add a deterministic per-ELEMENT 16-bit hash to the f32 bit
    pattern and truncate the low mantissa bits.  E[q(x)] = x; sign
    handled by IEEE magnitude-monotone bit patterns; idempotent on
    already-representable values.

    MEASURED NEGATIVE (r5, Higgs-1M, 100 rounds, exact-tail configs):
    SR consistently lands ~3e-4 AUC BELOW round-to-nearest (TPU AUC
    0.89812-0.89818 vs 0.89841-0.89842 across four converged-coverage
    configs; training is deterministic so these are real config deltas)
    — the added variance in small-leaf sums costs more than the RN bias
    it removes.  Kept available behind ``hist_dtype="bf16sr"`` for other
    workloads; NOT applied by default.
    """
    u = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    idx = lax.broadcasted_iota(jnp.uint32, x.shape, 0)
    for d in range(1, x.ndim):
        idx = idx * jnp.uint32(x.shape[d]) + lax.broadcasted_iota(
            jnp.uint32, x.shape, d)
    h = idx * jnp.uint32(2654435761) + jnp.uint32(974711)
    r16 = (h >> jnp.uint32(13)) & jnp.uint32(0xFFFF)
    q = (u + r16) & jnp.uint32(0xFFFF0000)
    out = lax.bitcast_convert_type(q, jnp.float32)
    return jnp.where(jnp.isfinite(x) & jnp.isfinite(out), out, x)


def _hist_one_chunk(bins_c: jnp.ndarray, segstats_c: jnp.ndarray,
                    num_bins: int, hist_dtype: str = "f32"):
    """bins_c: i32[nc, F]; segstats_c: f32[nc, K] -> f32[F, num_bins, K].

    hist_dtype: "f32" runs the matmul at HIGHEST precision (true f32 —
    split gains are differences of large sums and bf16-quantized inputs
    can corrupt them); "bf16" quantizes the matmul inputs for ~6x MXU
    throughput with f32 accumulation (~0.2% histogram error — validated
    against full-precision scores before use in benchmarks).
    """
    if hist_dtype == "bf16":
        segstats_c = segstats_c.astype(jnp.bfloat16)
    # "int8" is a pallas-kernel-only mode; this XLA path runs it at full
    # precision (same results, no quantization) rather than erroring so
    # hist_impl="jnp"/CPU fallbacks stay usable

    def per_feature(_, bins_f):
        # one-hot built ALREADY TRANSPOSED [B, n]: the contraction then runs
        # over the minor (lane) axis of both operands — a clean
        # [B, n] @ [n, K] MXU matmul with no relayout of a [n, B] matrix
        # (the n-major one-hot forces XLA to transpose 33M elements per
        # chunk-feature, which dominated the pass cost)
        onehot_t = (bins_f[None, :] == lax.iota(jnp.int32, num_bins)[:, None])
        onehot_t = onehot_t.astype(segstats_c.dtype)
        h = lax.dot_general(
            onehot_t, segstats_c,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=(lax.Precision.DEFAULT if hist_dtype == "bf16"
                       else lax.Precision.HIGHEST))
        return _, h

    _, hists = lax.scan(per_feature, None, bins_c.T)  # [F, B, K]
    return hists


def _hist_from_segstats(bins: jnp.ndarray, segstats: jnp.ndarray,
                        num_bins: int, row_chunk: int,
                        hist_dtype: str = "f32") -> jnp.ndarray:
    """Core one-hot-matmul histogram: bins [n,F] x segstats [n,K] ->
    [F, num_bins, K]; rows chunked to bound the materialized one-hot."""
    n, num_features = bins.shape
    k = segstats.shape[1]
    bins = bins.astype(jnp.int32)
    if n <= row_chunk:
        return _hist_one_chunk(bins, segstats, num_bins, hist_dtype)
    n_chunks = -(-n // row_chunk)
    pad = n_chunks * row_chunk - n
    if pad:
        bins = jnp.pad(bins, ((0, pad), (0, 0)))
        segstats = jnp.pad(segstats, ((0, pad), (0, 0)))
    bins_chunks = bins.reshape(n_chunks, row_chunk, num_features)
    seg_chunks = segstats.reshape(n_chunks, row_chunk, k)

    def chunk_body(acc, xs):
        b_c, s_c = xs
        return acc + _hist_one_chunk(b_c, s_c, num_bins, hist_dtype), None

    init = jnp.zeros((num_features, num_bins, k), jnp.float32)
    hists, _ = lax.scan(chunk_body, init, (bins_chunks, seg_chunks))
    return hists


def _segstats(stats: jnp.ndarray, seg_id: jnp.ndarray,
              num_segments: int) -> jnp.ndarray:
    """Fold (segment one-hot x stats) -> [..., n, num_segments * S]."""
    seg_onehot = (seg_id[..., None]
                  == lax.iota(jnp.int32, num_segments))
    out = (seg_onehot.astype(stats.dtype)[..., :, None]
           * stats[..., None, :])
    return out.reshape(*stats.shape[:-1], num_segments * stats.shape[-1])


def compute_histograms(
    bins: jnp.ndarray,
    stats: jnp.ndarray,
    seg_id: jnp.ndarray,
    num_segments: int,
    num_bins: int,
    row_chunk: int = DEFAULT_ROW_CHUNK,
    impl: str = "auto",
    hist_dtype: str = "f32",
    name: Optional[str] = None,
) -> jnp.ndarray:
    """Histogram of per-row statistics over (segment, feature, bin).

    Args:
      bins: uint8/int32 ``[n, F]`` bin codes.
      stats: f32 ``[n, S]`` per-row statistics (grad, hess, count-mask, ...).
        Rows excluded from the histogram (padding, bagged-out) must carry
        zero stats *or* an out-of-range ``seg_id``.
      seg_id: int32 ``[n]`` segment of each row; values outside
        ``[0, num_segments)`` contribute nothing.
      num_segments: static segment count (e.g. 2 for the two fresh children).
      num_bins: static bin-axis size.
      name: the role this pass plays for the caller, given to the Pallas
        kernel that serves it in place of the kernel's own name (what the
        device trace shows); the XLA path has no kernel to name.

    Returns:
      f32 ``[num_segments, F, num_bins, S]``.
    """
    named = {} if name is None else {"name": name}
    # "f32x" = EXPLICIT f32 request (resolve_hist_dtype): a contract for
    # exactness, so auto-routing may not swap in the fused kernel's hi/lo
    # bf16 approximation (~1e-5 relative) — only a forced hist_impl=
    # "pallas" overrides it (ADVICE r3)
    exact = hist_dtype == "f32x"
    if exact:
        hist_dtype = "f32"
    if hist_dtype == "bf16sr":         # opt-in SR variant (see sr_round_bf16)
        hist_dtype = "bf16"
        stats = sr_round_bf16(stats)
    if impl == "pallas" or (impl == "auto" and not exact
                            and jax.default_backend() == "tpu"):
        # the fused kernel folds the segment one-hot in VMEM and keeps the
        # [F, B, K] accumulator resident — ~100x less HBM traffic than the
        # XLA scan path and native-rate MXU passes (2 passes for "f32" via
        # a hi/lo bf16 split; see histogram_pallas.py)
        from . import histogram_pallas
        return histogram_pallas.hist_fused_pallas(
            bins, stats, seg_id, num_segments, num_bins,
            hist_dtype=hist_dtype, **named)

    num_features = bins.shape[1]
    s = stats.shape[1]
    segstats = _segstats(stats, seg_id, num_segments)
    hists = _hist_from_segstats(bins, segstats, num_bins, row_chunk,
                                hist_dtype)
    # [F, B, K] -> [num_segments, F, B, S]
    return hists.reshape(num_features, num_bins, num_segments, s).transpose(2, 0, 1, 3)


def compute_histograms_batched(
    bins: jnp.ndarray,
    stats: jnp.ndarray,
    seg_id: jnp.ndarray,
    num_segments: int,
    num_bins: int,
    row_chunk: int = DEFAULT_ROW_CHUNK,
    impl: str = "auto",
    hist_dtype: str = "f32",
    name: Optional[str] = None,
) -> jnp.ndarray:
    """Batched histograms with a SHARED binned matrix: the key memory-bound
    optimization for vmapped training (fused cv over configs x folds,
    multiclass class axis).

    Instead of E skinny matmuls re-materializing the per-feature one-hot E
    times (what naive vmap lowering does), the whole batch's statistics fold
    into one wide [n, E*num_segments*S] operand and each feature needs ONE
    matmul and ONE one-hot materialization per pass.

    Args: stats [E, n, S]; seg_id [E, n]; bins [n, F] shared.
    Returns f32 [E, num_segments, F, num_bins, S].
    """
    e, n, s = stats.shape
    num_features = bins.shape[1]
    k_inner = e * num_segments * s
    named = {} if name is None else {"name": name}
    exact = hist_dtype == "f32x"          # see compute_histograms
    if exact:
        hist_dtype = "f32"
    if hist_dtype == "bf16sr":            # see compute_histograms
        hist_dtype = "bf16"
        stats = sr_round_bf16(stats)
    if (impl in ("pallas", "auto") and not exact and hist_dtype != "int8"
            and num_segments * s >= 64
            and jax.default_backend() == "tpu"):
        # WIDE-segment batches only (wave grower under vmap, W*S >= 64
        # lanes): the element axis becomes a kernel GRID dim so per-element
        # segment folds happen in VMEM, never materializing the
        # [n, E*K*S] segstats operand in HBM (~700 MB/wave at the sweep
        # shape).  Narrow-segment calls (strict grower's K=2, root's K=1)
        # stay on the segstats route: their operand is small, the fold is
        # cheaper as one XLA pass, and sub-8-lane kernel blocks are the
        # Mosaic-fragility zone (r4: k=6 blocks faulted the TPU worker).
        from .histogram_pallas import hist_fused_pallas_batched
        return hist_fused_pallas_batched(bins, stats, seg_id, num_segments,
                                         num_bins, hist_dtype=hist_dtype,
                                         **named)
    segstats = _segstats(stats, seg_id, num_segments)      # [E, n, K*S]
    segstats = jnp.moveaxis(segstats, 0, 1).reshape(n, k_inner)
    # int8 never enters the segstats kernel: it has no quantization path
    # (and raises since r9 — before that it silently ran full precision).
    # The XLA fallback below runs int8 at full precision by documented
    # design, keeping hist_impl="jnp"/CPU usable.
    if hist_dtype != "int8" and (
            impl == "pallas" or (impl == "auto" and not exact
                                 and k_inner >= 64
                                 and jax.default_backend() == "tpu")):
        from .histogram_pallas import hist_from_segstats_pallas
        hists = hist_from_segstats_pallas(bins, segstats, num_bins,
                                          hist_dtype=hist_dtype, **named)
    else:
        hists = _hist_from_segstats(bins, segstats, num_bins, row_chunk,
                                    hist_dtype)
    hists = hists.reshape(num_features, num_bins, e, num_segments, s)
    return hists.transpose(2, 3, 0, 1, 4)


@functools.lru_cache(maxsize=None)
def batched_histogram_op(num_segments: int, num_bins: int,
                         row_chunk: int = DEFAULT_ROW_CHUNK,
                         impl: str = "auto", hist_dtype: str = "f32",
                         name: Optional[str] = None):
    """compute_histograms wrapped with a custom vmap rule.

    Under `jax.vmap` (fold/config/class batching of the tree grower), calls
    with a shared ``bins`` re-route to :func:`compute_histograms_batched`
    instead of the default per-element lowering.
    """
    from jax.custom_batching import custom_vmap

    @custom_vmap
    def op(bins, stats, seg_id):
        return compute_histograms(bins, stats, seg_id, num_segments,
                                  num_bins, row_chunk, impl, hist_dtype,
                                  name)

    @op.def_vmap
    def _rule(axis_size, in_batched, bins, stats, seg_id):
        bins_b, stats_b, seg_b = in_batched
        if bins_b:
            # rare: per-element binned matrices — no sharing to exploit
            out = jax.vmap(
                lambda b, st, sg: compute_histograms(
                    b, st, sg, num_segments, num_bins, row_chunk, impl,
                    hist_dtype, name)
            )(bins,
              stats if stats_b else jnp.broadcast_to(
                  stats, (axis_size,) + stats.shape),
              seg_id if seg_b else jnp.broadcast_to(
                  seg_id, (axis_size,) + seg_id.shape))
            return out, True
        if not stats_b:
            stats_ = jnp.broadcast_to(stats, (axis_size,) + stats.shape)
        else:
            stats_ = stats
        if not seg_b:
            seg_ = jnp.broadcast_to(seg_id, (axis_size,) + seg_id.shape)
        else:
            seg_ = seg_id
        out = compute_histograms_batched(bins, stats_, seg_, num_segments,
                                         num_bins, row_chunk, impl,
                                         hist_dtype, name)
        return out, True

    return op


def histogram_psum(hist: jnp.ndarray, axis_name: Optional[str]) -> jnp.ndarray:
    """Data-parallel histogram merge: the TPU-native equivalent of LightGBM's
    socket/MPI/NCCL allreduce (upstream ``network/``; SURVEY.md §5
    "Distributed communication backend").  Inside ``shard_map`` over a row-
    sharded mesh axis, per-shard partial histograms are summed over ICI/DCN.

    Thin compatibility wrapper over :func:`histogram_merge` with
    ``mode="psum"`` — the full-allreduce topology every shard replicates.
    """
    return histogram_merge(hist, axis_name, mode="psum")


def pad_feature_axis(hist: jnp.ndarray, n_shards: int,
                     axis: int) -> jnp.ndarray:
    """Zero-pad the feature axis to a multiple of ``n_shards`` (padded
    columns are all-zero histograms, masked out of every split scan by the
    sliced feature mask — same idiom as feature_parallel.pad_features)."""
    f = hist.shape[axis]
    f_pad = -(-f // n_shards) * n_shards
    if f_pad == f:
        return hist
    pads = [(0, 0)] * hist.ndim
    pads[axis] = (0, f_pad - f)
    return jnp.pad(hist, pads)


# r14: the wire quantizer moved to the shared ops.quantize module (the
# serving PackedForest quantizer reuses its symmetric-scale machinery);
# these are re-export shims so every r10 call site — and the measured
# quality gates behind it — stays byte-for-byte unchanged.
from .quantize import WIRE_DTYPES  # noqa: E402  (re-export)
from .quantize import wire_transfer as _wire_transfer  # noqa: E402


def merge_slice_width(num_features: int, n_shards: int,
                      mode: str = "reduce_scatter",
                      n_chunks: int = 1) -> int:
    """Per-shard feature-slice width a merge mode hands the scorer.

    Plain reduce-scatter pads F to a D-multiple; the pipelined mode pads
    to a ``D * n_chunks`` multiple so every shard slice splits into
    ``n_chunks`` equal sub-chunks.  Callers that size per-shard buffers
    (the frontier grower's histogram cache, the dist scorer's metadata
    slices) must use THIS width, not ``ceil(F/D)``.
    """
    mult = n_shards * (n_chunks if mode == "reduce_scatter_pipelined"
                       else 1)
    f_pad = -(-num_features // mult) * mult
    return f_pad // n_shards


def ring_reduce_scatter(x: jnp.ndarray, axis_name: str, n_shards: int,
                        axis: int, wire_dtype: str = "f32") -> jnp.ndarray:
    """Reduce-scatter decomposed into ``n_shards - 1`` ``ppermute`` hops.

    Chunk ``c``'s partial starts at shard ``c+1`` and travels the ring
    ``c+1 -> c+2 -> ... -> c``, each hop adding the receiver's local
    contribution, so shard ``i`` ends holding chunk ``i`` summed over all
    shards.  Semantically identical to ``lax.psum_scatter`` but each hop
    is an independent small collective the latency-hiding scheduler can
    overlap with whatever compute is pending between issue and first use
    (the frontier grower's cache gather / partition bookkeeping) — the
    "ppermute-friendly scheduling" half of the comm/compute overlap.
    Summation order is fixed (ring order) but differs from psum's
    reduction tree, so cross-mode results agree to f32 rounding, not
    bitwise.
    """
    f_pad = x.shape[axis]
    assert f_pad % n_shards == 0, "pad the feature axis first"
    f_loc = f_pad // n_shards
    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    def chunk(k):
        start = jnp.mod(idx - 1 - k, n_shards) * f_loc
        return lax.dynamic_slice_in_dim(x, start, f_loc, axis=axis)

    acc = chunk(0)
    for k in range(1, n_shards):
        acc = _wire_transfer(acc, axis_name, perm, wire_dtype,
                             f_axis=axis) + chunk(k)
    return acc


def ring_reduce_scatter_pipelined(x: jnp.ndarray, axis_name: str,
                                  n_shards: int, axis: int, n_chunks: int,
                                  wire_dtype: str = "f32") -> jnp.ndarray:
    """:func:`ring_reduce_scatter` split into ``n_chunks`` independent
    sub-rings along the feature axis — the double-buffered form.

    Each shard's ``f_loc`` slice is cut into ``n_chunks`` equal
    sub-chunks and every hop ``k`` is emitted for ALL chunks before hop
    ``k+1`` of any of them, so the chunks' hop-``k`` transfers are
    mutually independent collectives: on TPU the async scheduler can
    fly chunk ``k``'s ``ppermute`` while the consumer (the per-chunk
    split scan downstream) works on chunk ``k−1``'s landed slice.  Every
    column is still a fixed-order ring sum (the owner's ``idx−1−k``
    rotation), so the arithmetic contract matches the plain ring's:
    bitwise identical when the feature padding coincides (``n_chunks==1``
    or ``F`` already a ``D*n_chunks`` multiple — a wider pad moves a
    column to a different owner, hence a different rotation of the same
    addends), f32-rounding-close otherwise.  Tree-level parity with the
    serial grower is the gate the tests pin — the same bar r9's modes
    met.

    Requires ``x.shape[axis]`` divisible by ``n_shards * n_chunks``
    (pad with :func:`pad_feature_axis` using that multiple; see
    :func:`merge_slice_width`).
    """
    f_pad = x.shape[axis]
    assert f_pad % (n_shards * n_chunks) == 0, \
        "pad the feature axis to a shards*chunks multiple first"
    f_loc = f_pad // n_shards
    sub = f_loc // n_chunks
    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    def piece(c, k):
        start = jnp.mod(idx - 1 - k, n_shards) * f_loc + c * sub
        return lax.dynamic_slice_in_dim(x, start, sub, axis=axis)

    accs = [piece(c, 0) for c in range(n_chunks)]
    for k in range(1, n_shards):
        accs = [_wire_transfer(a, axis_name, perm, wire_dtype,
                               f_axis=axis) + piece(c, k)
                for c, a in enumerate(accs)]
    return jnp.concatenate(accs, axis=axis)


def histogram_merge(hist: jnp.ndarray, axis_name: Optional[str],
                    mode: str = "psum", n_shards: int = 1,
                    wire_dtype: str = "f32",
                    n_chunks: int = 1) -> jnp.ndarray:
    """Merge per-shard partial histograms ``[..., F, B, C]`` over a mesh axis.

    The topology choice — LightGBM's data-parallel learner evolution
    expressed as shard_map collectives (upstream ``DataParallelTreeLearner``
    replaced its naive allreduce with Reduce-Scatter for exactly this
    reason; arXiv:1706.08359 §distributed, arXiv:1806.11248):

      * ``"psum"`` — full allreduce; every shard materializes the whole
        merged histogram and re-runs split finding redundantly.  Per-shard
        received payload: the full ``S*F*B*C`` tensor.
      * ``"reduce_scatter"`` — one ``lax.psum_scatter`` over the feature
        axis; each shard receives only its ``F/D`` feature slice (padded to
        a shard multiple) and scans splits for those features only.
        Per-shard received payload drops by ``D``; the per-shard winners
        are then combined with an O(D) all-gather + argmax
        (parallel.feature_parallel.reduce_best_split).
      * ``"reduce_scatter_ring"`` — same result via an explicit
        :func:`ring_reduce_scatter` (D-1 ppermute hops the scheduler can
        interleave with independent compute).
      * ``"reduce_scatter_pipelined"`` — the ring split into ``n_chunks``
        independent sub-rings (:func:`ring_reduce_scatter_pipelined`):
        chunk ``k``'s hops fly while the scorer scans chunk ``k−1``.
        f32 wire is bitwise identical to the plain ring; the feature
        axis pads to a ``D * n_chunks`` multiple, so size metadata
        slices with :func:`merge_slice_width`.

    ``wire_dtype`` (``"f32"``/``"bf16"``/``"int8"``) compresses ring-hop
    messages (see :func:`_wire_transfer`); it only exists where a hop
    boundary exists, so non-f32 wire with ``psum``/``reduce_scatter``
    (single fused XLA collectives) is a ``ValueError``.

    The feature axis is ``ndim - 3`` (histograms are ``[..., F, B, C]``).
    Reduce-scatter modes return the LOCAL padded slice ``[..., F_pad/D, B,
    C]``; callers must slice per-feature metadata (masks, monotone signs,
    categorical flags) to the same window and globalize winning feature
    ids by ``shard * f_local``.
    """
    if axis_name is None:
        return hist
    if wire_dtype not in WIRE_DTYPES:
        raise ValueError(
            f"unknown wire dtype {wire_dtype!r}; expected one of "
            f"{WIRE_DTYPES}")
    if wire_dtype != "f32" and mode in ("psum", "reduce_scatter"):
        raise ValueError(
            f"wire_dtype={wire_dtype!r} needs a ring merge mode with "
            f"explicit hop boundaries; {mode!r} lowers to one fused XLA "
            "collective")
    if mode == "psum":
        return lax.psum(hist, axis_name)
    axis = hist.ndim - 3
    if mode == "reduce_scatter_pipelined":
        n_chunks = max(int(n_chunks), 1)
        padded = pad_feature_axis(hist, n_shards * n_chunks, axis)
        return ring_reduce_scatter_pipelined(padded, axis_name, n_shards,
                                             axis, n_chunks, wire_dtype)
    padded = pad_feature_axis(hist, n_shards, axis)
    if mode == "reduce_scatter":
        return lax.psum_scatter(padded, axis_name, scatter_dimension=axis,
                                tiled=True)
    if mode == "reduce_scatter_ring":
        return ring_reduce_scatter(padded, axis_name, n_shards, axis,
                                   wire_dtype)
    raise ValueError(
        f"unknown histogram merge mode {mode!r}; expected 'psum', "
        "'reduce_scatter', 'reduce_scatter_ring', or "
        "'reduce_scatter_pipelined'")
