"""Streamed × data-parallel training: per-shard BlockStores on the dp
mesh with per-block-round pipelined merges (ISSUE r19 tentpole).

Composition of the two scale axes that previously only worked alone:

* **r11 out-of-core**: the [n, F] code matrix lives in host blocks and
  every histogram pass is a host loop over prefetched ``device_put``
  transfers;
* **r9/r10 multi-chip**: rows shard over a 1-D ``Mesh(('data',))`` and
  per-shard histogram partials merge through
  ``ops.histogram.histogram_merge`` (psum / reduce-scatter ring /
  pipelined sub-chunk ring with optional bf16/int8 wire).

Here the parent :class:`~.block_store.BlockStore` splits into D
per-shard stores over contiguous block ranges
(:func:`~.block_store.shard_block_store`) — shard ``s`` streams ONLY its
own row range onto its own device, so D PCIe pipelines run concurrently
and per-device ingest bytes drop by D.  Each **block-round** is one
``shard_map``-ed program: every device runs the UNCHANGED serial
per-block kernel (``models.tree._stream_*_block_fn``) on its local
block, then the r10 merge runs **per block-round**, so the inter-chip
transfer of block ``j``'s partial flies while block ``j+1``'s PCIe
prefetch and histogram compute proceed (``analysis.budgets.
stream_dp_time_model`` budgets this overlap at the reference shape).

Under the reduce-scatter modes the merged partial stays FEATURE-SHARDED
across block-rounds — each shard accumulates only its F/D slice — and
the full histogram is gathered ONCE per split iteration when the
replicated update consumes it, so per-iteration ICI bytes are
``K·(D-1)/D·H`` (ring, wire-compressible) plus one ``(D-1)/D·H`` gather
instead of ``K·2(D-1)/D·H`` for per-block psums.

GOSS-at-the-source multiplies with the int8 wire format: each shard
samples its OWN rows on host (top-|g| + seeded uniform rest, upstream's
per-machine sampling) so PCIe bytes shrink by the sampling rate, while
the compacted shards' histograms merge over int8 ring hops so ICI bytes
shrink 4× — multiplicative, modeled in ``STREAM_DP_BUDGETS`` and
measured in tools/bench_stream_dp.py.

Parity contract (PARITY.md): with f32 wire the grown trees match
in-memory single-chip training on the established dp bar — split
structure and row partitions ``np.array_equal``, leaf values / preds to
f32 rounding — and are FULLY bit-identical where every histogram sum is
exact (single-round dyadic data pins this in tests/test_stream_dp.py).
int8/bf16 wire is tolerance-gated, never bit-claimed.

Feature screening (r20) stacks on BOTH byte reductions orthogonally:
on screened rounds the Booster wraps each per-shard store in a
:class:`~.block_store.ColumnViewStore` before handing it to the round
drivers below, so PCIe ingest shrinks by ``F / F_active`` per shard
(on top of GOSS's row sampling) and every per-block-round merge moves
``F_active``-width histograms over the ring (on top of the wire
dtype).  The drivers themselves are screening-blind — the view store
and the compacted kernel shapes carry the whole change.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models.spec import GrowSpec, WaveSchedule
from ..models.tree import (
    _stream_root_block_fn,
    _stream_strict_block_fn,
    _stream_wave_block_fn,
    _stream_wave_fns,
    _tree_from_packed,
    stream_exact_prune,
    stream_strict_init,
    stream_strict_update,
    stream_wave_init,
    wave_extent,
)
from ..ops.histogram import histogram_merge
from ..parallel.data_parallel import DATA_AXIS, shard_rows
from .stream_grow import _grad_stats_fn, _pred_update_fn

_RS_MODES = ("reduce_scatter", "reduce_scatter_ring",
             "reduce_scatter_pipelined")


def choose_stream_dp_devices(num_blocks: int, n_devices: int) -> int:
    """Largest device count <= ``n_devices`` dividing ``num_blocks``.

    Divisibility keeps the per-shard block walks in lockstep (every
    block-round is a full-mesh collective) and — because every block in
    a multi-block store is exactly ``block_rows`` — automatically makes
    the padded row extent shard-divisible too.
    """
    d = max(int(n_devices), 1)
    while d > 1 and num_blocks % d:
        d -= 1
    return d


def setup_stream_shards(store, mesh):
    """Shard ``store`` across ``mesh`` and pin each shard's transfers to
    its own device -> list of per-shard BlockStores (with independent
    ``bytes_streamed`` PCIe odometers, surfaced by the bench)."""
    from .block_store import shard_block_store

    devices = list(mesh.devices.flat)
    shards = shard_block_store(store, len(devices))
    for sh, dev in zip(shards, devices):
        sh.device = dev
    return shards


def drain_shard_odometers(store, shards) -> None:
    """Fold the per-shard PCIe odometers into the parent store's global
    ``bytes_streamed`` (keeping the r11 global odometer contract) while
    leaving per-shard counters intact for the per-device byte model."""
    store.bytes_streamed = sum(sh.bytes_streamed for sh in shards)


def dp_block_rounds(shards, mesh):
    """Yield ``(local_offset, bins_global)`` per block-round.

    Every shard's generator advances in lockstep: round ``j`` assembles
    shard ``s``'s local block ``j`` (already on device ``s`` via the
    per-shard prefetch pipeline) into ONE row-sharded global array —
    zero-copy, ``jax.make_array_from_single_device_arrays`` — whose
    local offset ``j * block_rows`` is the SAME replicated scalar on
    every shard, so the serial per-block kernels run verbatim on local
    slices.
    """
    sharding = NamedSharding(mesh, P(DATA_AXIS))
    n_shards = len(shards)
    block_rows = shards[0].block_rows
    num_features = shards[0].num_features
    gens = [sh.device_blocks() for sh in shards]
    for rounds in zip(*gens):
        blks = [r[1] for r in rounds]
        bins_g = jax.make_array_from_single_device_arrays(
            (n_shards * block_rows, num_features), sharding, blks)
        yield rounds[0][0], bins_g


def _hist_out_spec(merge_mode: str):
    # reduce-scatter modes leave the merged histogram FEATURE-sharded
    # ([S, F_pad/D, B, 3] per shard -> global [S, F_pad, B, 3]); psum
    # replicates it
    return P(None, DATA_AXIS) if merge_mode in _RS_MODES else P()


@functools.lru_cache(maxsize=None)
def _dp_root_block_step(mesh, num_bins: int, block_rows: int,
                        hist_impl: str, hist_dtype: str, merge_mode: str,
                        wire_dtype: str, merge_chunks: int):
    """One root block-round: the serial root block kernel on each local
    block + the per-block-round mesh merge."""
    n_shards = int(mesh.shape[DATA_AXIS])
    blk = _stream_root_block_fn(num_bins, block_rows, hist_impl,
                                hist_dtype)

    def body(bins_b, stats, off):
        h = blk(bins_b, stats, off)
        return histogram_merge(h, DATA_AXIS, merge_mode, n_shards,
                               wire_dtype, merge_chunks)

    return jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P()),
        out_specs=_hist_out_spec(merge_mode),
        check_vma=False))


@functools.lru_cache(maxsize=None)
def _dp_strict_block_step(mesh, num_bins: int, block_rows: int,
                          hist_impl: str, hist_dtype: str,
                          merge_mode: str, wire_dtype: str,
                          merge_chunks: int):
    """One strict split-iteration block-round: local partition +
    {left, right, other} histogram partial (the serial kernel verbatim),
    then the r10 merge — per block-round, so the ring hops of block
    ``j`` overlap block ``j+1``'s prefetch + compute."""
    n_shards = int(mesh.shape[DATA_AXIS])
    blk = _stream_strict_block_fn(num_bins, block_rows, hist_impl,
                                  hist_dtype)

    def body(bins_b, stats, row_leaf, off, aux, n_nodes):
        rl2, h = blk(bins_b, stats, row_leaf, off, aux, n_nodes)
        hm = histogram_merge(h, DATA_AXIS, merge_mode, n_shards,
                             wire_dtype, merge_chunks)
        return rl2, hm

    return jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS), P(), P(),
                  P()),
        out_specs=(P(DATA_AXIS), _hist_out_spec(merge_mode)),
        check_vma=False))


@functools.lru_cache(maxsize=None)
def _dp_wave_block_step(mesh, w_width: int, num_bins: int,
                        num_features: int, block_rows: int,
                        hist_impl: str, hist_dtype: str, merge_mode: str,
                        wire_dtype: str, merge_chunks: int):
    """One wave block-round: table-lookup routing + W-segment histogram
    partial on each local block, then the per-block-round merge."""
    n_shards = int(mesh.shape[DATA_AXIS])
    blk = _stream_wave_block_fn(w_width, num_bins, num_features,
                                block_rows, hist_impl, hist_dtype)

    def body(bins_b, stats, row_leaf, off, tbl, n_nodes):
        rl2, h = blk(bins_b, stats, row_leaf, off, tbl, n_nodes)
        hm = histogram_merge(h, DATA_AXIS, merge_mode, n_shards,
                             wire_dtype, merge_chunks)
        return rl2, hm

    return jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS), P(), P(),
                  P()),
        out_specs=(P(DATA_AXIS), _hist_out_spec(merge_mode)),
        check_vma=False))


@functools.lru_cache(maxsize=None)
def _dp_strict_update_fn(num_features: int):
    """Replicated strict table update consuming the accumulated merged
    histogram.  Under the reduce-scatter modes the accumulator is
    feature-sharded with zero padding — THIS is the once-per-iteration
    gather: slicing back to F makes jit insert one all-gather, the only
    full-histogram transfer per split iteration."""

    @jax.jit
    def fn(acc, Ptbl, aux, feature_mask, ctx, max_depth, n_nodes,
           n_leaves):
        hist = acc[:, :num_features]
        return stream_strict_update(hist, Ptbl, aux, feature_mask, ctx,
                                    max_depth, n_nodes, n_leaves)

    return fn


@functools.lru_cache(maxsize=None)
def _dp_wave_update_fn(capacity: int, w_width: int, grow_leaves: int,
                       num_features: int, num_bins: int, wave_tail: str):
    """Replicated wave update over the accumulated merged histogram
    (same once-per-wave gather note as :func:`_dp_strict_update_fn`)."""
    _, update, _ = _stream_wave_fns(capacity, w_width, grow_leaves,
                                    num_features, num_bins, wave_tail)

    @jax.jit
    def fn(Ptbl, cache, node_slot, n_nodes, n_leaves, acc, feature_mask,
           ctx, max_depth):
        return update(Ptbl, cache, node_slot, n_nodes, n_leaves,
                      acc[:, :num_features], feature_mask, ctx,
                      max_depth)

    return fn


def _accumulate(acc, h, multi: bool):
    """The serial streamed accumulator contract, verbatim: zero-init +
    ordered adds for multi-block, direct handoff for a single local
    block (0 + h is exact in f32, so the merged values are unchanged)."""
    if acc is None:
        return (jnp.zeros_like(h) + h) if multi else h
    return acc + h


def stream_dp_grow_tree(shards, mesh, stats, feature_mask, ctx,
                        num_leaves: int, num_bins: int, max_depth,
                        wave: WaveSchedule, hist_impl: str, hist_dtype: str,
                        merge_mode: str, wire_dtype: str,
                        merge_chunks: int):
    """Grow one tree streamed across the dp mesh; returns
    ``(tree [replicated], row_leaf [row-sharded])``."""
    args = (shards, mesh, stats, feature_mask, ctx, num_leaves, num_bins,
            max_depth, hist_impl, hist_dtype, merge_mode, wire_dtype,
            merge_chunks)
    if wave.width <= 1:
        return _grow_strict_dp(*args)
    return _grow_wave_dp(*args, wave)


def _dp_root_hist(shards, mesh, stats, num_bins, hist_impl, hist_dtype,
                  merge_mode, wire_dtype, merge_chunks):
    block_rows = shards[0].block_rows
    step = _dp_root_block_step(mesh, num_bins, block_rows, hist_impl,
                               hist_dtype, merge_mode, wire_dtype,
                               merge_chunks)
    multi = shards[0].num_blocks > 1
    acc = None
    for off, bins_g in dp_block_rounds(shards, mesh):
        h = step(bins_g, stats, jnp.int32(off))
        acc = _accumulate(acc, h, multi)
    return acc


def _sharded_zeros_i32(mesh, n: int):
    return jax.device_put(jnp.zeros(n, jnp.int32),
                          NamedSharding(mesh, P(DATA_AXIS)))


def _grow_strict_dp(shards, mesh, stats, feature_mask, ctx, num_leaves,
                    num_bins, max_depth, hist_impl, hist_dtype,
                    merge_mode, wire_dtype, merge_chunks):
    capacity = 2 * num_leaves - 1
    num_features = shards[0].num_features
    block_rows = shards[0].block_rows
    acc = _dp_root_hist(shards, mesh, stats, num_bins, hist_impl,
                        hist_dtype, merge_mode, wire_dtype, merge_chunks)
    Ptbl, aux = stream_strict_init(acc[0, :num_features], ctx,
                                   feature_mask, capacity)
    padded = sum(sh.padded_rows for sh in shards)
    row_leaf = _sharded_zeros_i32(mesh, padded)
    n_nodes = jnp.int32(1)
    n_leaves = jnp.int32(1)
    step = _dp_strict_block_step(mesh, num_bins, block_rows, hist_impl,
                                 hist_dtype, merge_mode, wire_dtype,
                                 merge_chunks)
    upd = _dp_strict_update_fn(num_features)
    multi = shards[0].num_blocks > 1
    for _ in range(num_leaves - 1):
        acc = None
        for off, bins_g in dp_block_rounds(shards, mesh):
            row_leaf, h = step(bins_g, stats, row_leaf, jnp.int32(off),
                               aux, n_nodes)
            acc = _accumulate(acc, h, multi)
        Ptbl, aux, n_nodes, n_leaves = upd(acc, Ptbl, aux, feature_mask,
                                           ctx, max_depth, n_nodes,
                                           n_leaves)
    return _tree_from_packed(Ptbl, n_leaves, None, None), row_leaf


def _grow_wave_dp(shards, mesh, stats, feature_mask, ctx, num_leaves,
                  num_bins, max_depth, hist_impl, hist_dtype, merge_mode,
                  wire_dtype, merge_chunks, wave):
    grow_leaves, w_width = wave_extent(wave, num_leaves)
    exact = wave.tail == "exact"
    capacity = 2 * grow_leaves - 1
    num_features = shards[0].num_features
    block_rows = shards[0].block_rows
    acc = _dp_root_hist(shards, mesh, stats, num_bins, hist_impl,
                        hist_dtype, merge_mode, wire_dtype, merge_chunks)
    Ptbl, cache, node_slot = stream_wave_init(
        acc[0, :num_features], ctx, feature_mask, capacity, grow_leaves)
    padded = sum(sh.padded_rows for sh in shards)
    row_leaf = _sharded_zeros_i32(mesh, padded)
    n_nodes = jnp.int32(1)
    n_leaves = jnp.int32(1)
    plan, _, cond = _stream_wave_fns(capacity, w_width, grow_leaves,
                                     num_features, num_bins, wave.tail)
    upd = _dp_wave_update_fn(capacity, w_width, grow_leaves,
                             num_features, num_bins, wave.tail)
    step = _dp_wave_block_step(mesh, w_width, num_bins, num_features,
                               block_rows, hist_impl, hist_dtype,
                               merge_mode, wire_dtype, merge_chunks)
    multi = shards[0].num_blocks > 1
    # host sync once per wave, same GL002-baselined predicate as the
    # serial streamed driver (the block loop is a host loop)
    while bool(cond(Ptbl, n_leaves, num_leaves)):
        tbl = plan(Ptbl, n_leaves)
        acc = None
        for off, bins_g in dp_block_rounds(shards, mesh):
            row_leaf, h = step(bins_g, stats, row_leaf, jnp.int32(off),
                               tbl, n_nodes)
            acc = _accumulate(acc, h, multi)
        Ptbl, cache, node_slot, n_nodes, n_leaves = upd(
            Ptbl, cache, node_slot, n_nodes, n_leaves, acc, feature_mask,
            ctx, max_depth)
    if exact:
        newP, row_leaf, n_leaves_f = stream_exact_prune(Ptbl, row_leaf,
                                                        num_leaves)
        return _tree_from_packed(newP, n_leaves_f, None, None), row_leaf
    return _tree_from_packed(Ptbl, n_leaves, None, None), row_leaf


# ---------------------------------------------------------------------------
# Boosting-round drivers (wired from models.gbdt.Booster.update)
# ---------------------------------------------------------------------------


def stream_dp_plain_round(shards, mesh, obj_key: tuple, y, w, bag, pred,
                          fmask, hyper, spec: GrowSpec, is_rf: bool,
                          merge_mode: str, wire_dtype: str,
                          merge_chunks: int):
    """One plain gbdt/rf round streamed across the dp mesh — the
    streamed-dp restatement of ``stream_grow.stream_plain_round`` with
    the SAME jitted gradient/update functions (row-sharded residents
    partition elementwise, so per-row arithmetic is unchanged)."""
    _, _, stats = _grad_stats_fn(obj_key)(pred, y, w, bag)
    tree, row_leaf = stream_dp_grow_tree(
        shards, mesh, stats, fmask, hyper.ctx(), spec.num_leaves,
        spec.num_bins, hyper.max_depth, spec.wave, spec.hist_impl,
        spec.hist_dtype, merge_mode, wire_dtype, merge_chunks)
    new_pred = _pred_update_fn(is_rf)(pred, hyper.learning_rate,
                                      row_leaf, tree.leaf_value)
    return tree, new_pred


@functools.lru_cache(maxsize=None)
def _dp_goss_pred_block_step(mesh, block_rows: int):
    """Sharded per-block train-score update for the streamed-dp GOSS
    round: each device traverses its own block and FMA-updates its local
    prediction slice (same contraction as the serial streamed pass)."""
    from ..ops.predict import predict_tree_binned

    def body(pred, bins_b, off, lr, tree):
        nb = bins_b.shape[0]
        delta = predict_tree_binned(tree, bins_b, None)
        p_b = lax.dynamic_slice(pred, (off,), (nb,))
        return lax.dynamic_update_slice(pred, p_b + lr * delta, (off,))

    return jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(), P(), P()),
        out_specs=P(DATA_AXIS),
        check_vma=False))


def stream_dp_goss_round(shards, mesh, obj_key: tuple, y, w, bag, pred,
                         fmask, hyper, key, goss_k_shard,
                         top_rate: float, other_rate: float, seed: int,
                         spec: GrowSpec, merge_mode: str, wire_dtype: str,
                         merge_chunks: int):
    """One GOSS round with PER-SHARD host sampling before transfer —
    the GOSS×wire compounding round.

    Each shard samples its OWN row range on host (exact top-|g| + seeded
    uniform rest, upstream's per-machine data-parallel GOSS) and gathers
    only those rows across PCIe — per-shard ingest bytes shrink by the
    sampling rate, counted on each shard's own odometer.  The compacted
    shards then grow one tree through the unchanged in-memory dp step
    (``parallel.data_parallel.make_dp_grow_step``), whose ring merges
    carry the int8/bf16 wire — so PCIe and ICI bytes shrink in the SAME
    round, multiplicatively.  Like serial streamed GOSS, the sampling
    RNG stream deliberately differs from device GOSS: statistically
    equivalent, tolerance-gated, never bit-claimed.
    """
    from ..parallel.data_parallel import make_dp_grow_step

    k_top_s, k_other_s = goss_k_shard
    k_shard = k_top_s + k_other_s
    g, h, _ = _grad_stats_fn(obj_key)(pred, y, w, bag)
    g_abs = np.asarray(jnp.abs(g))          # host sync: sampling source
    bag_h = np.asarray(bag)                 # host sync: validity mask
    g_h = np.asarray(g)
    h_h = np.asarray(h)
    w_h = np.asarray(w)
    n_shards = len(shards)
    rows_ps = g_abs.shape[0] // n_shards
    amp = np.float32((1.0 - top_rate) / max(other_rate, 1e-12))

    bins_parts, stats_parts = [], []
    idx_parts, wt_parts = [], []
    for s, sh in enumerate(shards):
        lo = s * rows_ps
        valid = bag_h[lo:lo + rows_ps] > 0
        score = np.where(valid, g_abs[lo:lo + rows_ps], -1.0)
        k_top_eff = min(k_top_s, int(valid.sum()))
        if k_top_eff > 0:
            top_idx = np.sort(np.argpartition(-score, k_top_eff - 1)
                              [:k_top_eff].astype(np.int64))
        else:
            top_idx = np.empty(0, np.int64)
        is_top = np.zeros(rows_ps, bool)
        is_top[top_idx] = True
        rest_idx = np.flatnonzero(valid & ~is_top)
        rng = np.random.default_rng((int(seed), s))
        k_other_eff = min(k_other_s, len(rest_idx))
        other_idx = np.sort(rng.choice(rest_idx, size=k_other_eff,
                                       replace=False))

        def pad_fill(idx, k):
            out = np.zeros(k, np.int64)
            out[:len(idx)] = idx
            fill = (np.arange(k) < len(idx)).astype(np.float32)
            return out, fill

        top_idx, top_fill = pad_fill(top_idx, k_top_s)
        other_idx, other_fill = pad_fill(other_idx, k_other_s)
        idx_local = np.concatenate([top_idx, other_idx])
        wt_local = np.concatenate([top_fill, other_fill * amp])

        # GOSS-at-the-source, per shard: only this shard's sampled rows
        # cross ITS PCIe lane (per-shard odometer)
        bins_s = sh.gather_rows(idx_local)
        sh.bytes_streamed += bins_s.nbytes
        bins_parts.append(bins_s)
        idx_g = lo + idx_local
        live = ((bag_h[idx_g] > 0) & (wt_local > 0)).astype(np.float32)
        wt_local = wt_local * live
        stats_parts.append(np.stack(
            [g_h[idx_g] * wt_local, h_h[idx_g] * wt_local, live],
            axis=-1).astype(np.float32))
        idx_parts.append(idx_g)
        wt_parts.append(wt_local)

    bins_g = shard_rows(mesh, jnp.asarray(np.concatenate(bins_parts)))
    stats_g = shard_rows(mesh, jnp.asarray(np.concatenate(stats_parts)))
    grow = make_dp_grow_step(
        mesh, dataclasses.replace(spec, row_chunk=shards[0].block_rows),
        merge_mode, 0, wire_dtype, merge_chunks)
    tree, _ = grow(bins_g, stats_g, fmask, hyper, key, cats=None)

    # train-score update: one full streamed sharded traversal pass
    pred_step = _dp_goss_pred_block_step(mesh, shards[0].block_rows)
    lr = jnp.float32(hyper.learning_rate)
    for off, bins_b in dp_block_rounds(shards, mesh):
        pred = pred_step(pred, bins_b, jnp.int32(off), lr, tree)
    del idx_parts, wt_parts, w_h, k_shard
    return tree, pred
