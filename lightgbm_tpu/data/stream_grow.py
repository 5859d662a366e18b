"""Host drivers for out-of-core (streamed) tree growth — ISSUE 7.

The in-memory growers are single device programs over a resident [n, F]
matrix.  Here the matrix lives in a :class:`~.block_store.BlockStore` and
every histogram pass becomes a host loop over double-buffered prefetched
blocks: per-block jitted kernels (``models.tree._stream_*_block_fn``) do
the row-axis partition + histogram work, their partials are summed with
the in-memory op's exact chunk semantics, and per-iteration jitted
updates run the unchanged split machinery on the accumulated histogram.
On the plain numeric path the resulting trees are BIT-IDENTICAL to
``grow_tree(..., row_chunk=block_rows)`` (tests/test_streaming.py).

Resident O(n) state: ``stats``/``row_leaf``/``pred``/``y``/``w``/``bag``
vectors stay in device memory — the HBM ceiling this subsystem breaks is
the [n, F] code matrix (F bytes/row vs ~24 bytes/row of vector state).

GOSS-at-the-source: under ``boosting=goss`` rows are sampled ON HOST
(top-|g| + uniform rest) and only the sampled subset is gathered and
shipped, so per-round histogram PCIe bytes shrink to ``(top_rate +
other_rate) * n * F`` plus one full streaming pass for train-score
updates.  The host sampler is a deliberately different RNG stream from
the device GOSS path (exact host top-k vs approx_top_mask), so GOSS
under streaming is statistically equivalent but not bit-identical to
in-memory GOSS — documented in README.

Feature screening (r20) composes here for free: on screened rounds the
Booster hands these drivers a
:class:`~.block_store.ColumnViewStore` — the EMA screener acting as a
hot-feature prior over the column axis, exactly dual to GOSS over the
row axis — so every per-block gather, kernel, and odometer count below
sees the compacted ``F_active`` width with no screened branch in this
module.  Both F in the GOSS byte formula above and the per-block
histograms shrink together.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..models.spec import GrowSpec, WaveSchedule
from ..models.tree import (
    _stream_root_block_fn,
    _stream_strict_block_fn,
    _stream_wave_block_fn,
    _stream_wave_fns,
    _tree_from_packed,
    grower_from_spec,
    renew_leaf_values,
    stream_exact_prune,
    stream_strict_init,
    stream_strict_update,
    stream_wave_init,
    wave_extent,
)
from ..ops.lookup import lookup_values
from ..ops.predict import predict_tree_binned


def _root_hist(store, stats, num_bins, hist_impl, hist_dtype):
    """Accumulate the [1, F, B, 3] root histogram over streamed blocks,
    replicating the in-memory chunk-scan's zero-init + ordered adds."""
    blk = _stream_root_block_fn(num_bins, store.block_rows, hist_impl,
                                hist_dtype)
    multi = store.num_blocks > 1
    acc = None
    for off, bins_b in store.device_blocks():
        h = blk(bins_b, stats, jnp.int32(off))
        if acc is None:
            acc = (jnp.zeros_like(h) + h) if multi else h
        else:
            acc = acc + h
    return acc[0]                                        # [F, B, 3]


def stream_grow_tree(store, stats, feature_mask, ctx, num_leaves: int,
                     num_bins: int, max_depth, wave: WaveSchedule,
                     hist_impl: str = "auto", hist_dtype: str = "f32"):
    """Grow one tree from a BlockStore (plain numeric path).

    Mirrors ``grow_tree``'s strict/wave dispatch on ``wave``; returns
    ``(tree, row_leaf)`` like the in-memory grower, with ``row_leaf``
    sized ``store.padded_rows``.
    """
    if wave.width <= 1:
        return _grow_strict(store, stats, feature_mask, ctx, num_leaves,
                            num_bins, max_depth, hist_impl, hist_dtype)
    return _grow_wave(store, stats, feature_mask, ctx, num_leaves,
                      num_bins, max_depth, wave, hist_impl, hist_dtype)


def _grow_strict(store, stats, feature_mask, ctx, num_leaves, num_bins,
                 max_depth, hist_impl, hist_dtype):
    capacity = 2 * num_leaves - 1
    root_hist = _root_hist(store, stats, num_bins, hist_impl, hist_dtype)
    P, aux = stream_strict_init(root_hist, ctx, feature_mask, capacity)
    row_leaf = jnp.zeros(store.padded_rows, jnp.int32)
    n_nodes = jnp.int32(1)
    n_leaves = jnp.int32(1)
    blk = _stream_strict_block_fn(num_bins, store.block_rows, hist_impl,
                                  hist_dtype)
    multi = store.num_blocks > 1
    for _ in range(num_leaves - 1):
        acc = None
        for off, bins_b in store.device_blocks():
            row_leaf, h = blk(bins_b, stats, row_leaf, jnp.int32(off),
                              aux, n_nodes)
            if acc is None:
                acc = (jnp.zeros_like(h) + h) if multi else h
            else:
                acc = acc + h
        P, aux, n_nodes, n_leaves = stream_strict_update(
            acc, P, aux, feature_mask, ctx, max_depth, n_nodes, n_leaves)
    return _tree_from_packed(P, n_leaves, None, None), row_leaf


def _grow_wave(store, stats, feature_mask, ctx, num_leaves, num_bins,
               max_depth, wave, hist_impl, hist_dtype):
    grow_leaves, w_width = wave_extent(wave, num_leaves)
    exact = wave.tail == "exact"
    capacity = 2 * grow_leaves - 1
    num_features = store.num_features
    root_hist = _root_hist(store, stats, num_bins, hist_impl, hist_dtype)
    P, cache, node_slot = stream_wave_init(root_hist, ctx, feature_mask,
                                           capacity, grow_leaves)
    row_leaf = jnp.zeros(store.padded_rows, jnp.int32)
    n_nodes = jnp.int32(1)
    n_leaves = jnp.int32(1)
    plan, update, cond = _stream_wave_fns(capacity, w_width, grow_leaves,
                                          num_features, num_bins,
                                          wave.tail)
    blk = _stream_wave_block_fn(w_width, num_bins, num_features,
                                store.block_rows, hist_impl, hist_dtype)
    multi = store.num_blocks > 1
    # host sync once per wave: the wave count is data-dependent and the
    # block loop is a host loop, so the while predicate must come back to
    # the host (graftlint GL002 — baselined with this justification)
    while bool(cond(P, n_leaves, num_leaves)):
        tbl = plan(P, n_leaves)
        acc = None
        for off, bins_b in store.device_blocks():
            row_leaf, h = blk(bins_b, stats, row_leaf, jnp.int32(off),
                              tbl, n_nodes)
            if acc is None:
                acc = (jnp.zeros_like(h) + h) if multi else h
            else:
                acc = acc + h
        P, cache, node_slot, n_nodes, n_leaves = update(
            P, cache, node_slot, n_nodes, n_leaves, acc, feature_mask,
            ctx, max_depth)
    if exact:
        newP, row_leaf, n_leaves_f = stream_exact_prune(P, row_leaf,
                                                        num_leaves)
        return _tree_from_packed(newP, n_leaves_f, None, None), row_leaf
    return _tree_from_packed(P, n_leaves, None, None), row_leaf


# ---------------------------------------------------------------------------
# Boosting-round drivers (wired from models.gbdt.Booster.update)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _grad_stats_fn(obj_key: tuple):
    """Jitted grad/hess + per-row stat stack, keyed like gbdt's round
    functions so repeated rounds reuse one compile."""
    from ..models.gbdt import _rebuild_objective

    obj = _rebuild_objective(obj_key)

    @jax.jit
    def fn(pred, y, w, bag):
        g, h = obj.grad_hess(pred, y, w)
        stats = jnp.stack([g * bag, h * bag,
                           (bag > 0).astype(jnp.float32)], axis=-1)
        return g, h, stats

    return fn


@functools.lru_cache(maxsize=None)
def _goss_grow_fn(spec: GrowSpec):
    """Jitted in-memory grower over the GOSS-compacted [k, F] matrix."""
    grow = grower_from_spec(spec, fuse_partition=True)

    @jax.jit
    def fn(bins_c, stats, fmask, ctx, max_depth, key):
        return grow(bins_c, stats, fmask, ctx, max_depth, None, key)[:2]

    return fn


@functools.lru_cache(maxsize=None)
def _block_pred_fn():
    @jax.jit
    def fn(tree, bins_b):
        return predict_tree_binned(tree, bins_b, None)

    return fn


@functools.lru_cache(maxsize=None)
def _replay_add_fn():
    """Jitted ``pred + shrink * delta`` used when a loaded forest is
    replayed onto a streamed Dataset (model-file continuation, r15).
    Jitted for the same FMA-contraction reason as
    :func:`_pred_update_fn` — the replayed predictions must be
    bit-identical to the ones the uninterrupted run carried."""

    @jax.jit
    def fn(pred, shrink, delta):
        return pred + shrink * delta

    return fn


@functools.lru_cache(maxsize=None)
def _pred_update_fn(is_rf: bool):
    """Jitted train-score update.  MUST be jitted, not eager: under jit
    XLA:CPU contracts ``pred + shrink * leaf`` into an FMA exactly like
    the in-memory round program does — computed eagerly the mul and add
    round separately and tree k+1 sees 1-ulp-different gradients."""

    @jax.jit
    def fn(pred, lr, row_leaf, leaf_value):
        shrink = jnp.where(is_rf, 1.0, lr)
        return pred + shrink * lookup_values(row_leaf, leaf_value)

    return fn


def stream_plain_round(store, obj_key: tuple, y, w, bag, pred, fmask,
                       hyper, spec: GrowSpec, is_rf: bool,
                       renew_alpha=None, renew_scale=None):
    """One plain gbdt/rf boosting round over a BlockStore — the streamed
    restatement of gbdt's serial ``round_fn``."""
    _, _, stats = _grad_stats_fn(obj_key)(pred, y, w, bag)
    tree, row_leaf = stream_grow_tree(
        store, stats, fmask, hyper.ctx(), spec.num_leaves, spec.num_bins,
        hyper.max_depth, spec.wave, spec.hist_impl, spec.hist_dtype)
    if renew_alpha is not None:
        rw = w * bag if renew_scale is None else w * bag * renew_scale(y)
        tree = renew_leaf_values(tree, row_leaf, y - pred, rw, renew_alpha)
    new_pred = _pred_update_fn(is_rf)(pred, hyper.learning_rate, row_leaf,
                                      tree.leaf_value)
    return tree, new_pred


def stream_goss_round(store, obj_key: tuple, y, w, bag, pred, fmask,
                      hyper, key, goss_k, top_rate: float,
                      other_rate: float, seed: int, spec: GrowSpec,
                      renew_alpha=None, renew_scale=None):
    """One GOSS round with host-side sampling before transfer.

    Selection runs on host copies of |g| and the bag (deliberate host
    syncs — graftlint GL002, baselined): exact top-``k_top`` by |g|, then
    a seeded uniform draw of ``k_other`` from the rest, then ONE host
    gather of just those rows crosses PCIe.  Weighting matches the device
    GOSS path (amplified other-weights, live masking); the selection RNG
    stream intentionally does not.
    """
    k_top, k_other = goss_k
    g, h, _ = _grad_stats_fn(obj_key)(pred, y, w, bag)
    g_abs = np.asarray(jnp.abs(g))          # host sync: sampling source
    bag_h = np.asarray(bag)                 # host sync: validity mask
    valid = bag_h > 0
    score = np.where(valid, g_abs, -1.0)
    k_top_eff = min(k_top, int(valid.sum()))
    if k_top_eff > 0:
        top_idx = np.sort(np.argpartition(-score, k_top_eff - 1)
                          [:k_top_eff].astype(np.int64))
    else:
        top_idx = np.empty(0, np.int64)
    is_top = np.zeros(score.shape[0], bool)
    is_top[top_idx] = True
    rest_idx = np.flatnonzero(valid & ~is_top)
    rng = np.random.default_rng(seed)
    k_other_eff = min(k_other, len(rest_idx))
    other_idx = np.sort(rng.choice(rest_idx, size=k_other_eff,
                                   replace=False))

    def pad_fill(idx, k):
        out = np.zeros(k, np.int64)
        out[:len(idx)] = idx
        fill = (np.arange(k) < len(idx)).astype(np.float32)
        return out, fill

    top_idx, top_fill = pad_fill(top_idx, k_top)
    other_idx, other_fill = pad_fill(other_idx, k_other)
    idx_h = np.concatenate([top_idx, other_idx])
    amp = np.float32((1.0 - top_rate) / max(other_rate, 1e-12))
    wt_h = np.concatenate([top_fill, other_fill * amp])

    # GOSS-at-the-source: only the k sampled rows cross PCIe
    bins_h = store.gather_rows(idx_h)
    store.bytes_streamed += bins_h.nbytes
    bins_c = jax.device_put(bins_h)
    idx = jnp.asarray(idx_h, jnp.int32)
    wt = jnp.asarray(wt_h)
    live = (bag[idx] > 0).astype(jnp.float32) * (wt > 0)
    wt = wt * live
    stats = jnp.stack([g[idx] * wt, h[idx] * wt, live], axis=-1)
    grow = _goss_grow_fn(
        dataclasses.replace(spec, row_chunk=store.block_rows))
    tree, rl_c = grow(bins_c, stats, fmask, hyper.ctx(), hyper.max_depth,
                      key)
    if renew_alpha is not None:
        rw = w[idx] * wt
        if renew_scale is not None:
            rw = rw * renew_scale(y[idx])
        tree = renew_leaf_values(tree, rl_c, y[idx] - pred[idx], rw,
                                 renew_alpha)
    # train-score update: one full streaming pass of traversal per round
    pred_fn = _block_pred_fn()
    deltas = [pred_fn(tree, bins_b) for _, bins_b in store.device_blocks()]
    delta = deltas[0] if len(deltas) == 1 else jnp.concatenate(deltas)
    new_pred = jax.jit(lambda p_, lr, d: p_ + lr * d)(
        pred, hyper.learning_rate, delta)
    return tree, new_pred
