"""Multi-feature-block partition fusion (r7): interpret-mode parity of
routing codes and histograms vs the unfused semantics at F=136-style
shapes — the MSLR class the r5 single-block kernel gated off.

Stats are small integers so the kernel's bf16 operand rounding is exact
and the reference histogram can be computed in plain f32.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.models.spec import WaveSchedule
from lightgbm_tpu.models.tree import grow_tree
from lightgbm_tpu.ops.histogram_pallas import (_vmem_blocking,
                                               hist_partition_fused_pallas,
                                               prepare_wave_operands)
from lightgbm_tpu.ops.split import SplitContext

F, B, W = 136, 256, 4
S = 3


def _wave_case(rng, n, wfeat, wthr=None, wdl=None):
    """Synthetic wave state: rows live in leaves 0..W+1; leaves 0..W-1
    split this wave (wave rank == leaf id), the rest stay put."""
    bins = rng.randint(0, B, size=(n, F)).astype(np.int32)
    g = rng.randint(-4, 5, size=n).astype(np.float32)
    stats = np.stack([g, np.ones(n, np.float32), np.ones(n, np.float32)], -1)
    leaf = rng.randint(0, W + 2, size=n)
    wthr = rng.randint(0, B, size=W) if wthr is None else wthr
    wdl = rng.randint(0, 2, size=W).astype(bool) if wdl is None else wdl
    sel = leaf < W
    lf = np.where(sel, leaf, 0)
    pv = np.stack([
        sel.astype(np.float32),
        np.where(sel, wfeat[lf], 0).astype(np.float32),
        np.where(sel, wthr[lf], 0).astype(np.float32),
        np.where(sel, 2 * leaf, 0).astype(np.float32),
        np.where(sel, wdl[lf], 0).astype(np.float32),
        np.zeros(n, np.float32), np.zeros(n, np.float32),
        np.zeros(n, np.float32)])                       # [8, n]
    return bins, stats, leaf, pv, wthr, wdl


def _reference(bins, stats, leaf, wfeat, wthr, wdl):
    """Unfused-path semantics: XLA-side routing + per-direct-child
    histogram accumulation in f32."""
    n = bins.shape[0]
    sel = leaf < W
    lf = np.where(sel, leaf, 0)
    v = bins[np.arange(n), wfeat[lf]]
    go_left = v <= wthr[lf]
    enc = np.where(sel, 2 * leaf + np.where(go_left, 0, 1) + 1, 0)
    to_direct = sel & (go_left == wdl[lf])
    seg = np.where(to_direct, leaf, W)
    hist = np.zeros((W, F, B, S), np.float32)
    for w in range(W):
        rows = np.flatnonzero(seg == w)
        for f in range(F):
            np.add.at(hist[w, f], (bins[rows, f],), stats[rows])
    # the fused pass hands its histograms over as planes [W, S, F, B]
    return hist.transpose(0, 3, 1, 2), enc


def run_fused(bins, stats, pv, wfeat):
    bins_t, stats_t, chunk = prepare_wave_operands(
        jnp.asarray(bins), jnp.asarray(stats), B, W)
    n_pad = bins_t.shape[1]
    pv_t = jnp.asarray(np.pad(pv, ((0, 0), (0, n_pad - pv.shape[1]))))
    hist, enc = jax.jit(lambda: hist_partition_fused_pallas(
        bins_t, stats_t, pv_t, W, B, chunk, hist_dtype="bf16",
        wfeat=jnp.asarray(wfeat, jnp.int32), num_features=F))()
    return np.asarray(hist), np.asarray(enc)[:bins.shape[0]]


def test_shape_actually_blocks():
    # the whole point: this shape must need >1 VMEM feature block
    f_blk, n_fblk, f_pad, _ = _vmem_blocking(F, B, W * S, chunk_align=512)
    assert n_fblk > 1
    assert f_pad > 0          # padded tail block is exercised


def test_hist_and_routing_parity_multiblock():
    rng = np.random.RandomState(0)
    # one split feature inside each of the feature blocks incl. the
    # padded tail block (f_blk=32: blocks are [0,32), ... [128,136)+pad)
    wfeat = np.array([3, 40, 101, 135])
    bins, stats, leaf, pv, wthr, wdl = _wave_case(rng, n=5000, wfeat=wfeat)
    hist_ref, enc_ref = _reference(bins, stats, leaf, wfeat, wthr, wdl)
    hist, enc = run_fused(bins, stats, pv, wfeat)
    np.testing.assert_array_equal(enc, enc_ref)
    np.testing.assert_array_equal(hist, hist_ref)


def test_split_feature_in_every_block_position():
    # routing keyed on wave rank must find the split value no matter
    # which block owns the feature — first/last column of each block
    rng = np.random.RandomState(1)
    for base in (0, 31, 32, 64, 96, 128):
        wfeat = np.minimum(np.array([base, base + 1, base + 2, base + 3]),
                           F - 1)
        bins, stats, leaf, pv, wthr, wdl = _wave_case(rng, n=3000,
                                                      wfeat=wfeat)
        _, enc_ref = _reference(bins, stats, leaf, wfeat, wthr, wdl)
        _, enc = run_fused(bins, stats, pv, wfeat)
        np.testing.assert_array_equal(enc, enc_ref, err_msg=str(base))


def test_tree_parity_f136():
    """End-to-end: the fused frontier grower engages at F=136 and grows
    the same tree as the unfused path."""
    rng = np.random.RandomState(2)
    n = 4000
    bins = jnp.asarray(rng.randint(0, B, size=(n, F)).astype(np.int32))
    g = jnp.asarray(rng.randn(n).astype(np.float32))
    stats = jnp.stack([g, jnp.ones(n, jnp.float32),
                       jnp.ones(n, jnp.float32)], -1)
    fmask = jnp.ones(F, jnp.float32)
    ctx = SplitContext(jnp.float32(0.0), jnp.float32(1.0), jnp.float32(20.0),
                       jnp.float32(1e-3), jnp.float32(0.0))

    def grow(fp):
        return grow_tree(bins, stats, fmask, ctx, 15, B, -1,
                         wave=WaveSchedule(8, "half"), hist_impl="pallas",
                         hist_dtype="bf16", fuse_partition=fp)

    tu, ru = jax.jit(lambda: grow(False))()
    tf, rf = jax.jit(lambda: grow(True))()
    np.testing.assert_array_equal(np.asarray(tu.split_feature),
                                  np.asarray(tf.split_feature))
    np.testing.assert_array_equal(np.asarray(tu.split_bin),
                                  np.asarray(tf.split_bin))
    np.testing.assert_array_equal(np.asarray(ru), np.asarray(rf))
    np.testing.assert_allclose(np.asarray(tu.leaf_value),
                               np.asarray(tf.leaf_value),
                               rtol=1e-5, atol=1e-6)
