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
from lightgbm_tpu.ops.histogram_pallas import (TURNED_MAX_K, _vmem_blocking,
                                               hist_fused_pallas_batched,
                                               hist_fused_prepared,
                                               hist_partition_fused_pallas,
                                               prepare_wave_operands)
from lightgbm_tpu.ops.split import SplitContext

F, B, W = 136, 256, 4
S = 3


def _wave_case(rng, n, wfeat, wthr=None, wdl=None, f=F):
    """Synthetic wave state of ``len(wfeat)`` splits over ``f`` features:
    rows live in leaves 0..W+1; leaves 0..W-1 split this wave (wave rank
    == leaf id), the rest stay put."""
    w = len(wfeat)
    bins = rng.randint(0, B, size=(n, f)).astype(np.int32)
    g = rng.randint(-4, 5, size=n).astype(np.float32)
    stats = np.stack([g, np.ones(n, np.float32), np.ones(n, np.float32)], -1)
    leaf = rng.randint(0, w + 2, size=n)
    wthr = rng.randint(0, B, size=w) if wthr is None else wthr
    wdl = rng.randint(0, 2, size=w).astype(bool) if wdl is None else wdl
    sel = leaf < w
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
    n, nf = bins.shape
    nw = len(wfeat)
    sel = leaf < nw
    lf = np.where(sel, leaf, 0)
    v = bins[np.arange(n), wfeat[lf]]
    go_left = v <= wthr[lf]
    enc = np.where(sel, 2 * leaf + np.where(go_left, 0, 1) + 1, 0)
    to_direct = sel & (go_left == wdl[lf])
    seg = np.where(to_direct, leaf, nw)
    hist = np.zeros((nw, nf, B, S), np.float32)
    for w in range(nw):
        rows = np.flatnonzero(seg == w)
        for f in range(nf):
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


# -- the last feature block's tail ------------------------------------------
#
# Where the blocks pad the feature axis (F = 129, 136, 152 in blocks of 32:
# tails of 1, 8 and 24 rows) the last block's feature loop runs over the
# table's features only; F = 128 fills four blocks and keeps one loop.

TAIL_FEATURES = [129, 136, 152, 128]
TREE_W, NARROW_W, N_TAIL = 42, 16, 3000


def _tail_case(num_features, w, seed):
    rng = np.random.RandomState(seed)
    # the last feature first, then features of every block
    wfeat = (np.arange(w) * 37 + num_features - 1) % num_features
    return (wfeat,) + _wave_case(rng, N_TAIL, wfeat, f=num_features)


def _tail_blocking(num_features):
    """The tree's feature block: 32 rows, the last block padded to it."""
    f_blk, _, f_pad, _ = _vmem_blocking(num_features, B, 3 * TREE_W,
                                        chunk_align=512)
    assert (f_blk, f_pad) == (32, -num_features % 32)
    return f_blk


@pytest.mark.parametrize("num_features", TAIL_FEATURES)
@pytest.mark.parametrize("width", [TREE_W, NARROW_W],
                         ids=["unturned_w42", "turned_w16"])
def test_partition_pass_parity_across_tails(num_features, width):
    """A wave pass at the tree's width (dot unturned) and a narrow pass
    (turned) on the operands and feature block of the tree's full width,
    as the frontier grower runs them."""
    f_blk = _tail_blocking(num_features)
    wfeat, bins, stats, leaf, pv, wthr, wdl = _tail_case(
        num_features, width, num_features + width)
    hist_ref, enc_ref = _reference(bins, stats, leaf, wfeat, wthr, wdl)
    bins_t, stats_t, chunk = prepare_wave_operands(
        jnp.asarray(bins), jnp.asarray(stats), B, TREE_W)
    pv_t = jnp.asarray(np.pad(pv, ((0, 0), (0, bins_t.shape[1] - N_TAIL))))
    hist, enc = jax.jit(lambda: hist_partition_fused_pallas(
        bins_t, stats_t, pv_t, width, B, chunk, hist_dtype="bf16",
        wfeat=jnp.asarray(wfeat, jnp.int32), num_features=num_features,
        f_blk=f_blk))()
    assert (width <= 16) == (3 * width <= TURNED_MAX_K)
    np.testing.assert_array_equal(np.asarray(enc)[:N_TAIL], enc_ref)
    np.testing.assert_array_equal(np.asarray(hist), hist_ref)


@pytest.mark.parametrize("num_features", TAIL_FEATURES)
def test_root_pass_parity_across_tails(num_features):
    """The root pass (``hist_fused_prepared``) on the wave's operands."""
    f_blk = _tail_blocking(num_features)
    _, bins, stats, *_ = _tail_case(num_features, 1, num_features)
    bins_t, stats_t, chunk = prepare_wave_operands(
        jnp.asarray(bins), jnp.asarray(stats), B, TREE_W)
    n_pad = bins_t.shape[1]
    hist = jax.jit(lambda: hist_fused_prepared(
        bins_t, stats_t, jnp.zeros((1, n_pad), jnp.int32), 1, B, chunk,
        f_blk, num_features, hist_dtype="bf16"))()
    ref = np.zeros((1, num_features, B, S), np.float32)
    for f in range(num_features):
        np.add.at(ref[0, f], (bins[:, f],), stats)
    np.testing.assert_array_equal(np.asarray(hist), ref)


@pytest.mark.parametrize("num_features", TAIL_FEATURES)
def test_batched_parity_across_tails(num_features):
    """``hist_fused_pallas_batched`` at E = 2: the feature block is the
    middle of its three grid axes."""
    e, segments = 2, 4
    rng = np.random.RandomState(num_features)
    bins = rng.randint(0, B, size=(N_TAIL, num_features)).astype(np.int32)
    stats = rng.randint(-4, 5, size=(e, N_TAIL, S)).astype(np.float32)
    seg = rng.randint(-1, segments + 1, size=(e, N_TAIL))
    f_blk, _, f_pad, _ = _vmem_blocking(num_features, B, 3 * segments,
                                        chunk_align=256)
    assert (f_blk, f_pad) == (32, -num_features % 32)
    hist = jax.jit(lambda: hist_fused_pallas_batched(
        jnp.asarray(bins), jnp.asarray(stats), jnp.asarray(seg), segments,
        B, hist_dtype="bf16"))()
    ref = np.zeros((e, segments, num_features, B, S), np.float32)
    for el in range(e):
        for sg in range(segments):
            rows = np.flatnonzero(seg[el] == sg)
            for f in range(num_features):
                np.add.at(ref[el, sg, f], (bins[rows, f],), stats[el, rows])
    np.testing.assert_array_equal(np.asarray(hist), ref)


def _kernel_jaxprs(jaxpr):
    """The kernel jaxpr of every ``pallas_call`` in ``jaxpr``, nested
    calls included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["jaxpr"]
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _kernel_jaxprs(sub)


def _feature_loop_trips(jaxpr):
    """Trip counts of the loops of a kernel jaxpr whose body runs a dot:
    the per-feature one-hot loops (the routing phase's select loop runs
    none)."""
    def has_dot(j):
        return any(e.primitive.name == "dot_general"
                   or any(has_dot(s)
                          for s in jax.core.jaxprs_in_params(e.params))
                   for e in j.eqns)

    trips = []
    for eqn in jaxpr.eqns:
        subs = list(jax.core.jaxprs_in_params(eqn.params))
        if eqn.primitive.name == "scan" and has_dot(subs[0]):
            trips.append(eqn.params["length"])
        else:
            for sub in subs:
                trips += _feature_loop_trips(sub)
    return trips


@pytest.mark.parametrize("num_features,trips", [
    (28, [28]), (128, [32]), (136, [32, 8]), (152, [32, 24])])
@pytest.mark.parametrize("kernel", ["wave", "narrow", "root", "batched"])
def test_feature_loops_follow_the_padding(kernel, num_features, trips):
    """A kernel on a shape whose blocks pad nothing holds ONE feature loop
    over its block, as before; where the last block is padded it holds a
    second loop over the tail alone.  Read from the jaxpr of each kernel
    of the pass (shapes only, nothing runs)."""
    n = 1024
    bins = jax.ShapeDtypeStruct((n, num_features), jnp.uint8)
    stats = jax.ShapeDtypeStruct((n, S), jnp.float32)
    if kernel == "batched":
        fn = (lambda b, s, g: hist_fused_pallas_batched(
            b, s, g, 4, B, hist_dtype="bf16", interpret=True))
        args = (bins, jax.ShapeDtypeStruct((2, n, S), jnp.float32),
                jax.ShapeDtypeStruct((2, n), jnp.int32))
    else:
        f_blk = _vmem_blocking(num_features, B, 3 * TREE_W)[0]

        def fn(b, s, wfeat):
            bins_t, stats_t, chunk = prepare_wave_operands(b, s, B, TREE_W)
            n_pad = bins_t.shape[1]
            if kernel == "root":
                return hist_fused_prepared(
                    bins_t, stats_t, jnp.zeros((1, n_pad), jnp.int32), 1, B,
                    chunk, f_blk, num_features, hist_dtype="bf16",
                    interpret=True)
            return hist_partition_fused_pallas(
                bins_t, stats_t, jnp.zeros((8, n_pad), jnp.float32),
                wfeat.shape[0], B, chunk, interpret=True, hist_dtype="bf16",
                wfeat=wfeat, num_features=num_features, f_blk=f_blk)

        width = NARROW_W if kernel == "narrow" else TREE_W
        args = (bins, stats, jax.ShapeDtypeStruct((width,), jnp.int32))
    kernels = list(_kernel_jaxprs(jax.make_jaxpr(fn)(*args).jaxpr))
    assert kernels
    for kj in kernels:
        assert _feature_loop_trips(kj) == trips
