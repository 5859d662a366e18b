"""The narrow phase (PR 32): a tree's doubling passes run at a narrow
static width through the partition-fused kernel with its dot turned.

Kernel: the turned pass (``operand [K, chunk] x onehot [B, chunk]^T``,
accumulator ``[F_blk, K, B]``) equals the unturned one on operands prepared
at the TREE's width, single and multi feature block, bfloat16 and hi/lo
float32.  Grower: a tree grown with ``narrow_width`` 16 is the tree grown
with 0, and the narrow loop runs the doubling passes and no more.

Since PR 35 the exact tail chooses the width before every pass: narrow
while the leaves its replay still needs (``tree._replay_needed``) fit the
narrow width, which contains the doubling passes and adds the
certification passes of a tree that holds its splits already.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.models import tree as tree_mod
from lightgbm_tpu.models.spec import (WaveSchedule, narrow_width_for,
                                      resolve_wave)
from lightgbm_tpu.models.tree import HIST_NARROW, HIST_WAVE, grow_tree
from lightgbm_tpu.ops import histogram_pallas
from lightgbm_tpu.ops.histogram_pallas import (TURNED_MAX_K, _vmem_blocking,
                                               hist_partition_fused_pallas,
                                               prepare_wave_operands)
from lightgbm_tpu.ops.split import SplitContext

B, S, TREE_W = 255, 3, 42
# 136 features need five VMEM blocks of 32 at the tree's width (the last
# one padded by 24 rows); 12 fit one
BLOCKS = {"single_block": 12, "multi_block": 136}


def _wave_case(rng, n, f, w):
    """Rows in leaves 0..w+1; leaves 0..w-1 split this wave (wave rank ==
    leaf id), the other two stay put.  Integer statistics for bfloat16
    (exact in it), real-valued ones for the hi/lo split."""
    bins = rng.integers(0, B, (n, f)).astype(np.int32)
    leaf = rng.integers(0, w + 2, n)
    wfeat = rng.integers(0, f, w)
    wfeat[-1] = f - 1                 # the last (padded) block routes too
    wthr = rng.integers(0, B, w)
    wdl = rng.integers(0, 2, w).astype(bool)
    sel = leaf < w
    lf = np.where(sel, leaf, 0)
    z = np.zeros(n, np.float32)
    pv = np.stack([sel.astype(np.float32),
                   np.where(sel, wfeat[lf], 0).astype(np.float32),
                   np.where(sel, wthr[lf], 0).astype(np.float32),
                   np.where(sel, 2 * leaf, 0).astype(np.float32),
                   np.where(sel, wdl[lf], 0).astype(np.float32), z, z, z])
    v = bins[np.arange(n), wfeat[lf]]
    go_left = v <= wthr[lf]
    enc = np.where(sel, 2 * leaf + np.where(go_left, 0, 1) + 1, 0)
    direct = sel & (go_left == wdl[lf])
    return bins, leaf, pv, wfeat, enc, direct


@pytest.mark.parametrize("hist_dtype", ["bf16", "f32"])
@pytest.mark.parametrize("blocks", sorted(BLOCKS))
@pytest.mark.parametrize("w", [1, 2, 4, 16])
def test_turned_pass_equals_unturned(w, blocks, hist_dtype):
    f = BLOCKS[blocks]
    rng = np.random.default_rng(100 * w + f)
    n = 2500
    bins, leaf, pv, wfeat, enc_ref, direct = _wave_case(rng, n, f, w)
    g = (rng.integers(-4, 5, n) if hist_dtype == "bf16"
         else rng.normal(0, 1, n)).astype(np.float32)
    h = (np.ones(n) if hist_dtype == "bf16"
         else rng.uniform(0.05, 0.25, n)).astype(np.float32)
    stats = np.stack([g, h, np.ones(n, np.float32)], -1)
    # operands prepared ONCE, at the tree's width: the narrow pass reads
    # the full-width pass's blocks and chunk
    bins_t, stats_t, chunk = prepare_wave_operands(
        jnp.asarray(bins), jnp.asarray(stats), B, TREE_W)
    f_blk, n_fblk, f_pad, _ = _vmem_blocking(f, B, S * TREE_W)
    assert (n_fblk > 1) == (blocks == "multi_block")
    assert (f_pad > 0) == (blocks == "multi_block")
    n_pad = bins_t.shape[1]
    pv_t = jnp.asarray(np.pad(pv, ((0, 0), (0, n_pad - n))))

    def one(turned):
        return jax.jit(lambda: hist_partition_fused_pallas(
            bins_t, stats_t, pv_t, w, B, chunk, hist_dtype=hist_dtype,
            wfeat=jnp.asarray(wfeat, jnp.int32), num_features=f,
            f_blk=f_blk, bins_minor=turned))()

    (wide, enc_w), (turned, enc_t) = one(False), one(True)
    # the same planes, padded feature rows trimmed; the same routing
    assert wide.shape == turned.shape == (w, S, f, B)
    np.testing.assert_array_equal(np.asarray(enc_t)[:n], enc_ref)
    np.testing.assert_array_equal(np.asarray(enc_t), np.asarray(enc_w))
    assert not np.asarray(enc_t)[n:].any()
    if hist_dtype == "bf16":
        np.testing.assert_array_equal(np.asarray(turned), np.asarray(wide))
    else:       # float32 summation order inside one contraction
        np.testing.assert_allclose(np.asarray(turned), np.asarray(wide),
                                   rtol=1e-5, atol=1e-5)
    # every selected row that went to its split's direct child is counted
    # once in its leaf's segment; a row in no selected leaf adds nothing
    counts = np.asarray(turned)[:, 2].sum(axis=2)             # [w, f]
    want = np.bincount(leaf[direct], minlength=w)[:w]
    np.testing.assert_allclose(counts, np.repeat(want[:, None], f, 1),
                               atol=1e-3)
    assert direct.sum() < (leaf < w).sum() <= n


def test_orientation_follows_the_width(monkeypatch):
    """Not told an orientation, the pass turns its dot up to
    ``TURNED_MAX_K`` statistics columns, and the schedule's narrow width is
    the largest power of two of segments inside that."""
    seen = []
    real = histogram_pallas._accumulate_wave

    def spy(*args, bins_minor, **kwargs):
        seen.append(bins_minor)
        return real(*args, bins_minor=bins_minor, **kwargs)

    monkeypatch.setattr(histogram_pallas, "_accumulate_wave", spy)
    rng = np.random.default_rng(7)
    nw = TURNED_MAX_K // S
    for w in (nw, nw + 1):
        bins, _, pv, wfeat, _, _ = _wave_case(rng, 600, 12, w)
        stats = np.ones((600, 3), np.float32)
        bins_t, stats_t, chunk = prepare_wave_operands(
            jnp.asarray(bins), jnp.asarray(stats), B, w)
        pv_t = jnp.asarray(np.pad(pv, ((0, 0), (0, bins_t.shape[1] - 600))))
        jax.jit(lambda: hist_partition_fused_pallas(
            bins_t, stats_t, pv_t, w, B, chunk))()
    assert seen == [True, False]
    assert 3 * narrow_width_for(TREE_W) <= TURNED_MAX_K
    assert 3 * 2 * narrow_width_for(TREE_W) > TURNED_MAX_K


@pytest.mark.parametrize("narrow,width,ok", [
    (0, 42, True), (16, 42, True), (32, 42, True), (1, 2, True),
    (16, 16, False), (64, 42, False), (12, 42, False), (-4, 42, False)])
def test_schedule_refuses_a_bad_narrow_width(narrow, width, ok):
    if ok:
        assert WaveSchedule(width, "greedy",
                            narrow_width=narrow).narrow_width == narrow
        return
    with pytest.raises(ValueError, match="narrow_width"):
        WaveSchedule(width, "greedy", narrow_width=narrow)


@pytest.mark.parametrize("width,want", [(42, 16), (64, 16), (17, 16),
                                        (16, 0), (8, 0)])
def test_narrow_width_is_resolved_with_the_width(width, want):
    from lightgbm_tpu.config import parse_params

    assert narrow_width_for(42) == 16
    p = parse_params({"objective": "binary", "num_leaves": 255,
                      "wave_width": width, "verbosity": -1})
    wave = resolve_wave(p, 1 << 20)
    assert (wave.width, wave.narrow_width) == (width, want)
    assert wave.tail == "exact" and wave.cap_leaves > 255


def _ctx():
    z = jnp.float32
    return SplitContext(lambda_l1=z(0.0), lambda_l2=z(1.0),
                        min_data_in_leaf=z(2.0), min_sum_hessian=z(1e-3),
                        min_gain_to_split=z(0.0))


def _table(n=4096, f=12, seed=5):
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, B, (n, f)).astype(np.uint8)
    y = (0.02 * bins[:, 0] - 0.01 * (bins[:, 1] > 100) * bins[:, 2]
         + np.sin(bins[:, 3] * 0.05) + 0.3 * rng.normal(0, 1, n))
    g = (y - y.mean()).astype(np.float32)
    h = rng.uniform(0.5, 1.0, n).astype(np.float32)
    stats = jnp.stack([jnp.asarray(-g), jnp.asarray(h),
                       jnp.ones(n, jnp.float32)], axis=-1)
    return jnp.asarray(bins), stats


def _grow(bins, stats, num_leaves, narrow, hist_dtype, cap=None):
    """One tree through the partition-fused path, exact tail (``cap``:
    the overgrowth's, the schedule's own 2x unless given)."""
    from lightgbm_tpu.models.spec import _exact_overgrow_target

    wave = WaveSchedule(TREE_W, "exact",
                        cap or _exact_overgrow_target(num_leaves, TREE_W,
                                                      2.0),
                        narrow_width=narrow)
    fmask = jnp.ones(bins.shape[1], jnp.float32)
    return jax.jit(lambda: grow_tree(
        bins, stats, fmask, _ctx(), num_leaves, B, -1, wave=wave,
        hist_dtype=hist_dtype, hist_impl="pallas", fuse_partition=True))()


def _assert_same_tree(a, b):
    (ta, ra), (tb, rb) = a, b
    assert int(ta.num_leaves) == int(tb.num_leaves)
    for field in ("split_feature", "split_bin", "left", "right", "is_leaf",
                  "count"):
        np.testing.assert_array_equal(np.asarray(getattr(ta, field)),
                                      np.asarray(getattr(tb, field)), field)
    np.testing.assert_array_equal(np.asarray(ra), np.asarray(rb))
    np.testing.assert_allclose(np.asarray(ta.leaf_value),
                               np.asarray(tb.leaf_value), rtol=0, atol=1e-6)


@pytest.mark.parametrize("hist_dtype", ["bf16", "f32"])
@pytest.mark.parametrize("num_leaves", [63, 255])
def test_narrow_phase_grows_the_same_tree(num_leaves, hist_dtype,
                                          monkeypatch):
    bins, stats = _table()
    calls = []          # (role, width) of every kernel call a tree traces
    real = histogram_pallas.hist_partition_fused_pallas

    def spy(bins_t, stats_t, pv_t, num_segments, *args, **kwargs):
        calls.append((kwargs["name"], num_segments))
        return real(bins_t, stats_t, pv_t, num_segments, *args, **kwargs)

    monkeypatch.setattr(histogram_pallas, "hist_partition_fused_pallas", spy)
    narrow = _grow(bins, stats, num_leaves, 16, hist_dtype)
    # what the program HOLDS: one narrow and one full-width loop body (a
    # loop's body is traced once, however its passes alternate at run time:
    # test_needed_width_passes_grow_the_same_tree reads that order)
    assert calls == [(HIST_NARROW, 16), (HIST_WAVE, TREE_W)]
    del calls[:]
    wide = _grow(bins, stats, num_leaves, 0, hist_dtype)
    assert calls == [(HIST_WAVE, TREE_W)]
    assert int(narrow[0].num_leaves) == num_leaves
    _assert_same_tree(narrow, wide)


# ---------------------------------------------------------------------------
# PR 35: the width of an exact-tail pass follows the leaves the replay needs
# ---------------------------------------------------------------------------


def _chain_table(n=4096, f=12, seed=13):
    """Heavy-tailed targets (Pareto, shape 2): best-first growth follows a
    few outliers down a deep chain, so a tree that holds its splits still
    runs many certification passes, each needing a handful of leaves (the
    shape of a boosted Higgs tree from round 7 on: PERF.md, PR 35)."""
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, B, (n, f)).astype(np.uint8)
    y = rng.pareto(2.0, n) * (1 + bins[:, 0] / 64.0) * (1 + bins[:, 1] / 128.0)
    g = (y - y.mean()).astype(np.float32)
    h = rng.uniform(0.5, 1.0, n).astype(np.float32)
    stats = jnp.stack([jnp.asarray(-g), jnp.asarray(h),
                       jnp.ones(n, jnp.float32)], axis=-1)
    return jnp.asarray(bins), stats


# name -> (table, the overgrowth cap by num_leaves; None = the schedule's).
# The chain's caps are far enough out that its replay is certified.
_TABLES = {
    "plain": (_table, None),
    "plain_other_rows": (lambda: _table(n=6000, f=8, seed=11), None),
    "deep_chain": (_chain_table, {63: 610, 255: 1030}),
}


@pytest.fixture
def passes(monkeypatch):
    """``(role, width)`` of every partition-fused pass a tree RUNS, in
    order (a callback in the traced call; the trace itself holds each
    loop body once)."""
    ran = []
    real = histogram_pallas.hist_partition_fused_pallas

    def spy(bins_t, stats_t, pv_t, num_segments, *args, **kwargs):
        role = kwargs["name"]
        jax.debug.callback(lambda _: ran.append((role, num_segments)),
                           pv_t[0, 0])
        return real(bins_t, stats_t, pv_t, num_segments, *args, **kwargs)

    monkeypatch.setattr(histogram_pallas, "hist_partition_fused_pallas", spy)

    def take():
        jax.effects_barrier()
        out = list(ran)
        del ran[:]
        return out

    return take


def _runs(ran):
    """Consecutive passes of one (role, width) merged: [(role, width, n)]."""
    out = []
    for call in ran:
        if out and out[-1][:2] == call:
            out[-1] = call + (out[-1][2] + 1,)
        else:
            out.append(call + (1,))
    return out


def _certified_spy(monkeypatch, num_leaves):
    """Whether the table handed to the replay was certified."""
    seen = []
    prune = tree_mod._exact_prune

    def spy(P, *args):
        jax.debug.callback(lambda c: seen.append(bool(c)),
                           tree_mod._replay_certified(P, num_leaves))
        return prune(P, *args)

    monkeypatch.setattr(tree_mod, "_exact_prune", spy)
    return seen


def _assert_strict_tree(got, bins, stats, num_leaves, hist_dtype):
    """The strict (one split a pass) grower's tree over the same kernels'
    histograms: the same partition of the rows into as many leaves, the
    same value on every row.  (Not split for split: in a leaf of a few
    rows two thresholds with no row between them tie exactly, and which
    one wins is the summation order of the two growers' histograms.)"""
    from lightgbm_tpu.models.spec import STRICT
    from lightgbm_tpu.ops.lookup import lookup_values

    def partition(row_leaf):
        _, first, inverse = np.unique(np.asarray(row_leaf),
                                      return_index=True, return_inverse=True)
        return first[inverse]

    fmask = jnp.ones(bins.shape[1], jnp.float32)
    t_s, rl_s = jax.jit(lambda: grow_tree(
        bins, stats, fmask, _ctx(), num_leaves, B, -1, wave=STRICT,
        hist_dtype=hist_dtype, hist_impl="pallas"))()
    t, rl = got
    assert int(t_s.num_leaves) == int(t.num_leaves)
    np.testing.assert_array_equal(partition(rl_s), partition(rl))
    np.testing.assert_allclose(
        np.asarray(lookup_values(rl_s, t_s.leaf_value)),
        np.asarray(lookup_values(rl, t.leaf_value)), rtol=2e-4, atol=2e-6)


@pytest.mark.parametrize("hist_dtype", ["bf16", "f32"])
@pytest.mark.parametrize("num_leaves", [63, 255])
@pytest.mark.parametrize("table", sorted(_TABLES))
def test_needed_width_passes_grow_the_same_tree(table, num_leaves,
                                                hist_dtype, passes,
                                                monkeypatch):
    """The tree of the full-width-only schedule, array for array, in as
    many passes; where the replay was certified, the strict grower's.  On
    the chain the passes run narrow, wide, narrow: the doubling passes, the
    growth passes (every candidate is needed) and first certification
    passes, then at least three certification passes of few needed
    leaves."""
    make, caps = _TABLES[table]
    bins, stats = make()
    cap = caps and caps[num_leaves]
    certified = _certified_spy(monkeypatch, num_leaves)
    narrow = _grow(bins, stats, num_leaves, 16, hist_dtype, cap)
    ran = passes()
    wide = _grow(bins, stats, num_leaves, 0, hist_dtype, cap)
    ran_wide = passes()
    assert set(ran_wide) == {(HIST_WAVE, TREE_W)}
    assert len(ran) == len(ran_wide)
    assert int(narrow[0].num_leaves) == num_leaves
    _assert_same_tree(narrow, wide)
    assert certified[0] == certified[1]
    roles = [r[:2] for r in _runs(ran)]
    assert roles[:2] == [(HIST_NARROW, 16), (HIST_WAVE, TREE_W)]
    if table == "deep_chain":
        assert certified[0]
        assert roles == [(HIST_NARROW, 16), (HIST_WAVE, TREE_W),
                         (HIST_NARROW, 16)]
        assert _runs(ran)[-1][2] >= 3
    if certified[0]:
        _assert_strict_tree(narrow, bins, stats, num_leaves, hist_dtype)


def test_needed_rises_past_the_narrow_width_mid_tail(passes, monkeypatch):
    """``needed`` is not monotone: the chain at 63 leaves needs 27, 13, 6,
    9, 9, 3, 2, 1 leaves in its certification passes, so at narrow width 8
    the full-width body takes over again mid-tail, and the tree is still
    the full-width schedule's and the strict grower's."""
    bins, stats = _chain_table()
    certified = _certified_spy(monkeypatch, 63)
    wave = WaveSchedule(TREE_W, "exact", 610, narrow_width=8)
    fmask = jnp.ones(bins.shape[1], jnp.float32)
    got = jax.jit(lambda: grow_tree(
        bins, stats, fmask, _ctx(), 63, B, -1, wave=wave, hist_dtype="bf16",
        hist_impl="pallas", fuse_partition=True))()
    ran = _runs(passes())
    assert [r[:2] for r in ran] == [
        (HIST_NARROW, 8), (HIST_WAVE, TREE_W), (HIST_NARROW, 8),
        (HIST_WAVE, TREE_W), (HIST_NARROW, 8)]
    # 1, 2, 4, 8 leaves; 16, 32 and the needed 27, 13; 6; 9, 9; 3, 2, 1
    assert [r[2] for r in ran] == [4, 5, 1, 2, 3]
    _assert_same_tree(got, _grow(bins, stats, 63, 0, "bf16", 610))
    assert certified == [True, True]
    _assert_strict_tree(got, bins, stats, 63, "bf16")


@pytest.mark.parametrize("table", ["full_waves", "deep_chain"])
def test_a_tree_never_certified_stops_at_the_full_width_pass_count(
        table, passes, monkeypatch):
    """The cap bounds the passes as it bounded them: narrow passes that add
    16 leaves do not buy an uncertified tree more passes than the
    full-width schedule's 42 a pass reach the cap in, because the cap is
    held against the leaves THAT schedule would have.  Exact where every
    pass is full (16,384 rows, the certificate off: the doubling passes
    and ``ceil((526 - 32) / 42)`` = 12 more); where passes run short of
    candidates (the chain at the schedule's own cap, never certified) the
    schedule's count follows this tree's candidates, not the other's: to
    within a pass."""
    if table == "full_waves":
        bins, stats = _table(n=16384, f=8, seed=8)
        monkeypatch.setattr(tree_mod, "_replay_certified",
                            lambda P, num_leaves: jnp.bool_(False))
    else:
        bins, stats = _chain_table()
    narrow = _grow(bins, stats, 255, 16, "bf16")
    ran = passes()
    wide = _grow(bins, stats, 255, 0, "bf16")
    ran_wide = passes()
    if table == "full_waves":
        assert len(ran) == len(ran_wide) == 5 + 12
    else:
        assert abs(len(ran) - len(ran_wide)) <= 1
    assert (HIST_NARROW, 16) in ran[6:]
    _assert_same_tree(narrow, wide)


@pytest.mark.parametrize("narrow_width", [4, 16, 32])
def test_narrow_loop_runs_the_doubling_passes(narrow_width, monkeypatch):
    """On a tree whose every leaf can split, the narrow loop runs
    ``ceil(log2(narrow_width)) + 1`` passes (1, 2, 4 .. narrow_width
    leaves) and hands over with twice ``narrow_width`` leaves."""
    bins, stats = _table(n=8192, seed=8)    # doubles up to 64 leaves
    loops = []
    real = jax.lax.while_loop

    def counting(cond, body, init):
        def counted(carry):
            st, k = carry
            return body(st), k + 1
        st, k = real(lambda c: cond(c[0]), counted, (init, jnp.int32(0)))
        loops.append((k, st.n_leaves))
        return st

    monkeypatch.setattr(tree_mod.lax, "while_loop", counting)
    fmask = jnp.ones(bins.shape[1], jnp.float32)
    wave = WaveSchedule(TREE_W, "greedy", narrow_width=narrow_width)

    def grow():
        loops.clear()
        tree_mod.grow_tree_frontier(
            bins, stats, fmask, _ctx(), 255, B, -1, wave=wave,
            hist_impl="pallas", hist_dtype="bf16", fuse_partition=True)
        return [x for pair in loops for x in pair]

    n_passes, n_leaves, wide_passes, leaves = jax.jit(grow)()
    assert int(n_passes) == int(np.ceil(np.log2(narrow_width))) + 1
    assert int(n_leaves) == 2 * narrow_width
    assert int(leaves) == 255 and int(wide_passes) >= 1


@pytest.mark.parametrize("off", ["unfused", "xla_histograms", "int8",
                                 "cpu_default"])
def test_off_the_fused_path_the_field_is_ignored(off):
    """Without the partition-fused kernel the grower has its one loop and
    traces the program it traces with no narrow width."""
    bins, stats = _table(n=1024, f=6)
    opts = dict(hist_impl="pallas", fuse_partition=True, hist_dtype="bf16")
    opts.update({"unfused": dict(fuse_partition=False),
                 "xla_histograms": dict(hist_impl="jnp"),
                 "int8": dict(hist_dtype="int8"),
                 "cpu_default": dict(hist_impl="auto")}[off])
    fmask = jnp.ones(bins.shape[1], jnp.float32)

    def traced(narrow):
        wave = WaveSchedule(TREE_W, "greedy", narrow_width=narrow)
        return str(jax.make_jaxpr(lambda b, s: grow_tree(
            b, s, fmask, _ctx(), 63, B, -1, wave=wave, **opts))(bins, stats))

    with_field = traced(16)
    assert HIST_NARROW not in with_field
    assert with_field == traced(0)
    opts.update(hist_impl="pallas", fuse_partition=True, hist_dtype="bf16")
    assert HIST_NARROW in traced(16)
