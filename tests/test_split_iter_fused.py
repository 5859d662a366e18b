"""Split-iteration mega-kernel parity (r7 tentpole).

The fused strict grower replaces the XLA ``find_best_split`` + packed
node-table update with one Pallas call per split iteration
(``split_iter_pallas``).  These tests pin the kernel to the XLA
semantics:

* kernel-level: identical histogram + table inputs -> bitwise-identical
  new packed table and next-leaf pick vs an XLA reference built from
  ``find_best_split`` (regression fixture);
* tree-level: ``fuse_split=True`` vs ``False`` trees are bitwise equal
  on structure, thresholds, leaf values, counts and row routing —
  unbatched, under the multiclass class-vmap, and under the
  hyperparameter-batched E-sweep.  The stored ``split_gain`` diagnostic
  alone is compared to ~2 ulp: the two programs compile ``hist_fn`` in
  different fusion contexts (the fused path feeds a transpose into the
  Pallas operand) and XLA:CPU's accumulation order is not bitwise
  stable across contexts.  Given identical histogram bits the kernel
  matches exactly (first test);
* categorical fixtures gate the fusion off and must stay on the byte-
  identical XLA path.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.models.tree import _PK, _packed_root_table, grow_tree
from lightgbm_tpu.ops.histogram_pallas import split_iter_pallas
from lightgbm_tpu.ops.split import (CatInfo, SplitContext,
                                    constrained_leaf_output, find_best_split)


def make_ctx(l1=0.1, l2=1.0, min_data=3.0, min_hess=1e-3, min_gain=0.0,
             mds=0.5, ps=1.5):
    return SplitContext(
        lambda_l1=jnp.float32(l1), lambda_l2=jnp.float32(l2),
        min_data_in_leaf=jnp.float32(min_data),
        min_sum_hessian=jnp.float32(min_hess),
        min_gain_to_split=jnp.float32(min_gain),
        max_delta_step=jnp.float32(mds), path_smooth=jnp.float32(ps))


def reg_fixture(seed=3, n=300, num_features=7, num_bins=16):
    rng = np.random.RandomState(seed)
    bins = jnp.asarray(rng.randint(0, num_bins, size=(n, num_features)),
                       jnp.int32)
    g = jnp.asarray(rng.randn(n).astype(np.float32))
    stats = jnp.stack([g, jnp.ones(n, jnp.float32),
                       jnp.ones(n, jnp.float32)], -1)
    return bins, stats, jnp.ones(num_features, jnp.float32)


def assert_trees_equal(t1, t0, r1, r0, gain_ulp=False):
    for f in t1._fields:
        a, b = getattr(t1, f), getattr(t0, f)
        if a is None:
            assert b is None
            continue
        a, b = np.asarray(a), np.asarray(b)
        if gain_ulp and f == "split_gain":
            np.testing.assert_allclose(a, b, rtol=5e-7, atol=0.0,
                                       err_msg=f)
        else:
            np.testing.assert_array_equal(a, b, err_msg=f)
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r0))


def _xla_split_iter_ref(P, hist2, ctx, fmask, max_depth, n_nodes, capacity):
    """XLA reference for one split iteration: pick the best expandable
    leaf, score both children with ``find_best_split`` and apply the
    one-row-gather / three-row-scatter table update — same code shape as
    the pre-r7 strict grower body."""
    K = _PK
    neg_inf = jnp.float32(-jnp.inf)
    gains = jnp.where(P[:, K.IS_LEAF] > 0.5, P[:, K.CAND_GAIN], neg_inf)
    leaf = jnp.argmax(gains).astype(jnp.int32)
    active = jnp.isfinite(gains[leaf])
    nl, nr = n_nodes, n_nodes + 1
    row = P[leaf]
    feat = row[K.CAND_FEAT]
    thr = row[K.CAND_BIN]
    gain = row[K.CAND_GAIN]
    wl_v, wr_v = row[K.CAND_WL], row[K.CAND_WR]
    lo, hi = row[K.BOUND_LO], row[K.BOUND_HI]
    child_depth = row[K.DEPTH] + 1.0
    depth_ok = (max_depth <= 0) | (child_depth < max_depth.astype(jnp.float32))

    def score(h, lo_, hi_, po):
        return find_best_split(h, ctx, fmask, depth_ok, None, None,
                               lo_, hi_, po)

    bs = jax.vmap(score)(hist2, jnp.stack([lo, lo]), jnp.stack([hi, hi]),
                         jnp.stack([wl_v, wr_v]))
    leaf_row = row.at[jnp.array([K.SPLIT_FEAT, K.SPLIT_BIN, K.LEFT, K.RIGHT,
                                 K.IS_LEAF, K.SPLIT_GAIN])].set(
        jnp.stack([feat, thr, nl.astype(jnp.float32),
                   nr.astype(jnp.float32), jnp.float32(0.0), gain]))
    two = lambda a, b: jnp.stack([a, b])
    child_rows = jnp.stack([
        jnp.full((2,), -1.0), jnp.zeros((2,)), jnp.full((2,), -1.0),
        jnp.full((2,), -1.0), two(wl_v, wr_v), jnp.ones((2,)),
        two(row[K.CAND_LC], row[K.CAND_RC]), jnp.zeros((2,)),
        jnp.full((2,), child_depth), bs.gain, bs.feature.astype(jnp.float32),
        bs.bin.astype(jnp.float32), bs.left_g, bs.left_h, bs.left_c,
        bs.right_g, bs.right_h, bs.right_c, bs.left_out, bs.right_out,
        two(lo, lo), two(hi, hi), jnp.zeros((2,)),
        jnp.minimum(row[K.PM], bs.gain)], axis=-1)
    oob = jnp.int32(capacity)
    P = P.at[jnp.where(active, leaf, oob)].set(leaf_row, mode="drop")
    P = P.at[jnp.where(active, jnp.stack([nl, nr]), oob)].set(
        child_rows, mode="drop")
    return P


def _root_iteration(hist2, num_leaves, ctx, fmask):
    """Operands of the FIRST split iteration for children histograms
    ``hist2`` [2, F, B, 3]: (packed root table, aux pick, scalars)."""
    cap = 2 * num_leaves - 1
    root_hist = hist2[0] + hist2[1]
    root_tot = jnp.sum(root_hist.sum(0), axis=0)
    root_out = constrained_leaf_output(
        root_tot[0], root_tot[1], root_tot[2],
        ctx._replace(path_smooth=jnp.float32(0.0)),
        jnp.float32(-jnp.inf), jnp.float32(jnp.inf), jnp.float32(0.0))
    root_best = find_best_split(root_hist, ctx, fmask, jnp.bool_(True), None,
                                parent_out=root_out)
    tab = _packed_root_table(cap, root_out, root_tot, root_best, None)
    aux = jnp.stack([jnp.float32(0), root_best.feature.astype(jnp.float32),
                     root_best.bin.astype(jnp.float32),
                     jnp.isfinite(root_best.gain).astype(jnp.float32),
                     jnp.float32(0), jnp.float32(0), jnp.float32(0),
                     jnp.float32(0)]).reshape(1, 8)
    scal = jnp.concatenate([jnp.stack([
        ctx.lambda_l1, ctx.lambda_l2, ctx.min_data_in_leaf,
        ctx.min_sum_hessian, ctx.min_gain_to_split, ctx.max_delta_step,
        ctx.path_smooth, jnp.float32(0), jnp.float32(1)]),
        jnp.zeros(7)]).reshape(1, 16)
    return tab, aux, scal


def test_kernel_bitmatches_xla_one_iteration():
    rng = np.random.RandomState(7)
    F, B, num_leaves = 9, 32, 15
    cap = 2 * num_leaves - 1
    ctx = make_ctx()
    fmask = jnp.ones(F, jnp.float32)
    hist2 = jnp.asarray((rng.randn(2, F, B, 3).astype(np.float32)) ** 2)
    tab, aux, scal = _root_iteration(hist2, num_leaves, ctx, fmask)
    md = jnp.int32(0)
    n_nodes = jnp.int32(1)

    def both():
        Pk, auxk = split_iter_pallas(hist2.transpose(0, 1, 3, 2), tab,
                                     fmask.reshape(1, F), aux, scal, pk=_PK)
        Px = _xla_split_iter_ref(tab, hist2, ctx, fmask, md, n_nodes, cap)
        return Pk, Px, auxk

    Pk, Px, auxk = jax.jit(both)()
    np.testing.assert_array_equal(np.asarray(Pk), np.asarray(Px))
    # next-pick aux mirrors the XLA leaf selection on the updated table
    K = _PK
    Px_np = np.asarray(Px)
    gains = np.where(Px_np[:, K.IS_LEAF] > 0.5, Px_np[:, K.CAND_GAIN],
                     -np.inf)
    leaf_n = int(np.argmax(gains))
    a = np.asarray(auxk)[0]
    assert int(a[0]) == leaf_n
    assert a[1] == Px_np[leaf_n, K.CAND_FEAT]
    assert a[2] == Px_np[leaf_n, K.CAND_BIN]
    assert bool(a[3]) == bool(np.isfinite(gains[leaf_n]))


@pytest.mark.parametrize("num_bins", [32, 255])
def test_chip_branch_matches_interpret_branch(monkeypatch, num_bins):
    """What the chip compiles (bin axis padded to the lane tile, prefix
    sums as shifted adds, padded bins masked out of the argmax), run
    through the interpreter: with integer-valued histograms every prefix
    sum is exact in any order, so it must make the ``jnp.cumsum``
    branch's picks exactly and its gains to an ulp (XLA:CPU vectorizes
    the padded and unpadded gain formulas differently).
    ``interpret=False`` selects the chip branch; the patched
    ``pallas_call`` keeps the run on the CPU."""
    from jax.experimental import pallas as pl

    rng = np.random.RandomState(11)
    F, num_leaves = 5, 15
    ctx = make_ctx()
    fmask = jnp.ones(F, jnp.float32)
    cnt = rng.randint(0, 40, size=(2, F, num_bins)).astype(np.float32)
    cnt[:, 1:] = cnt[:, :1][:, :, rng.permutation(num_bins)]  # same totals
    g = np.round(rng.randn(2, F, num_bins) * 8) * (cnt > 0)
    hist2 = jnp.asarray(np.stack([g, cnt, cnt], axis=-1), jnp.float32)
    tab, aux, scal = _root_iteration(hist2, num_leaves, ctx, fmask)
    args = (hist2.transpose(0, 1, 3, 2), tab, fmask.reshape(1, F), aux, scal)

    want_tab, want_aux = split_iter_pallas(*args, pk=_PK, interpret=True)
    real_call = pl.pallas_call
    monkeypatch.setattr(
        pl, "pallas_call",
        lambda *a, **k: real_call(*a, **{**k, "interpret": True}))
    got_tab, got_aux = split_iter_pallas(*args, pk=_PK, interpret=False)
    got_tab, want_tab = np.asarray(got_tab), np.asarray(want_tab)
    K = _PK
    picks = [K.SPLIT_FEAT, K.SPLIT_BIN, K.LEFT, K.RIGHT, K.IS_LEAF,
             K.CAND_FEAT, K.CAND_BIN, K.COUNT, K.DEPTH]
    np.testing.assert_array_equal(got_tab[:, picks], want_tab[:, picks])
    np.testing.assert_allclose(got_tab, want_tab, rtol=5e-7, atol=0.0)
    np.testing.assert_array_equal(np.asarray(got_aux), np.asarray(want_aux))
    assert want_tab[0, K.IS_LEAF] == 0                   # the root did split


def test_tree_parity_regression_unbatched():
    bins, stats, fmask = reg_fixture()
    ctx = make_ctx()
    t1, r1 = jax.jit(lambda: grow_tree(bins, stats, fmask, ctx, 31, 16, 0,
                                       fuse_split=True))()
    t0, r0 = jax.jit(lambda: grow_tree(bins, stats, fmask, ctx, 31, 16, 0,
                                       fuse_split=False))()
    assert_trees_equal(t1, t0, r1, r0, gain_ulp=True)


def test_tree_parity_early_stop():
    # min_data_in_leaf so large growth stalls before the leaf budget:
    # the active flag must kill all remaining iterations identically.
    bins, stats, fmask = reg_fixture()
    ctx = make_ctx(min_data=120.0)
    t1, r1 = jax.jit(lambda: grow_tree(bins, stats, fmask, ctx, 63, 16, 0,
                                       fuse_split=True))()
    t0, r0 = jax.jit(lambda: grow_tree(bins, stats, fmask, ctx, 63, 16, 0,
                                       fuse_split=False))()
    assert_trees_equal(t1, t0, r1, r0, gain_ulp=True)
    assert int(t1.num_leaves) < 63


def test_tree_parity_multiclass_vmap():
    rng = np.random.RandomState(11)
    n, F, B = 400, 7, 16
    bins = jnp.asarray(rng.randint(0, B, size=(n, F)), jnp.int32)
    gm = jnp.asarray(rng.randn(3, n).astype(np.float32))
    sm = jnp.stack([gm, jnp.ones((3, n), jnp.float32),
                    jnp.ones((3, n), jnp.float32)], axis=-1)
    fmask = jnp.ones(F, jnp.float32)
    ctx = make_ctx()

    def grow(fs):
        return jax.vmap(lambda s: grow_tree(bins, s, fmask, ctx, 15, B, 0,
                                            fuse_split=fs))(sm)

    t1, r1 = jax.jit(lambda: grow(True))()
    t0, r0 = jax.jit(lambda: grow(False))()
    assert_trees_equal(t1, t0, r1, r0, gain_ulp=True)


def test_tree_parity_hyper_vmap_sweep():
    # fused-CV-style E-batch: hyperparameters vary across the batch axis.
    bins, stats, fmask = reg_fixture()
    E = 5
    l1s = jnp.asarray(np.linspace(0.0, 0.4, E), jnp.float32)
    mds = jnp.asarray([0, 4, 6, 0, 5], jnp.int32)

    def grow(l1, md, fs):
        ctx = make_ctx(l1=l1)
        return grow_tree(bins, stats, fmask, ctx, 31, 16, md, fuse_split=fs)

    t1, r1 = jax.jit(jax.vmap(lambda a, b: grow(a, b, True)))(l1s, mds)
    t0, r0 = jax.jit(jax.vmap(lambda a, b: grow(a, b, False)))(l1s, mds)
    assert_trees_equal(t1, t0, r1, r0, gain_ulp=True)


def test_categorical_fixture_gates_off_identically():
    # cat_info forces the XLA path; fuse_split=True must be a no-op.
    rng = np.random.RandomState(5)
    n, F, B = 500, 4, 24
    bins = jnp.asarray(rng.randint(0, B, size=(n, F)), jnp.int32)
    g = jnp.asarray(rng.randn(n).astype(np.float32))
    stats = jnp.stack([g, jnp.ones(n, jnp.float32),
                       jnp.ones(n, jnp.float32)], -1)
    fmask = jnp.ones(F, jnp.float32)
    cat = CatInfo(is_cat=jnp.zeros(F, bool).at[0].set(True),
                  cat_smooth=jnp.float32(10.0), cat_l2=jnp.float32(10.0),
                  max_cat_threshold=8)
    ctx = make_ctx(ps=0.0, mds=0.0)
    t1, r1 = jax.jit(lambda: grow_tree(bins, stats, fmask, ctx, 15, B, 0,
                                       cat_info=cat, fuse_split=True))()
    t0, r0 = jax.jit(lambda: grow_tree(bins, stats, fmask, ctx, 15, B, 0,
                                       cat_info=cat, fuse_split=False))()
    assert_trees_equal(t1, t0, r1, r0)
    assert bool(np.asarray(t1.is_cat_split).any())
