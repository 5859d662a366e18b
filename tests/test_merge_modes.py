"""r9 histogram-merge topologies on the virtual 8-device CPU mesh.

The reduce-scatter split finding must be SERIAL-PARITY-IDENTICAL: each
shard receives only its F/D feature slice of the merged histogram, runs
the split iteration over the slice, and the per-shard BestSplit
candidates combine through an O(D) argmax all-gather — so the winning
(feature, bin) must match the single-chip grower exactly, including when
the feature axis pads unevenly (F=13 over 8 shards leaves shards 6-7
holding ONLY padding columns).  Voting mode is approximate by contract,
but its exact-union case (2k >= F: every feature is a candidate) must
also reproduce serial trees bit-for-bit.

These are the tier-1-visible merge-mode scenarios (ISSUE r9 satellite:
fast virtual-mesh subset); the full Booster-level chains live in
test_parallel.py and __graft_entry__.dryrun_multichip.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.config import Params
from lightgbm_tpu.models.gbdt import HyperScalars
from lightgbm_tpu.models.spec import STRICT, GrowSpec, WaveSchedule
from lightgbm_tpu.models.tree import grow_tree
from lightgbm_tpu.ops.split import SplitContext
from lightgbm_tpu.parallel.data_parallel import (
    make_dp_grow_step,
    make_dp_train_step,
    make_mesh,
    shard_rows,
)

OBJ_KEY = ("regression", 1.0, 1.0, 0.9, 1.0, 0.7, 30, True, 1)
N_DEV = 8


def _ctx():
    return SplitContext(
        lambda_l1=jnp.float32(0.0), lambda_l2=jnp.float32(1.0),
        min_data_in_leaf=jnp.float32(20.0),
        min_sum_hessian=jnp.float32(1e-3),
        min_gain_to_split=jnp.float32(0.0))


def _make_problem(f, n=1024, num_bins=16, seed=0):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, num_bins, size=(n, f)).astype(np.uint8)
    y = (np.sin(bins[:, 0].astype(np.float32))
         + 0.5 * bins[:, min(1, f - 1)].astype(np.float32)
         + rng.normal(0, 0.1, n)).astype(np.float32)
    stats = np.stack([(0.0 - y).astype(np.float32),
                      np.ones(n, np.float32),
                      np.ones(n, np.float32)], axis=1)
    return bins, y, stats


def _grow_pair(f, merge, voting_k=0, wave_width=1, num_leaves=15,
               num_bins=16):
    """(serial tree/rows, distributed tree/rows) for one merge mode."""
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    bins, _y, stats = _make_problem(f, num_bins=num_bins)
    fmask = jnp.ones(f, jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:N_DEV]), ("data",))
    ctx = _ctx()
    wave = STRICT if wave_width == 1 else WaveSchedule(wave_width, "half")

    tree_s, rows_s = jax.jit(lambda: grow_tree(
        jnp.asarray(bins), jnp.asarray(stats), fmask, ctx, num_leaves,
        num_bins, jnp.int32(-1), wave=wave))()

    def step(b, s):
        return grow_tree(b, s, fmask, ctx, num_leaves, num_bins,
                         jnp.int32(-1), axis_name="data",
                         wave=wave, hist_merge=merge,
                         n_shards=N_DEV, voting_k=voting_k)

    tree_d, rows_d = jax.jit(shard_map(
        step, mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=(P(), P("data")), check_vma=False))(
        jnp.asarray(bins), jnp.asarray(stats))
    return ((jax.device_get(tree_s), np.asarray(rows_s)),
            (jax.device_get(tree_d), np.asarray(rows_d)))


def _assert_tree_parity(serial, dist):
    (ts, rs), (td, rd) = serial, dist
    np.testing.assert_array_equal(ts.split_feature, td.split_feature)
    np.testing.assert_array_equal(ts.split_bin, td.split_bin)
    np.testing.assert_allclose(ts.leaf_value, td.leaf_value,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(rs, rd)


def test_reduce_scatter_parity_ragged_tail():
    """F=13 over 8 shards: features pad to 16, shards 6-7 hold ONLY
    padding columns — the masked-out slice must never win a split."""
    assert len(jax.devices()) >= N_DEV
    _assert_tree_parity(*_grow_pair(13, "reduce_scatter"))


def test_reduce_scatter_parity_f136_wave():
    """The MSLR feature width (F=136, 17/shard) under the frontier
    (wave) grower with the reduce-scatter-sliced histogram cache."""
    _assert_tree_parity(*_grow_pair(136, "reduce_scatter", wave_width=4))


def test_reduce_scatter_parity_fewer_features_than_shards():
    """F=5 < D=8: most shards are pure padding; still exact."""
    _assert_tree_parity(*_grow_pair(5, "reduce_scatter"))


def test_ring_reduce_scatter_parity():
    """The ppermute ring realization must agree with psum_scatter."""
    _assert_tree_parity(*_grow_pair(13, "reduce_scatter_ring",
                                    wave_width=4))


def test_voting_exact_union_parity():
    """2k >= F short-circuits the ballot to the full feature set; the
    candidate reduce-scatter must then reproduce serial trees exactly."""
    _assert_tree_parity(*_grow_pair(13, "voting", voting_k=7,
                                    wave_width=4))


def test_voting_approximate_grows_valid_tree():
    """k << F voting is approximate by contract: it must still grow a
    tree whose splits all come from real (non-padding) features."""
    (ts, _), (td, _) = _grow_pair(136, "voting", voting_k=5)
    assert int(np.sum(td.split_feature >= 0)) > 0
    live = td.split_feature[td.split_feature >= 0]
    assert live.max() < 136


@pytest.mark.parametrize("f", [5, 13, 136])
@pytest.mark.parametrize("wave_width", [1, 4])
def test_pipelined_parity_strict_and_wave(f, wave_width):
    """r10 tentpole exactness bar: the chunked pipelined ring (C=4,
    f32 wire) grows SERIAL-PARITY-IDENTICAL trees across ragged widths
    — F=5 < D, F=13 (pads 32 with chunking vs 16 without: different
    column ownership than plain reduce-scatter, same trees), and the
    MSLR width F=136 — under both the strict and the wave grower."""
    assert len(jax.devices()) >= N_DEV
    _assert_tree_parity(*_grow_pair(f, "reduce_scatter_pipelined",
                                    wave_width=wave_width))


def test_pipelined_multiclass_matches_psum():
    """Class axis vmapped inside the shard_map over the pipelined merge:
    per-class chunked rings batch, trees match psum's."""
    k = 3
    obj_mc = ("multiclass", 1.0, 1.0, 0.9, 1.0, 0.7, 30, True, k)
    bins_np, _y, _ = _make_problem(5, n=1024)
    n = bins_np.shape[0]
    y_mc = (bins_np[:, 0] % k).astype(np.float32)
    mesh = make_mesh(N_DEV)

    def run(merge_mode):
        step = make_dp_train_step(mesh, obj_mc, GrowSpec(7, 16),
                                  num_class=k, merge_mode=merge_mode)
        bins, y, w, bag = shard_rows(
            mesh, jnp.asarray(bins_np), jnp.asarray(y_mc),
            jnp.ones(n, jnp.float32), jnp.ones(n, jnp.float32))
        pred = shard_rows(mesh, jnp.zeros((n, k), jnp.float32))
        fmask = jnp.ones(bins_np.shape[1], jnp.float32)
        trees, new_pred = step(bins, y, w, bag, pred, fmask,
                               HyperScalars.from_params(Params()),
                               jax.random.PRNGKey(1))
        return jax.device_get(trees), np.asarray(new_pred)

    t_ps, p_ps = run("psum")
    t_pl, p_pl = run("reduce_scatter_pipelined")
    np.testing.assert_array_equal(t_ps.split_feature, t_pl.split_feature)
    np.testing.assert_array_equal(t_ps.split_bin, t_pl.split_bin)
    np.testing.assert_allclose(p_ps, p_pl, rtol=1e-5, atol=1e-6)


def test_pipelined_ranking_stats():
    """The stats-only dp grow step (ranking path) under the pipelined
    merge vs serial."""
    bins_np, _y, stats_np = _make_problem(13, n=1024)
    mesh = make_mesh(N_DEV)
    grow = make_dp_grow_step(mesh, GrowSpec(15, 16),
                             merge_mode="reduce_scatter_pipelined")
    bins, stats = shard_rows(mesh, jnp.asarray(bins_np),
                             jnp.asarray(stats_np))
    fmask = jnp.ones(bins_np.shape[1], jnp.float32)
    hyper = HyperScalars.from_params(Params())
    tree_d, _ = grow(bins, stats, fmask, hyper, jax.random.PRNGKey(2))

    tree_s, _ = grow_tree(jnp.asarray(bins_np), jnp.asarray(stats_np),
                          fmask, hyper.ctx(), 15, 16, hyper.max_depth)
    np.testing.assert_array_equal(np.asarray(tree_s.split_feature),
                                  np.asarray(tree_d.split_feature))
    np.testing.assert_array_equal(np.asarray(tree_s.split_bin),
                                  np.asarray(tree_d.split_bin))


def test_wire_dtypes_close_and_guarded():
    """bf16/int8 wire formats: merged histograms stay within the
    documented tolerance of the exact merge, and non-f32 wire refuses
    the fused collectives (no hop boundary to compress at)."""
    from jax.sharding import Mesh, PartitionSpec as P

    from lightgbm_tpu.ops.histogram import histogram_merge
    from jax import shard_map

    s, f, b = 2, 13, 8
    rng = np.random.RandomState(5)
    counts = rng.poisson(16, (N_DEV, s, f, b)).astype(np.float32)
    hist = jnp.asarray(np.stack(
        [counts * rng.randn(N_DEV, s, f, b).astype(np.float32),
         counts * 0.25, counts], axis=-1))
    mesh = Mesh(np.array(jax.devices()[:N_DEV]), ("data",))

    def run(mode, wire):
        def body(h):
            return histogram_merge(h[0], "data", mode=mode,
                                   n_shards=N_DEV, wire_dtype=wire)
        return np.asarray(jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P("data"),),
            out_specs=P("data"), check_vma=False))(hist))

    exact = run("reduce_scatter_ring", "f32")
    scale = np.abs(exact).max()
    for wire in ("bf16", "int8"):
        got = run("reduce_scatter_ring", wire)
        rel = np.abs(got - exact).max() / scale
        assert rel < 0.03, (wire, rel)      # documented ring-hop tolerance
        got_p = run("reduce_scatter_pipelined", wire)
        assert np.abs(got_p).max() > 0
    with pytest.raises(ValueError, match="ring merge mode"):
        run("psum", "int8")
    with pytest.raises(ValueError, match="ring merge mode"):
        run("reduce_scatter", "bf16")
    with pytest.raises(ValueError, match="wire dtype"):
        run("reduce_scatter_ring", "fp8")


def test_mesh_shape_routing():
    """r10 satellite: 2-D rows x features mesh is the default topology
    at D>=8, F>=64 (bit-identical predictions to serial); mesh_shape
    overrides pin or disable it; malformed values die early."""
    import lightgbm_tpu as lgb

    rng = np.random.default_rng(23)
    n, f = 1024, 64
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] * 2 + np.sin(X[:, 5] * 3)
         + rng.normal(0, 0.1, n)).astype(np.float32)
    base = {"objective": "regression", "num_leaves": 7, "verbosity": -1,
            "learning_rate": 0.2, "tree_learner": "data"}

    b_ser = lgb.train({k: v for k, v in base.items()
                       if k != "tree_learner"},
                      lgb.Dataset(X, label=y), num_boost_round=3)
    p_ser = b_ser.predict(X)

    b_auto = lgb.train(dict(base), lgb.Dataset(X, label=y),
                       num_boost_round=3)
    assert getattr(b_auto, "_dp2", False)
    assert dict(b_auto._dp_mesh.shape) == {"data": 4, "feature": 2}
    np.testing.assert_allclose(b_auto.predict(X), p_ser,
                               rtol=1e-5, atol=1e-6)

    b_1d = lgb.train(dict(base, mesh_shape="1d"),
                     lgb.Dataset(X, label=y), num_boost_round=3)
    assert not getattr(b_1d, "_dp2", False)
    np.testing.assert_allclose(b_1d.predict(X), p_ser,
                               rtol=1e-5, atol=1e-6)

    b_2x4 = lgb.train(dict(base, mesh_shape="2x4"),
                      lgb.Dataset(X, label=y), num_boost_round=3)
    assert dict(b_2x4._dp_mesh.shape) == {"data": 2, "feature": 4}
    np.testing.assert_allclose(b_2x4.predict(X), p_ser,
                               rtol=1e-5, atol=1e-6)

    # narrow data stays 1-D under auto (halving the slice buys nothing)
    b_narrow = lgb.train(dict(base), lgb.Dataset(X[:, :8], label=y),
                         num_boost_round=2)
    assert not getattr(b_narrow, "_dp2", False)

    # explicit ring merge keeps the 1-D topology (grow_tree rejects
    # ring merges composed with a feature axis)
    b_ring = lgb.train(dict(base, histogram_merge="reduce_scatter"),
                       lgb.Dataset(X, label=y), num_boost_round=2)
    assert not getattr(b_ring, "_dp2", False)

    with pytest.raises(ValueError, match="mesh_shape"):
        lgb.train(dict(base, mesh_shape="coil"),
                  lgb.Dataset(X, label=y), num_boost_round=1)


def test_histogram_wire_override_param():
    """params={'histogram_wire': ...}: routes through _dp_wire, rejects
    fused-collective merges, trains within the documented tolerance."""
    import lightgbm_tpu as lgb

    rng = np.random.default_rng(31)
    n = 1500
    X = rng.normal(size=(n, 5)).astype(np.float32)
    y = (X[:, 0] + rng.normal(0, 0.1, n)).astype(np.float32)
    base = {"objective": "regression", "num_leaves": 7, "verbosity": -1,
            "tree_learner": "data"}
    b_f32 = lgb.train(dict(base), lgb.Dataset(X, label=y),
                      num_boost_round=4)
    b_q = lgb.train(dict(base, histogram_wire="int8"),
                    lgb.Dataset(X, label=y), num_boost_round=4)
    assert b_q._dp_wire("reduce_scatter_pipelined", n) == ("int8", 4)
    # quality, not parity: quantized wire tracks the f32 model loosely
    mse_f = float(np.mean((b_f32.predict(X) - y) ** 2))
    mse_q = float(np.mean((b_q.predict(X) - y) ** 2))
    assert mse_q < 1.5 * mse_f + 1e-3, (mse_f, mse_q)
    with pytest.raises(ValueError, match="histogram_wire"):
        lgb.train(dict(base, histogram_wire="fp8"),
                  lgb.Dataset(X, label=y), num_boost_round=1)
    with pytest.raises(ValueError, match="reduce_scatter_ring"):
        lgb.train(dict(base, histogram_merge="psum",
                       histogram_wire="int8"),
                  lgb.Dataset(X, label=y), num_boost_round=1)
    b_c2 = lgb.train(dict(base, merge_chunks=2),
                     lgb.Dataset(X, label=y), num_boost_round=4)
    assert b_c2._dp_wire("reduce_scatter_pipelined", n) == ("f32", 2)
    np.testing.assert_allclose(b_c2.predict(X), b_f32.predict(X),
                               rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="merge_chunks"):
        lgb.train(dict(base, merge_chunks=0),
                  lgb.Dataset(X, label=y), num_boost_round=1)


def test_histogram_merge_slices_match_psum():
    """Unit check: each shard's reduce-scatter output equals its feature
    slice of the full psum merge, for both realizations."""
    from jax.sharding import Mesh, PartitionSpec as P

    from lightgbm_tpu.ops.histogram import histogram_merge
    from jax import shard_map

    s, f, b = 2, 13, 8
    rng = np.random.RandomState(3)
    hist = jnp.asarray(rng.randn(N_DEV, s, f, b, 3).astype(np.float32))
    mesh = Mesh(np.array(jax.devices()[:N_DEV]), ("data",))

    def run(mode):
        def body(h):
            return histogram_merge(h[0], "data", mode=mode,
                                   n_shards=N_DEV)
        return np.asarray(jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P("data"),),
            out_specs=P("data"), check_vma=False))(hist))

    full = np.asarray(hist.sum(axis=0))                      # [S, F, B, 3]
    f_loc = -(-f // N_DEV)                                   # 2, padded 16
    padded = np.concatenate(
        [full, np.zeros((s, N_DEV * f_loc - f, b, 3), np.float32)], axis=1)
    want = padded.reshape(s, N_DEV, f_loc, b, 3).transpose(1, 0, 2, 3, 4)
    want = want.reshape(N_DEV * s, f_loc, b, 3)
    for mode in ("reduce_scatter", "reduce_scatter_ring"):
        got = run(mode).reshape(N_DEV * s, f_loc, b, 3)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="merge mode"):
        run("allgatherify")


def test_dp_train_step_merge_modes_match_psum():
    """The full dp train step (objective grad -> grow -> score update)
    over each r9 merge mode reproduces the psum step's tree; psum's own
    serial parity is pinned by test_parallel.py."""
    bins_np, y_np, _ = _make_problem(6, n=1024)
    n = len(y_np)
    mesh = make_mesh(N_DEV)

    def run(merge_mode, voting_k=0):
        step = make_dp_train_step(mesh, OBJ_KEY, GrowSpec(15, 16),
                                  merge_mode=merge_mode,
                                  voting_k=voting_k)
        bins, y, w, bag, pred = shard_rows(
            mesh, jnp.asarray(bins_np), jnp.asarray(y_np),
            jnp.ones(n, jnp.float32), jnp.ones(n, jnp.float32),
            jnp.zeros(n, jnp.float32))
        fmask = jnp.ones(bins_np.shape[1], jnp.float32)
        tree, new_pred = step(bins, y, w, bag, pred, fmask,
                              HyperScalars.from_params(Params()),
                              jax.random.PRNGKey(0))
        return jax.device_get(tree), np.asarray(new_pred)

    tree_ps, pred_ps = run("psum")
    for mode, vk in (("reduce_scatter", 0), ("voting", 6)):
        tree_m, pred_m = run(mode, vk)        # vk=6 -> exact union (F=6)
        np.testing.assert_array_equal(tree_ps.split_feature,
                                      tree_m.split_feature)
        np.testing.assert_array_equal(tree_ps.split_bin, tree_m.split_bin)
        np.testing.assert_allclose(pred_ps, pred_m, rtol=1e-5, atol=1e-6)


def test_dp_grow_step_reduce_scatter_ranking_stats():
    """The stats-only dp grow step (the ranking path: lambdas computed
    replicated, growth sharded) under reduce_scatter vs serial."""
    bins_np, _y, stats_np = _make_problem(13, n=1024)
    n = stats_np.shape[0]
    mesh = make_mesh(N_DEV)
    grow = make_dp_grow_step(mesh, GrowSpec(15, 16),
                             merge_mode="reduce_scatter")
    bins, stats = shard_rows(mesh, jnp.asarray(bins_np),
                             jnp.asarray(stats_np))
    fmask = jnp.ones(bins_np.shape[1], jnp.float32)
    hyper = HyperScalars.from_params(Params())
    tree_d, _ = grow(bins, stats, fmask, hyper, jax.random.PRNGKey(2))

    tree_s, _ = grow_tree(jnp.asarray(bins_np), jnp.asarray(stats_np),
                          fmask, hyper.ctx(), 15, 16, hyper.max_depth)
    np.testing.assert_array_equal(np.asarray(tree_s.split_feature),
                                  np.asarray(tree_d.split_feature))
    np.testing.assert_array_equal(np.asarray(tree_s.split_bin),
                                  np.asarray(tree_d.split_bin))


def test_dp_multiclass_reduce_scatter_matches_psum():
    """Class axis vmapped inside the shard_map: per-class histograms
    reduce-scatter as one batched collective; trees match psum's."""
    k = 3
    obj_mc = ("multiclass", 1.0, 1.0, 0.9, 1.0, 0.7, 30, True, k)
    bins_np, _y, _ = _make_problem(5, n=1024)
    n = bins_np.shape[0]
    y_mc = (bins_np[:, 0] % k).astype(np.float32)
    mesh = make_mesh(N_DEV)

    def run(merge_mode):
        step = make_dp_train_step(mesh, obj_mc, GrowSpec(7, 16),
                                  num_class=k, merge_mode=merge_mode)
        bins, y, w, bag = shard_rows(
            mesh, jnp.asarray(bins_np), jnp.asarray(y_mc),
            jnp.ones(n, jnp.float32), jnp.ones(n, jnp.float32))
        pred = shard_rows(mesh, jnp.zeros((n, k), jnp.float32))
        fmask = jnp.ones(bins_np.shape[1], jnp.float32)
        trees, new_pred = step(bins, y, w, bag, pred, fmask,
                               HyperScalars.from_params(Params()),
                               jax.random.PRNGKey(1))
        return jax.device_get(trees), np.asarray(new_pred)

    t_ps, p_ps = run("psum")
    t_rs, p_rs = run("reduce_scatter")
    np.testing.assert_array_equal(t_ps.split_feature, t_rs.split_feature)
    np.testing.assert_array_equal(t_ps.split_bin, t_rs.split_bin)
    np.testing.assert_allclose(p_ps, p_rs, rtol=1e-5, atol=1e-6)


def test_booster_tree_learner_voting_routes_and_trains():
    """tree_learner='voting' must engage the dp mesh, route the voting
    merge, and (top_k small) still learn the target."""
    import lightgbm_tpu as lgb

    rng = np.random.default_rng(17)
    n = 2000
    X = rng.normal(size=(n, 12)).astype(np.float32)
    y = (X[:, 0] * 2 + np.sin(X[:, 5] * 3)
         + rng.normal(0, 0.1, n)).astype(np.float32)
    b = lgb.train({"objective": "regression", "num_leaves": 15,
                   "learning_rate": 0.2, "verbosity": -1,
                   "tree_learner": "voting", "top_k": 3},
                  lgb.Dataset(X, label=y), num_boost_round=8)
    assert b._dp_mesh is not None
    mode, k = b._dp_merge_mode()
    assert (mode, k) == ("voting", 3)
    rmse = float(np.sqrt(np.mean((b.predict(X) - y) ** 2)))
    assert rmse < float(np.std(y)) * 0.6, rmse


def test_comm_budget_model_and_gate():
    """The declarative comm budgets: reduce-scatter receives exactly the
    F/D slice at the r9 reference shape (D=8, F=136, B=256, S=2) — an
    8x drop vs psum against the >=4x acceptance floor."""
    from lightgbm_tpu.analysis.budgets import (check_comm_budgets,
                                               hist_merge_comm_bytes)

    ps = hist_merge_comm_bytes("psum", 8, 136, 256, 2)
    rs = hist_merge_comm_bytes("reduce_scatter", 8, 136, 256, 2)
    bestsplit = 8 * 16 * 4
    assert ps["received_bytes_per_shard"] == 2 * 136 * 256 * 3 * 4 \
        + bestsplit
    assert rs["received_bytes_per_shard"] == 2 * 17 * 256 * 3 * 4 \
        + bestsplit
    results = check_comm_budgets()
    assert all(r["ok"] for r in results), results
    assert {r["mode"] for r in results} == {
        "reduce_scatter", "reduce_scatter_ring",
        "reduce_scatter_pipelined", "voting"}
    with pytest.raises(ValueError):
        hist_merge_comm_bytes("gather", 8, 136, 256, 2)


def test_comm_time_model_and_pipelined_budgets():
    """r10: the comm *time* model.  At the D=8/F=136/B=256 reference the
    wave's histogram matmul (~2.7 ms) dwarfs ring comm (~50 us), so the
    pipelined schedule hides all but the first chunk's wire time:
    hidden_frac = 1 - 1/C = 0.75 at C=4, over the 60% acceptance floor.
    int8 wire must cut modeled ring bytes >=2x vs r9's 104,960 B/shard."""
    from lightgbm_tpu.analysis.budgets import (
        check_comm_time_budgets, comm_budget_by_name,
        hist_merge_comm_bytes, hist_merge_comm_time)

    # pipelined C=4 pads F=136 -> 160: the slice widens to 20 features
    pipe = hist_merge_comm_bytes("reduce_scatter_pipelined", 8, 136,
                                 256, 2)
    bestsplit = 8 * 16 * 4
    assert pipe["received_bytes_per_shard"] == 2 * 20 * 256 * 3 * 4 \
        + bestsplit
    # int8 wire: 1 B cells + a 12 B per-feature scale sidecar on each of
    # the (d-1)*chunks hop messages (5 features per message at C=4)
    q = hist_merge_comm_bytes("reduce_scatter_pipelined", 8, 136, 256, 2,
                              wire_dtype="int8")
    assert q["received_bytes_per_shard"] == 2 * 20 * 256 * 3 * 1 \
        + 7 * 20 * 12 + bestsplit
    assert 104_960 / q["received_bytes_per_shard"] >= 2.0
    assert comm_budget_by_name("hist_wire_int8_d8").check()["ok"]

    # wire compression only makes sense where per-hop messages exist
    with pytest.raises(ValueError, match="ring"):
        hist_merge_comm_bytes("psum", 8, 136, 256, 2, wire_dtype="int8")
    with pytest.raises(ValueError, match="wire"):
        hist_merge_comm_bytes("reduce_scatter_ring", 8, 136, 256, 2,
                              wire_dtype="fp8")

    t = hist_merge_comm_time("reduce_scatter_pipelined", 8, 136, 256, 2)
    assert t["compute_bound"]
    assert abs(t["hidden_frac"] - 0.75) < 1e-9   # 1 - 1/C at C=4
    assert abs(t["hidden_ms"] + t["exposed_ms"] - t["comm_ms"]) < 1e-9
    # serial modes expose their full comm time
    ser = hist_merge_comm_time("reduce_scatter", 8, 136, 256, 2)
    assert ser["hidden_frac"] == 0.0
    assert ser["exposed_ms"] == ser["comm_ms"]
    # comm-bound regime: tiny compute -> makespan is comm-dominated and
    # only the chunk-0 compute bubble is hidden
    cb = hist_merge_comm_time("reduce_scatter_pipelined", 8, 136, 256, 2,
                              rows_per_shard=1)
    assert not cb["compute_bound"]
    assert 0.0 < cb["hidden_frac"] < 0.25

    results = check_comm_time_budgets()
    assert all(r["ok"] for r in results), results
    assert {r["name"] for r in results} == {
        "merge_hidden_pipelined_d8", "merge_hidden_pipelined_int8_d8"}


def test_int8_overflow_guards():
    """The int8 accumulation cliff (2^31/127 rows per (segment, bin)
    cell) must raise at every layer instead of silently wrapping."""
    from lightgbm_tpu.config import parse_params
    from lightgbm_tpu.models.spec import check_int8_row_limit
    from lightgbm_tpu.ops.histogram_pallas import (
        INT8_ACC_ROW_LIMIT, hist_from_segstats_pallas)

    assert INT8_ACC_ROW_LIMIT == (1 << 31) // 127
    p = parse_params({"objective": "regression", "hist_dtype": "int8"},
                     warn_unknown=False)
    check_int8_row_limit(p, INT8_ACC_ROW_LIMIT, 1)          # at the bound
    with pytest.raises(ValueError, match="int8"):
        check_int8_row_limit(p, INT8_ACC_ROW_LIMIT + 1, 1)
    check_int8_row_limit(p, INT8_ACC_ROW_LIMIT + 1, 8)      # sharded: fine
    p_f32 = parse_params({"objective": "regression"}, warn_unknown=False)
    check_int8_row_limit(p_f32, 10 ** 9, 1)                 # non-int8

    with pytest.raises(ValueError, match="int8"):
        hist_from_segstats_pallas(jnp.zeros((8, 2), jnp.int32),
                                  jnp.ones((8, 4)), 4, hist_dtype="int8")


def test_tree_learner_and_top_k_validation():
    from lightgbm_tpu.config import parse_params

    p = parse_params({"objective": "regression",
                      "tree_learner": "voting", "topk": 11},
                     warn_unknown=False)
    assert p.tree_learner == "voting" and p.top_k == 11
    with pytest.raises(ValueError):
        parse_params({"objective": "regression", "tree_learner": "ring"},
                     warn_unknown=False)
    with pytest.raises(ValueError):
        parse_params({"objective": "regression", "top_k": 0},
                     warn_unknown=False)


def test_histogram_merge_override_param():
    """params={'histogram_merge': ...} forces the topology; bad values
    die in _dp_merge_mode before any tracing."""
    import lightgbm_tpu as lgb

    rng = np.random.default_rng(29)
    n = 1500
    X = rng.normal(size=(n, 5)).astype(np.float32)
    y = (X[:, 0] + rng.normal(0, 0.1, n)).astype(np.float32)
    base = {"objective": "regression", "num_leaves": 7, "verbosity": -1,
            "tree_learner": "data"}
    b_ps = lgb.train(dict(base, histogram_merge="psum"),
                     lgb.Dataset(X, label=y), num_boost_round=4)
    assert b_ps._dp_merge_mode()[0] == "psum"
    b_rs = lgb.train(dict(base), lgb.Dataset(X, label=y),
                     num_boost_round=4)
    # r10: the data learner's default is the pipelined chunked ring
    assert b_rs._dp_merge_mode()[0] == "reduce_scatter_pipelined"
    np.testing.assert_allclose(b_ps.predict(X), b_rs.predict(X),
                               rtol=1e-5, atol=1e-5)
    b_plain = lgb.train(dict(base, histogram_merge="reduce_scatter"),
                        lgb.Dataset(X, label=y), num_boost_round=4)
    assert b_plain._dp_merge_mode()[0] == "reduce_scatter"
    np.testing.assert_allclose(b_ps.predict(X), b_plain.predict(X),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="histogram_merge"):
        lgb.train(dict(base, histogram_merge="gather"),
                  lgb.Dataset(X, label=y), num_boost_round=1)
