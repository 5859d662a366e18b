"""r18 fused predict mega-kernel: quantized-space parity + residency.

Covers the r18 acceptance surface at both levels:

* kernel level — ``predict_forest_pallas`` over a ``pack_forest_soa``
  layout matches the legacy ``predict_forest_binned`` scan bit-exactly
  across precision {f32, bf16, int8} x tree shape {balanced, ragged,
  single-leaf}, including staged ``num_iteration``/``start_iteration``
  windows and grower garbage sentinels left in dead node slots;
* runtime level — the fused device path matches the lazily-built numpy
  oracle for trained (ragged) and multiclass forests, bin-edge rows
  route identically in quantized and f32 space (``code <= threshold``
  is the SAME integer comparison), ``ThresholdBoundError`` still rejects
  out-of-range thresholds at ingest, categorical forests fall back to
  the legacy path, the stats counters account mega-kernel launches, the
  resident SoA keeps the compact storage dtypes (no f32/i32 node table
  for int8/bf16 — the byte contract of ``PACKED_NODE_BYTES``), and
  ``warm()`` covers the full (bucket, raw_score, route) compile key so
  a post-warm quantized dp traffic sweep compiles nothing.

dp bit-identity and tp ulp parity for the fused path ride the existing
matrix in test_serving_mesh.py (the runtimes there serve on the fused
path now); this file pins what is NEW in r18.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.dataset import BinMapper
from lightgbm_tpu.models.tree import Tree
from lightgbm_tpu.ops import quantize as qz
from lightgbm_tpu.ops.predict import (
    PREDICT_TREE_CHUNKS,
    forest_depth_cap,
    pack_forest_soa,
    predict_forest_binned,
    predict_forest_pallas,
    soa_tree_chunk,
)
from lightgbm_tpu.serving import (
    PackedForest,
    PredictorRuntime,
    ThresholdBoundError,
    pack_booster,
)

TOL = 1e-6


# ---------------------------------------------------------------------------
# kernel-level parity matrix (no runtime, interpret-mode Pallas)
# ---------------------------------------------------------------------------
def _rand_tree(rng, m, f, num_bins, shape):
    """One tree's arrays with grower-style garbage in dead slots."""
    feat = np.zeros(m, np.int32)
    thr = np.zeros(m, np.int32)
    left = -np.ones(m, np.int32)
    right = -np.ones(m, np.int32)
    leafv = np.zeros(m, np.float32)
    isl = np.zeros(m, bool)
    if shape == "single-leaf":
        isl[0] = True
        leafv[0] = rng.normal()
        leafv[1:] = 999.0                 # dead-slot sentinels must not leak
        return feat, thr, left, right, leafv, isl
    n_nodes, frontier = 1, [0]
    while frontier and n_nodes + 2 <= m:
        i = frontier.pop(rng.integers(len(frontier)))
        if shape == "ragged" and rng.random() < 0.3 and i != 0:
            isl[i] = True
            leafv[i] = rng.normal()
            continue
        feat[i] = rng.integers(f)
        thr[i] = rng.integers(0, num_bins)
        left[i], right[i] = n_nodes, n_nodes + 1
        frontier += [n_nodes, n_nodes + 1]
        n_nodes += 2
    for i in frontier:
        isl[i] = True
        leafv[i] = rng.normal()
    leafv[~isl & (left < 0)] = 777.0      # garbage in dead slots
    return feat, thr, left, right, leafv, isl


def _rand_forest(seed, t=5, m=11, f=4, num_bins=8, shape="ragged"):
    rng = np.random.default_rng(seed)
    shapes = [shape] * t
    if shape == "ragged":                 # mix in one degenerate tree
        shapes[t // 2] = "single-leaf"
    arrs = [_rand_tree(rng, m, f, num_bins, s) for s in shapes]
    feat, thr, left, right, leafv, isl = (np.stack(x) for x in zip(*arrs))
    forest = Tree(
        split_feature=jnp.asarray(feat), split_bin=jnp.asarray(thr),
        left=jnp.asarray(left), right=jnp.asarray(right),
        leaf_value=jnp.asarray(leafv), is_leaf=jnp.asarray(isl),
        count=jnp.zeros((t, 1), jnp.int8),
        split_gain=jnp.zeros((t, 1), jnp.int8),
        num_leaves=jnp.zeros(t, jnp.int32))
    bins = rng.integers(0, num_bins, (37, f)).astype(np.uint8)
    return (feat, thr, left, right, leafv, isl), forest, bins


@pytest.mark.parametrize("shape", ["balanced", "ragged", "single-leaf"])
@pytest.mark.parametrize("precision", ["f32", "bf16", "int8"])
def test_kernel_parity_matrix(precision, shape):
    (feat, thr, left, right, leafv, isl), forest, bins = _rand_forest(
        seed=hash((precision, shape)) % 2**31, shape=shape)
    t = feat.shape[0]
    cap = forest_depth_cap(forest)
    if precision == "f32":
        soa = pack_forest_soa(feat, thr, left, right, leafv, isl,
                              precision="f32")
        ref_leaf = leafv
    elif precision == "bf16":
        stored = np.asarray(jnp.asarray(leafv, jnp.bfloat16), np.float32)
        soa = pack_forest_soa(feat, thr, left, right, stored, isl,
                              precision="bf16")
        ref_leaf = stored
    else:
        scale = np.full(t, 0.01, np.float32)
        codes = np.clip(np.round(leafv / scale[:, None]),
                        -127, 127).astype(np.int8)
        soa = pack_forest_soa(feat.astype(np.int16), thr.astype(np.uint8),
                              left.astype(np.int16),
                              right.astype(np.int16), codes, isl,
                              precision="int8", leaf_scale=scale)
        ref_leaf = codes.astype(np.float32) * scale[:, None]
    assert soa_tree_chunk(soa) == PREDICT_TREE_CHUNKS[precision]
    # legacy scan over the SAME stored values = the semantics oracle
    ref_forest = forest._replace(leaf_value=jnp.asarray(ref_leaf))
    ref = predict_forest_binned(ref_forest, jnp.asarray(bins), 0.1, 0.5,
                                jnp.int32(t), cap)
    got = predict_forest_pallas(soa, jnp.asarray(bins), 0.1, 0.5,
                                jnp.int32(t), cap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=TOL, rtol=0)
    # staged windows: num/start are traced operands of the round mask
    for k, s in [(2, 0), (3, 1), (1, t - 1)]:
        r = predict_forest_binned(ref_forest, jnp.asarray(bins), 0.1, 0.0,
                                  jnp.int32(k), cap,
                                  start_iteration=jnp.int32(s))
        g = predict_forest_pallas(soa, jnp.asarray(bins), 0.1, 0.0,
                                  jnp.int32(k), cap,
                                  start_iteration=jnp.int32(s))
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=TOL, rtol=0, err_msg=f"{k=} {s=}")


def test_multiclass_kernel_parity():
    # 3 classes = 3 independent SoAs; the runtime stacks the columns
    per_class = [_rand_forest(seed=100 + c) for c in range(3)]
    bins = per_class[0][2]
    for c, (arrs, forest, _) in enumerate(per_class):
        feat, thr, left, right, leafv, isl = arrs
        soa = pack_forest_soa(feat, thr, left, right, leafv, isl)
        cap = forest_depth_cap(forest)
        ref = predict_forest_binned(forest, jnp.asarray(bins), 0.2, 0.0,
                                    jnp.int32(feat.shape[0]), cap)
        got = predict_forest_pallas(soa, jnp.asarray(bins), 0.2, 0.0,
                                    jnp.int32(feat.shape[0]), cap)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=TOL, rtol=0, err_msg=f"class {c}")


# ---------------------------------------------------------------------------
# runtime-level fixtures
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def reg_packed(small_regression):
    X, y = small_regression
    b = lgb.train(
        {"objective": "regression", "num_leaves": 15, "verbosity": -1},
        lgb.Dataset(X, label=y), num_boost_round=10)
    return X, pack_booster(b)


@pytest.fixture(scope="module")
def mc_packed():
    rng = np.random.default_rng(7)
    X = rng.normal(size=(600, 4))
    y = ((X[:, 0] + X[:, 1] > 0).astype(int)
         + (X[:, 2] > 0.5).astype(int)).astype(np.float64)
    b = lgb.train(
        {"objective": "multiclass", "num_class": 3, "num_leaves": 7,
         "verbosity": -1},
        lgb.Dataset(X, label=y), num_boost_round=3)
    return X, pack_booster(b)


def _edge_forest(num_bins=8, edge_bin=3):
    """One tree: root splits feature 0 at ``edge_bin``; left leaf -1,
    right leaf +1 — the bin-edge routing probe."""
    t, m = 1, 3
    split_feature = np.zeros((t, m), np.int32)
    split_bin = np.full((t, m), 0, np.int32)
    split_bin[0, 0] = edge_bin
    left = np.full((t, m), -1, np.int32)
    right = np.full((t, m), -1, np.int32)
    left[0, 0], right[0, 0] = 1, 2
    is_leaf = np.zeros((t, m), bool)
    is_leaf[:, 1:] = True
    leaf_value = np.zeros((t, m), np.float32)
    leaf_value[0, 1], leaf_value[0, 2] = -1.0, 1.0
    mapper = BinMapper(
        upper_bounds=[np.arange(num_bins - 1) + 0.5],
        nan_bin=np.full(1, -1, np.int32),
        n_bins=np.full(1, num_bins, np.int32))
    return PackedForest(
        split_feature=split_feature, split_bin=split_bin,
        left=left, right=right, leaf_value=leaf_value, is_leaf=is_leaf,
        is_cat_split=None, cat_mask=None, shrink=1.0,
        init_score=np.zeros(1, np.float32), num_class=1,
        best_iteration=t, depth_cap=1,
        params={"objective": "regression"},
        bin_mapper_dict=mapper.to_dict()).validate()


# ---------------------------------------------------------------------------
# runtime parity + routing + rejection
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("precision", ["f32", "bf16", "int8"])
def test_runtime_oracle_parity(reg_packed, precision):
    X, pf = reg_packed
    rt = PredictorRuntime(pf, max_bucket=256,
                          forest_precision=precision)
    assert rt.fused_predict and rt.cache_info()["fused_path"]
    codes = pf.bin_mapper.transform(np.asarray(X[:200], np.float64))
    dev = rt.predict_binned(codes, raw_score=True)
    oracle = rt.oracle.predict_numpy(codes, raw_score=True)
    assert np.max(np.abs(dev - oracle)) <= 1e-5, precision


def test_runtime_multiclass_parity(mc_packed):
    X, pf = mc_packed
    rt = PredictorRuntime(pf, max_bucket=128,
                          forest_precision="int8")
    assert rt.kernel_launches_per_dispatch == 3      # one kernel per class
    codes = pf.bin_mapper.transform(np.asarray(X[:100], np.float64))
    dev = rt.predict_binned(codes, raw_score=True)
    oracle = rt.oracle.predict_numpy(codes, raw_score=True)
    assert dev.shape == (100, 3)
    assert np.max(np.abs(dev - oracle)) <= 1e-5


@pytest.mark.parametrize("precision", ["f32", "bf16", "int8"])
def test_bin_edge_routes_left(precision):
    # code <= threshold goes LEFT; the quantized path compares the SAME
    # stored u8 bin codes, so the edge row lands identically
    pf = _edge_forest(edge_bin=3)
    rt = PredictorRuntime(pf, max_bucket=16,
                          forest_precision=precision)
    codes = np.arange(8, dtype=np.uint8)[:, None]
    out = rt.predict_binned(codes, raw_score=True)
    want = np.where(np.arange(8) <= 3, -1.0, 1.0)
    np.testing.assert_allclose(out, want, atol=1e-5)
    oracle = rt.oracle.predict_numpy(codes, raw_score=True)
    np.testing.assert_allclose(out, oracle, atol=1e-5)


def test_threshold_bound_rejected_at_ingest(reg_packed):
    _, pf = reg_packed
    bad_bin = pf.split_bin.copy()
    bad_bin[0, int(np.argmin(pf.is_leaf[0]))] = 300
    import dataclasses

    bad = dataclasses.replace(pf, split_bin=bad_bin)
    with pytest.raises(ThresholdBoundError, match="split_bin"):
        PredictorRuntime(bad, max_bucket=16,
                         forest_precision="int8")


# ---------------------------------------------------------------------------
# residency: compact dtypes stay resident, no f32/i32 node table
# ---------------------------------------------------------------------------
def test_soa_residency_byte_contract(reg_packed):
    X, pf = reg_packed
    for precision, idx_t, thr_t, leaf_t in (
            ("int8", np.int16, np.uint8, jnp.int8),
            ("bf16", np.int16, np.uint8, jnp.bfloat16)):
        rt = PredictorRuntime(pf, max_bucket=64,
                              forest_precision=precision)
        (soa,) = rt._soa
        assert soa.split_feature.dtype == idx_t
        assert soa.left.dtype == idx_t and soa.right.dtype == idx_t
        assert soa.split_bin.dtype == thr_t
        assert soa.leaf.dtype == leaf_t
        # no node field is 4 bytes wide -> zero f32 (or i32) table bytes
        assert max(a.dtype.itemsize
                   for a in (soa.split_feature, soa.split_bin, soa.left,
                             soa.right, soa.leaf)) <= 2
        # per-slot bytes match the r14 layout contract the SLO budgets
        # and the analysis model both charge
        per_slot = sum(a.dtype.itemsize
                       for a in (soa.split_feature, soa.split_bin,
                                 soa.left, soa.right, soa.leaf,
                                 soa.is_leaf))
        assert per_slot == qz.PACKED_NODE_BYTES[precision]


def test_analysis_model_matches_layout_contract():
    from lightgbm_tpu.analysis.budgets import (PREDICT_SOA_NODE_BYTES,
                                               predict_kernel_time)

    assert PREDICT_SOA_NODE_BYTES == qz.PACKED_NODE_BYTES
    m = predict_kernel_time(precision="int8")
    assert m["f32_node_table_bytes"] == 0
    assert m["launch_drop_x"] >= 4.0
    assert m["vmem_block_mb"] <= 16.0
    assert predict_kernel_time(precision="bf16")["f32_node_table_bytes"] \
        == 0


def test_cat_forest_falls_back_to_legacy(small_regression):
    X, y = small_regression
    rng = np.random.default_rng(3)
    Xc = np.column_stack([rng.integers(0, 8, len(y)).astype(float),
                          X[:, :2]])
    b = lgb.train(
        {"objective": "regression", "num_leaves": 7, "verbosity": -1,
         "min_data_in_leaf": 5},
        lgb.Dataset(Xc, label=y, categorical_feature=[0]),
        num_boost_round=4)
    rt = PredictorRuntime(pack_booster(b), max_bucket=32)
    assert not rt.fused_predict
    assert rt.kernel_launches_per_dispatch == 0
    rt.predict(Xc[:10])
    snap = rt.stats.snapshot()
    assert snap["predict_kernel_launches"] == 0
    assert snap["fused_path"]["dispatches"] == 0
    assert snap["fused_path"]["legacy_dispatches"] >= 1


# ---------------------------------------------------------------------------
# stats accounting + full-compile-key warm (the r18 zero-recompile pin)
# ---------------------------------------------------------------------------
def test_stats_count_kernel_launches(mc_packed):
    X, pf = mc_packed
    rt = PredictorRuntime(pf, max_bucket=64,
                          forest_precision="int8")
    for n in (5, 40, 64):
        rt.predict(X[:n])
    snap = rt.stats.snapshot()
    assert snap["fused_path"]["dispatches"] == 3
    assert snap["fused_path"]["legacy_dispatches"] == 0
    # 3 dispatches x num_class mega-kernels each
    assert snap["predict_kernel_launches"] == 3 * 3
    assert rt.cache_info()["kernel_launches_per_dispatch"] == 3


def test_warm_covers_full_compile_key_quantized_dp(reg_packed):
    X, pf = reg_packed
    # cache must hold the full warmed key set: 8-bucket ladder x 2
    # raw_score settings (the LRU would otherwise evict early warms —
    # documented warm() semantics)
    rt = PredictorRuntime(pf, max_bucket=128,
                          forest_precision="int8", mesh_devices=4,
                          shard_policy="dp", max_cache_entries=32)
    for raw in (False, True):
        rt.warm(raw_score=raw)
    keys = set(rt.warmed_keys)
    # every bucket warmed at both raw_score settings, on its traffic route
    assert {k[0] for k in keys} == set(rt.buckets)
    assert {k[1] for k in keys} == {False, True}
    assert all(k[2] == rt.route_for(k[0]) for k in keys)
    assert "dp" in {k[2] for k in keys}               # shard program warmed
    before = rt.num_compiles
    for n in (1, 3, 17, 64, 100, 128):
        for raw in (False, True):
            rt.predict(X[:n], raw_score=raw)
    assert rt.num_compiles == before                  # zero traffic compiles
