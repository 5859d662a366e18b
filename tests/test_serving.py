"""Serving subsystem: packed forest format, runtime, micro-batching, CLI.

Covers the r6 acceptance criteria: packed round-trip parity vs
Booster.predict (incl. multiclass + categorical), ingest validation
rejecting cyclic/dangling trees, bucket rounding + padding-mask
correctness at batch sizes 1/7/128/1000, LRU eviction, the
compile-counter bound for mixed-batch workloads, and micro-batch
coalescing/timeout behavior with a mocked clock (zero sleeps).
"""

import io
import json
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.serving import (
    MicroBatcher,
    PACKED_FORMAT_VERSION,
    PackedForest,
    PackedForestError,
    PendingPrediction,
    PredictorRuntime,
    RequestTimeout,
    ServingStats,
    bucket_for,
    pack_booster,
)

TOL = 1e-6


# ---------------------------------------------------------------------------
# model fixtures (kept tiny: CPU compiles dominate this suite's wall time)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def reg_booster(small_regression):
    X, y = small_regression
    return X, lgb.train(
        {"objective": "regression", "num_leaves": 15, "verbosity": -1},
        lgb.Dataset(X, label=y), num_boost_round=12)


@pytest.fixture(scope="module")
def mc_booster():
    rng = np.random.default_rng(7)
    n, f = 900, 4
    X = rng.normal(size=(n, f))
    y = ((X[:, 0] + X[:, 1] > 0).astype(int)
         + (X[:, 2] > 0.5).astype(int)).astype(np.float64)
    b = lgb.train(
        {"objective": "multiclass", "num_class": 3, "num_leaves": 7,
         "verbosity": -1},
        lgb.Dataset(X, label=y), num_boost_round=5)
    return X, b


@pytest.fixture(scope="module")
def cat_booster():
    rng = np.random.default_rng(11)
    n = 900
    cat = rng.integers(0, 12, n).astype(float)
    X = np.column_stack([cat, rng.normal(size=(n, 2))])
    y = (np.where(cat % 3 == 0, 2.0, -1.0) + 0.3 * X[:, 1]
         + 0.05 * rng.normal(size=n))
    b = lgb.train(
        {"objective": "regression", "num_leaves": 7, "verbosity": -1,
         "min_data_in_leaf": 5},
        lgb.Dataset(X, label=y, categorical_feature=[0]), num_boost_round=6)
    return X, b


def _roundtrip(booster, tmp_path, name="m.npz", **kw):
    path = os.path.join(str(tmp_path), name)
    pack_booster(booster, **kw).save(path)
    return PackedForest.load(path)


# ---------------------------------------------------------------------------
# packed round-trip parity
# ---------------------------------------------------------------------------
def test_packed_roundtrip_regression(reg_booster, tmp_path):
    X, b = reg_booster
    rt = PredictorRuntime(_roundtrip(b, tmp_path))
    got = rt.predict(X[:300])
    assert np.abs(got - b.predict(X[:300])).max() <= TOL
    # raw_score and staged truncation share the parity bound
    raw = rt.predict(X[:100], raw_score=True)
    assert np.abs(raw - b.predict(X[:100], raw_score=True)).max() <= TOL
    st = rt.predict(X[:100], num_iteration=5)
    assert np.abs(st - b.predict(X[:100], num_iteration=5)).max() <= TOL


def test_packed_roundtrip_multiclass(mc_booster, tmp_path):
    X, b = mc_booster
    pf = _roundtrip(b, tmp_path)
    assert pf.num_class == 3
    rt = PredictorRuntime(pf)
    got = rt.predict(X[:200])
    ref = b.predict(X[:200])
    assert got.shape == ref.shape == (200, 3)
    assert np.abs(got - ref).max() <= TOL
    assert np.abs(got.sum(axis=1) - 1.0).max() < 1e-5


def test_packed_roundtrip_categorical(cat_booster, tmp_path):
    X, b = cat_booster
    pf = _roundtrip(b, tmp_path)
    assert pf.is_cat_split is not None and pf.is_cat_split.any()
    rt = PredictorRuntime(pf)
    assert np.abs(rt.predict(X[:200]) - b.predict(X[:200])).max() <= TOL


def test_predict_numpy_oracle_parity(mc_booster, tmp_path):
    X, b = mc_booster
    pf = _roundtrip(b, tmp_path)
    codes = pf.bin_mapper.transform(X[:64])
    got = pf.predict_numpy(codes, raw_score=False)
    assert np.abs(got - b.predict(X[:64])).max() <= TOL


def test_booster_save_model_npz_roundtrip(reg_booster, tmp_path):
    """.npz routing through save_model/Booster(model_file=...)."""
    X, b = reg_booster
    path = os.path.join(str(tmp_path), "model.npz")
    b.save_model(path)
    b2 = lgb.Booster(model_file=path)
    assert np.abs(b2.predict(X[:200]) - b.predict(X[:200])).max() <= TOL
    assert b2.num_trees() == b.num_trees()
    assert b2.feature_name() == b.feature_name()


def test_pack_truncation_semantics(reg_booster, tmp_path):
    X, b = reg_booster
    pf = _roundtrip(b, tmp_path, name="trunc.npz", num_iteration=4)
    assert pf.num_trees == 4
    assert pf.best_iteration == -1          # stored best no longer indexes
    rt = PredictorRuntime(pf)
    assert np.abs(rt.predict(X[:50])
                  - b.predict(X[:50], num_iteration=4)).max() <= TOL
    with pytest.raises(ValueError):
        pack_booster(b, start_iteration=b.num_trees())


# ---------------------------------------------------------------------------
# ingest validation
# ---------------------------------------------------------------------------
def _tamper_and_reload(pf, tmp_path, name, mutate):
    mutate(pf)
    path = os.path.join(str(tmp_path), name)
    pf.save(path)                            # save() does not re-validate
    return path


def test_ingest_rejects_cycle(reg_booster, tmp_path):
    X, b = reg_booster
    pf = _roundtrip(b, tmp_path, name="c0.npz")

    def mk_cycle(p):
        p.left[0, 0] = 0                     # root's left child is the root

    path = _tamper_and_reload(pf, tmp_path, "cyc.npz", mk_cycle)
    with pytest.raises(PackedForestError, match="reachable twice"):
        PackedForest.load(path)
    # validate=False loads without raising; traversal still terminates
    # because the convergence loop is bounded by node capacity
    pf_raw = PackedForest.load(path, validate=False)
    out = pf_raw.to_tree()
    from lightgbm_tpu.ops.predict import predict_tree_binned
    import jax.tree_util as jtu
    one = jtu.tree_map(lambda a: a[0], out)
    codes = pf_raw.bin_mapper.transform(X[:8])
    vals = predict_tree_binned(one, np.asarray(codes), max_depth_cap=None)
    assert np.asarray(vals).shape == (8,)    # terminated, no hang


def test_ingest_rejects_dangling_child(reg_booster, tmp_path):
    pf = _roundtrip(reg_booster[1], tmp_path, name="d0.npz")

    def dangle(p):
        internal = np.argwhere(~p.is_leaf[0]
                               & (p.left[0] >= 0)).ravel()
        p.left[0, internal[0]] = -1

    path = _tamper_and_reload(pf, tmp_path, "dang.npz", dangle)
    with pytest.raises(PackedForestError, match="dangling"):
        PackedForest.load(path)


def test_ingest_rejects_out_of_range_child(reg_booster, tmp_path):
    pf = _roundtrip(reg_booster[1], tmp_path, name="o0.npz")

    def oob(p):
        internal = np.argwhere(~p.is_leaf[0] & (p.left[0] >= 0)).ravel()
        p.right[0, internal[0]] = p.capacity + 5

    path = _tamper_and_reload(pf, tmp_path, "oob.npz", oob)
    with pytest.raises(PackedForestError, match="out of range"):
        PackedForest.load(path)


def test_ingest_rejects_bad_feature_and_nonfinite_leaf(reg_booster,
                                                      tmp_path):
    pf = _roundtrip(reg_booster[1], tmp_path, name="f0.npz")

    def badfeat(p):
        internal = np.argwhere(~p.is_leaf[0] & (p.left[0] >= 0)).ravel()
        p.split_feature[0, internal[0]] = 999

    path = _tamper_and_reload(pf, tmp_path, "feat.npz", badfeat)
    with pytest.raises(PackedForestError, match="feature"):
        PackedForest.load(path)

    pf2 = _roundtrip(reg_booster[1], tmp_path, name="n0.npz")

    def nanleaf(p):
        leaf = np.argwhere(p.is_leaf[0]).ravel()
        p.leaf_value[0, leaf[0]] = np.nan

    path2 = _tamper_and_reload(pf2, tmp_path, "nan.npz", nanleaf)
    with pytest.raises(PackedForestError, match="non-finite"):
        PackedForest.load(path2)


def test_ingest_rejects_foreign_and_future_files(reg_booster, tmp_path):
    foreign = os.path.join(str(tmp_path), "foreign.npz")
    np.savez(foreign, stuff=np.arange(4))
    with pytest.raises(PackedForestError, match="missing meta_json"):
        PackedForest.load(foreign)

    pf = _roundtrip(reg_booster[1], tmp_path, name="v0.npz")
    path = os.path.join(str(tmp_path), "future.npz")
    pf.save(path)
    with np.load(path) as z:
        arrays = {k: z[k] for k in z.files}
    meta = json.loads(bytes(arrays["meta_json"]).decode())
    meta["format_version"] = PACKED_FORMAT_VERSION + 1
    arrays["meta_json"] = np.frombuffer(
        json.dumps(meta).encode(), dtype=np.uint8)
    np.savez(path, **arrays)
    with pytest.raises(PackedForestError, match="newer than supported"):
        PackedForest.load(path)


def test_validate_recomputes_depth_cap(reg_booster, tmp_path):
    pf = _roundtrip(reg_booster[1], tmp_path, name="dc.npz")
    stored = pf.depth_cap
    pf.depth_cap = 1                         # lie, as a hostile file could
    assert pf.validate().depth_cap == stored


# ---------------------------------------------------------------------------
# runtime: buckets, padding, compile cache
# ---------------------------------------------------------------------------
def test_bucket_for_rounding():
    cases = {1: 1, 2: 2, 3: 4, 7: 8, 8: 8, 128: 128, 129: 256,
             1000: 1024, 16384: 16384}
    for n, want in cases.items():
        assert bucket_for(n, 16384) == want
    assert bucket_for(1000, 256) == 256      # capped at max_bucket
    assert bucket_for(0, 16384) == 1


@pytest.mark.parametrize("n", [1, 7, 128, 1000])
def test_bucket_padding_parity(reg_booster, tmp_path, n):
    """Padded rows never leak into real outputs, at every bucket shape."""
    X, b = reg_booster
    rt = PredictorRuntime(_roundtrip(b, tmp_path), max_bucket=256)
    Xn = np.resize(X, (n, X.shape[1]))
    got = rt.predict(Xn)
    assert got.shape == (n,)
    assert np.abs(got - b.predict(Xn)).max() <= TOL


def test_compile_counter_mixed_batches(reg_booster, tmp_path):
    """Acceptance: a mixed-size workload compiles at most len(buckets)
    programs — sizes from {1..1000} collapse onto power-of-two buckets."""
    X, b = reg_booster
    rt = PredictorRuntime(_roundtrip(b, tmp_path), max_bucket=1024)
    rng = np.random.default_rng(3)
    sizes = [1, 7, 128, 1000] + list(rng.integers(1, 1001, size=12))
    for n in sizes:
        Xn = np.resize(X, (int(n), X.shape[1]))
        got = rt.predict(Xn)
        assert np.abs(got - b.predict(Xn)).max() <= TOL
    assert rt.num_compiles <= len(rt.buckets)
    info = rt.cache_info()
    assert info["num_compiles"] == rt.num_compiles
    # repeating the workload is all cache hits
    before = rt.num_compiles
    for n in sizes[:6]:
        rt.predict(np.resize(X, (int(n), X.shape[1])))
    assert rt.num_compiles == before


def test_chunking_beyond_max_bucket(reg_booster, tmp_path):
    X, b = reg_booster
    rt = PredictorRuntime(_roundtrip(b, tmp_path), max_bucket=64)
    got = rt.predict(X[:300])                # 4 full chunks + remainder
    assert np.abs(got - b.predict(X[:300])).max() <= TOL
    assert max(k[0] for k in rt._cache) <= 64


def test_lru_eviction_recompiles(reg_booster, tmp_path):
    X, b = reg_booster
    rt = PredictorRuntime(_roundtrip(b, tmp_path), max_bucket=1024,
                          max_cache_entries=2)
    for n in (1, 2, 4):                      # 3 buckets through a 2-slot LRU
        rt.predict(X[:n])
    assert len(rt._cache) == 2
    assert (1, False) not in rt._cache       # oldest evicted
    c = rt.num_compiles
    rt.predict(X[:1])                        # evicted bucket recompiles
    assert rt.num_compiles == c + 1
    rt.predict(X[:4])                        # survivor still cached
    assert rt.num_compiles == c + 1


def test_empty_batch_and_bad_max_bucket(reg_booster, tmp_path):
    X, b = reg_booster
    pf = _roundtrip(b, tmp_path)
    rt = PredictorRuntime(pf)
    assert rt.predict(X[:0]).shape == (0,)
    with pytest.raises(ValueError, match="power of two"):
        PredictorRuntime(pf, max_bucket=300)


def test_stats_snapshot_counters(reg_booster, tmp_path):
    X, b = reg_booster
    rt = PredictorRuntime(_roundtrip(b, tmp_path), stats=ServingStats())
    rt.predict(X[:7])
    rt.predict(X[:7])
    snap = rt.stats.snapshot()
    bk = {e["bucket"]: e for e in snap["buckets"]}[8]
    assert bk["dispatches"] == 2 and bk["rows"] == 14
    assert bk["cache_hits"] == 1 and bk["cache_misses"] == 1
    assert bk["padded_rows"] == 2
    assert 0.0 < bk["padding_waste"] < 1.0
    assert bk["latency_p50_ms"] >= 0.0
    json.dumps(snap)                         # snapshot is JSON-able


def test_warm_buckets_precompiles_ladder(reg_booster, tmp_path):
    """warm() builds the whole ladder up front; subsequent traffic of any
    size class is pure cache hits (r7 satellite)."""
    X, b = reg_booster
    rt = PredictorRuntime(_roundtrip(b, tmp_path), max_bucket=64)
    n = rt.warm()
    assert n == len(rt.buckets) == rt.warmed_buckets        # 1..64 fits
    c = rt.num_compiles
    for sz in (1, 2, 5, 33, 64):
        got = rt.predict(np.resize(X, (sz, X.shape[1])))
        assert got.shape == (sz,)
    assert rt.num_compiles == c              # zero compiles on traffic
    # ladder larger than the LRU: warm only the LARGEST entries that fit
    # (warming all would evict programs it just built)
    rt2 = PredictorRuntime(_roundtrip(b, tmp_path, name="m2.npz"),
                           max_bucket=1024, max_cache_entries=3)
    assert rt2.warm() == 3
    assert sorted(k[0] for k in rt2._cache) == [256, 512, 1024]


def test_snapshot_folds_compile_cache(reg_booster, tmp_path):
    X, b = reg_booster
    rt = PredictorRuntime(_roundtrip(b, tmp_path), max_bucket=256,
                          stats=ServingStats())
    rt.predict(X[:5])
    snap = rt.stats.snapshot()
    cc = snap["compile_cache"]
    assert cc["num_compiles"] == rt.num_compiles == 1
    assert cc["buckets_live"] == [8]
    assert cc["warmed_buckets"] == 0
    json.dumps(snap)


# ---------------------------------------------------------------------------
# micro-batching queue (mocked clock, no sleeps)
# ---------------------------------------------------------------------------
class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.fixture()
def reg_runtime(reg_booster, tmp_path):
    return PredictorRuntime(_roundtrip(reg_booster[1], tmp_path))


def test_microbatch_coalesces_on_delay(reg_booster, reg_runtime):
    X, b = reg_booster
    clk = _Clock()
    mb = MicroBatcher(reg_runtime, max_batch=8, max_delay_ms=10.0,
                      clock=clk)
    handles = [mb.submit(X[i]) for i in range(3)]
    assert mb.pump() == 0                    # below batch AND below delay
    assert not handles[0].done and mb.pending_count() == 3
    clk.t = 0.011                            # oldest passes max_delay
    assert mb.pump() == 1                    # ONE coalesced dispatch
    got = np.array([h.result() for h in handles])
    assert np.abs(got - b.predict(X[:3])).max() <= TOL
    assert reg_runtime.stats.batched_dispatches == 1


def test_microbatch_full_batch_dispatches_immediately(reg_booster,
                                                      reg_runtime):
    X, b = reg_booster
    mb = MicroBatcher(reg_runtime, max_batch=4, max_delay_ms=1e6,
                      clock=_Clock())
    handles = [mb.submit(X[i]) for i in range(9)]
    assert mb.pump() == 2                    # two full batches, 1 leftover
    assert mb.pending_count() == 1
    assert mb.flush() == 1
    got = np.array([h.result() for h in handles])
    assert np.abs(got - b.predict(X[:9])).max() <= TOL


def test_microbatch_timeout_expires_requests(reg_booster, reg_runtime):
    X, _ = reg_booster
    clk = _Clock()
    mb = MicroBatcher(reg_runtime, max_batch=8, max_delay_ms=1e6,
                      timeout_ms=5.0, clock=clk)
    h_expire = mb.submit(X[0])
    h_live = mb.submit(X[1], timeout_ms=1e6)
    clk.t = 0.006                            # past default deadline
    mb.pump()
    with pytest.raises(RequestTimeout):
        h_expire.result()
    assert not h_live.done                   # own deadline still far
    mb.flush()
    assert h_live.done and h_live.error is None
    assert reg_runtime.stats.timeouts == 1


def test_microbatch_fallback_on_device_error(reg_booster, tmp_path):
    X, b = reg_booster
    rt = PredictorRuntime(_roundtrip(b, tmp_path))
    rt.predict = None                        # simulate a dead device path

    def boom(*a, **k):
        raise RuntimeError("device gone")

    rt.predict = boom
    mb = MicroBatcher(rt, max_batch=2, max_delay_ms=0.0, clock=_Clock())
    h1, h2 = mb.submit(X[0]), mb.submit(X[1])
    mb.pump()
    got = np.array([h1.result(), h2.result()])
    assert np.abs(got - b.predict(X[:2])).max() <= TOL
    assert rt.stats.fallbacks == 2

    mb2 = MicroBatcher(rt, max_batch=1, max_delay_ms=0.0, clock=_Clock(),
                       fallback_unbatched=False)
    h3 = mb2.submit(X[0])
    mb2.pump()
    with pytest.raises(RuntimeError, match="fallback is disabled"):
        h3.result()


def test_microbatch_rejects_bad_row_and_unready_result(reg_booster,
                                                       reg_runtime):
    X, _ = reg_booster
    mb = MicroBatcher(reg_runtime, clock=_Clock())
    h = mb.submit(X[0, :3])                  # wrong feature count
    assert h.done
    with pytest.raises(ValueError, match="features"):
        h.result()
    h2 = mb.submit(X[0])
    with pytest.raises(RuntimeError, match="not ready"):
        h2.result()
    mb.flush()
    assert h2.done
    assert isinstance(h2, PendingPrediction)


def test_microbatch_mixed_truncation_groups(reg_booster, reg_runtime):
    X, b = reg_booster
    mb = MicroBatcher(reg_runtime, max_batch=16, max_delay_ms=0.0,
                      clock=_Clock())
    ha = mb.submit(X[0], num_iteration=3)
    hb = mb.submit(X[1])
    mb.pump()
    assert abs(ha.result() - b.predict(X[:1], num_iteration=3)[0]) <= TOL
    assert abs(hb.result() - b.predict(X[1:2])[0]) <= TOL


# ---------------------------------------------------------------------------
# CLI: lightgbm_tpu serve over stdio (in-process, injected streams)
# ---------------------------------------------------------------------------
def test_cli_serve_inprocess(cat_booster, tmp_path):
    from lightgbm_tpu.__main__ import _serve

    X, b = cat_booster
    path = os.path.join(str(tmp_path), "serve.npz")
    pack_booster(b).save(path)
    lines = "\n".join(",".join(f"{v:.6f}" for v in X[i]) for i in range(7))
    out, err = io.StringIO(), io.StringIO()
    rc = _serve(path, {"max_batch": "4", "show_stats": "true"},
                stdin=io.StringIO(lines + "\n"), stdout=out, stderr=err)
    assert rc == 0
    preds = np.array([float(x) for x in out.getvalue().split()])
    assert np.abs(preds - b.predict(X[:7])).max() <= TOL
    snap = json.loads(err.getvalue())
    assert snap["requests"] == 7


def test_cli_serve_warm_buckets(cat_booster, tmp_path):
    from lightgbm_tpu.__main__ import _serve

    X, b = cat_booster
    path = os.path.join(str(tmp_path), "serve_warm.npz")
    pack_booster(b).save(path)
    lines = "\n".join(",".join(f"{v:.6f}" for v in X[i]) for i in range(3))
    out, err = io.StringIO(), io.StringIO()
    rc = _serve(path, {"warm_buckets": "true", "max_bucket": "8",
                       "show_stats": "true"},
                stdin=io.StringIO(lines + "\n"), stdout=out, stderr=err)
    assert rc == 0
    preds = np.array([float(x) for x in out.getvalue().split()])
    assert np.abs(preds - b.predict(X[:3])).max() <= TOL
    err_lines = err.getvalue().strip().splitlines()
    assert "warmed 4" in err_lines[0]        # ladder 1,2,4,8
    snap = json.loads(err_lines[-1])
    assert snap["compile_cache"]["warmed_buckets"] == 4
    # the request traffic itself compiled nothing new
    assert snap["compile_cache"]["num_compiles"] == 4


def test_cli_serve_json_and_error_lines(mc_booster, tmp_path):
    from lightgbm_tpu.__main__ import _serve

    X, b = mc_booster
    path = os.path.join(str(tmp_path), "serve_mc.npz")
    pack_booster(b).save(path)
    rows = [json.dumps(list(X[i])) for i in range(3)]
    rows.insert(1, "not,a,number,row")       # malformed request mid-stream
    out = io.StringIO()
    rc = _serve(path, {"output_format": "json"},
                stdin=io.StringIO("\n".join(rows) + "\n"),
                stdout=out, stderr=io.StringIO())
    assert rc == 0
    emitted = out.getvalue().strip().splitlines()
    assert len(emitted) == 4
    assert emitted[1].startswith("ERROR:")   # order preserved, stream lives
    ok = np.array([json.loads(emitted[i]) for i in (0, 2, 3)])
    assert np.abs(ok - b.predict(X[:3])).max() <= TOL


# ---------------------------------------------------------------------------
# satellite regressions
# ---------------------------------------------------------------------------
def test_convergence_loop_bounded_on_malformed_tree():
    """predict_tree_binned(max_depth_cap=None) terminates on a cyclic
    tree instead of spinning the while_loop forever."""
    import jax.numpy as jnp

    from lightgbm_tpu.models.tree import Tree
    from lightgbm_tpu.ops.predict import predict_tree_binned

    m = 5
    tree = Tree(
        split_feature=jnp.zeros(m, jnp.int32),
        split_bin=jnp.zeros(m, jnp.int32),
        left=jnp.zeros(m, jnp.int32),        # every child edge -> root
        right=jnp.zeros(m, jnp.int32),
        leaf_value=jnp.zeros(m, jnp.float32),
        is_leaf=jnp.zeros(m, bool),          # no leaf ever closes the path
        count=jnp.zeros(m, jnp.float32),
        split_gain=jnp.zeros(m, jnp.float32),
        num_leaves=jnp.int32(0),
    )
    bins = jnp.zeros((4, 2), jnp.int32)
    vals = predict_tree_binned(tree, bins, max_depth_cap=None)
    assert np.asarray(vals).shape == (4,)    # returned: bounded by capacity


def test_wave_schedule_refuses_what_no_grower_runs():
    """What the encoded width's collision check caught lives in the value:
    a width past 512, an exact tail without its cap (or a cap without the
    exact tail), an unknown tail; and where ``num_leaves`` is known, a cap
    that does not exceed it."""
    import jax.numpy as jnp

    from lightgbm_tpu.models.spec import STRICT, WaveSchedule
    from lightgbm_tpu.models.tree import grow_tree, wave_extent

    with pytest.raises(ValueError, match="width"):
        WaveSchedule(2000, "greedy")
    with pytest.raises(ValueError, match="width"):
        WaveSchedule(0, "half")
    with pytest.raises(ValueError, match="cap_leaves"):
        WaveSchedule(42, "exact")
    with pytest.raises(ValueError, match="cap_leaves"):
        WaveSchedule(42, "greedy", 526)
    with pytest.raises(ValueError, match="tail"):
        WaveSchedule(42, "eager")
    with pytest.raises(ValueError, match="strict"):
        WaveSchedule(42, "strict")
    assert STRICT == WaveSchedule(1, "strict")
    assert wave_extent(WaveSchedule(42, "exact", 62), 31) == (62, 42)
    assert wave_extent(WaveSchedule(42, "half"), 31) == (31, 30)
    with pytest.raises(ValueError, match="num_leaves=31"):
        grow_tree(jnp.zeros((4, 2), jnp.int32), None, None, None,
                  num_leaves=31, num_bins=256, max_depth=-1,
                  wave=WaveSchedule(42, "exact", 31))


def test_fused_part_kernel_has_no_hist_dtype_param():
    import inspect

    from lightgbm_tpu.ops import histogram_pallas as hp

    sig = inspect.signature(hp._fused_part_kernel)
    assert "hist_dtype" not in sig.parameters
