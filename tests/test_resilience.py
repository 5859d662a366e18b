"""Serving resilience: faults, admission control, tenancy, hot swap.

Covers the r12 acceptance surface: deterministic fault injection at
every site (device error mid-predict, corrupt artifact, stalled compile,
clock skew), admission control shedding with typed ``Overloaded``
rejections, heap-ordered deadline expiry, thread-safe stats, and the
ModelBank deploy/swap/rollback lifecycle — including the ingest-
rejection round-trip per corrupted artifact field, where the previous
version must keep serving bit-identically.

Everything runs on mocked/injected clocks and hit-count-triggered
faults: zero sleeps, zero randomness in the failure points.
"""

import io
import json
import os
import signal
import threading

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.serving import (
    DeviceProgramError,
    FaultError,
    FaultInjector,
    FaultSpec,
    MicroBatcher,
    ModelBank,
    Overloaded,
    PackedForest,
    PredictorRuntime,
    RequestTimeout,
    ServingStats,
    SwapRejected,
    enable_persistent_cache,
    pack_booster,
)

TOL = 1e-6


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# fixtures (tiny models, small buckets: CPU compiles dominate wall time)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def served_models(small_regression, tmp_path_factory):
    """(X, booster_v1, v1_path, v2_path): two same-feature-count models
    with DIFFERENT predictions, saved as .npz serving artifacts."""
    X, y = small_regression
    d = tmp_path_factory.mktemp("resilience")
    b1 = lgb.train(
        {"objective": "regression", "num_leaves": 15, "verbosity": -1},
        lgb.Dataset(X, label=y), num_boost_round=10)
    b2 = lgb.train(
        {"objective": "regression", "num_leaves": 7, "verbosity": -1},
        lgb.Dataset(X, label=np.asarray(X[:, 0], np.float64)),
        num_boost_round=4)
    v1, v2 = str(d / "v1.npz"), str(d / "v2.npz")
    pack_booster(b1).save(v1)
    pack_booster(b2).save(v2)
    return X, b1, v1, v2


@pytest.fixture()
def reg_runtime(served_models):
    _, _, v1, _ = served_models
    return PredictorRuntime(PackedForest.load(v1), max_bucket=64)


def _bank(**kw):
    kw.setdefault("max_bucket", 16)
    kw.setdefault("canary_rows", 4)
    return ModelBank(**kw)


# ---------------------------------------------------------------------------
# fault injector semantics
# ---------------------------------------------------------------------------
def test_fault_spec_semantics():
    inj = FaultInjector()
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultSpec("bogus_site")
    with pytest.raises(ValueError, match="unknown fault site"):
        inj.check("bogus_site")
    inj.arm("device_predict", after=2, times=2, message="boom")
    inj.check("device_predict")               # hit 1: clean
    inj.check("device_predict")               # hit 2: clean
    for _ in range(2):                        # hits 3-4: fire
        with pytest.raises(FaultError, match="device_predict: boom"):
            inj.check("device_predict")
    inj.check("device_predict")               # times exhausted: clean
    snap = inj.snapshot()
    assert snap["hits"]["device_predict"] == 5
    assert snap["fired"]["device_predict"] == 2
    inj.disarm_all()
    inj.arm("artifact_load", times=-1)        # -1 = forever
    for _ in range(3):
        with pytest.raises(FaultError):
            inj.check("artifact_load")


def test_fault_compile_stall_and_clock_skew():
    inj = FaultInjector([FaultSpec("compile", stall_s=7.5)])
    assert inj.check("compile") == 7.5        # returned, not raised
    assert inj.check("compile") == 0.0        # single-shot
    clk = _Clock()
    skewed = inj.wrap_clock(clk)
    assert skewed() == 0.0                    # nothing armed: passthrough
    inj.arm("clock", after=inj.hits["clock"], times=-1, skew_s=60.0)
    clk.t = 1.0
    assert skewed() == 61.0                   # every later read skewed
    assert inj.fired["clock"] >= 1


def test_runtime_device_fault_raises_then_recovers(served_models):
    X, _, v1, _ = served_models
    inj = FaultInjector()
    rt = PredictorRuntime(PackedForest.load(v1), max_bucket=16,
                          faults=inj)
    want = rt.predict(X[:4])
    inj.arm("device_predict", message="dropped core")
    with pytest.raises(FaultError, match="dropped core"):
        rt.predict(X[:4])
    assert np.array_equal(rt.predict(X[:4]), want)   # next dispatch fine


def test_microbatcher_fallback_on_device_fault(served_models):
    """A device error mid-predict degrades to the numpy predictor —
    traffic is answered, not errored (and the fault is counted)."""
    X, b, v1, _ = served_models
    inj = FaultInjector([FaultSpec("device_predict", times=1)])
    rt = PredictorRuntime(PackedForest.load(v1), max_bucket=16,
                          faults=inj)
    mb = MicroBatcher(rt, max_batch=4, max_delay_ms=0.0, clock=_Clock())
    hs = [mb.submit(X[i]) for i in range(4)]
    assert mb.pump() == 1
    got = np.array([h.result() for h in hs])
    assert np.abs(got - b.predict(X[:4])).max() <= TOL
    assert rt.stats.snapshot()["fallbacks"] == 4


def _refuse_to_compile(*_a, **_k):
    import jax

    def fn(*_args):
        raise NotImplementedError("Unsupported cast: uint8 -> float32")
    return jax.jit(fn)


def test_unbuildable_program_is_never_served_from_the_host(served_models,
                                                           monkeypatch):
    """A program that cannot be built (traced, lowered, compiled) is a
    deterministic failure: warm() raises, the requests fail with
    DeviceProgramError, and nothing is answered from the numpy oracle —
    before r21 the queue served such a model 100% from the host."""
    X, _, v1, _ = served_models
    rt = PredictorRuntime(PackedForest.load(v1), max_bucket=16)
    monkeypatch.setattr(rt, "_build_fn", _refuse_to_compile)
    with pytest.raises(DeviceProgramError, match="Unsupported cast"):
        rt.warm()
    mb = MicroBatcher(rt, max_batch=4, max_delay_ms=0.0, clock=_Clock())
    hs = [mb.submit(X[i]) for i in range(4)]
    assert mb.pump() == 1
    for h in hs:
        with pytest.raises(DeviceProgramError):
            h.result()
    assert rt.stats.snapshot()["fallbacks"] == 0


def _dropped_core(*_args):
    raise RuntimeError("device lost")


def test_built_program_degrades_when_it_fails_running(served_models):
    """... whereas a compiled program that raises while RUNNING degrades
    to the oracle: that is the transient fault the fallback is for."""
    X, b, v1, _ = served_models
    rt = PredictorRuntime(PackedForest.load(v1), max_bucket=16)
    want = rt.predict(X[:4])
    (key,) = rt._cache
    rt._cache[key] = _dropped_core
    mb = MicroBatcher(rt, max_batch=4, max_delay_ms=0.0, clock=_Clock())
    hs = [mb.submit(X[i]) for i in range(4)]
    mb.pump()
    got = np.array([h.result() for h in hs])
    assert np.abs(got - want).max() <= TOL
    assert rt.stats.snapshot()["fallbacks"] == 4


def test_first_run_fault_of_a_rebuilt_bucket_degrades_too(served_models,
                                                          monkeypatch):
    """The fault may hit a program's very FIRST run — an un-warmed
    bucket, or one the LRU evicted and rebuilt.  The program compiled,
    so this is still the transient fault: answered from the oracle, and
    the next dispatch is back on the device."""
    X, b, v1, _ = served_models
    rt = PredictorRuntime(PackedForest.load(v1), max_bucket=16,
                          max_cache_entries=1)
    want = rt.predict(X[:4])
    rt.predict(X[:8])                              # evicts bucket 4
    assert [k[0] for k in rt._cache] == [8]

    class _CompilesThenDrops:
        def lower(self, *_shapes):
            return self

        def compile(self):
            return _dropped_core

    monkeypatch.setattr(rt, "_build_fn",
                        lambda *a, **k: _CompilesThenDrops())
    mb = MicroBatcher(rt, max_batch=4, max_delay_ms=0.0, clock=_Clock())
    hs = [mb.submit(X[i]) for i in range(4)]
    mb.pump()
    got = np.array([h.result() for h in hs])
    assert np.abs(got - want).max() <= TOL
    assert rt.stats.snapshot()["fallbacks"] == 4
    monkeypatch.undo()
    rt._cache.clear()                              # the core came back
    assert np.array_equal(rt.predict(X[:4]), want)


@pytest.mark.parametrize("warm,stage", [(True, "warm"), (False, "canary")])
def test_bank_rejects_a_model_whose_program_cannot_compile(
        served_models, monkeypatch, warm, stage):
    X, _, v1, v2 = served_models
    bank = _bank(warm_on_deploy=warm)
    bank.deploy("m", v1)
    before = bank.predict("m", X[:4])
    monkeypatch.setattr(PredictorRuntime, "_build_fn",
                        lambda self, *a, **k: _refuse_to_compile())
    with pytest.raises(SwapRejected) as ei:
        bank.deploy("m", v2)
    assert ei.value.stage == stage
    monkeypatch.undo()
    assert bank.version("m") == "v1"               # old version still serves
    assert np.array_equal(bank.predict("m", X[:4]), before)


# ---------------------------------------------------------------------------
# admission control / backpressure
# ---------------------------------------------------------------------------
def test_depth_policy_sheds_typed_overloaded(served_models, reg_runtime):
    X, _, _, _ = served_models
    mb = MicroBatcher(reg_runtime, max_batch=8, max_delay_ms=1e6,
                      clock=_Clock(), max_queue_depth=2,
                      shed_policy="depth")
    h1, h2 = mb.submit(X[0]), mb.submit(X[1])
    h3 = mb.submit(X[2])
    assert h3.done and not h1.done and not h2.done
    with pytest.raises(Overloaded, match="queue full"):
        h3.result()
    assert mb.pending_count() == 2
    snap = reg_runtime.stats.snapshot()
    assert snap["sheds"] >= 1
    mb.flush()
    assert h1.result() is not None and h2.result() is not None


def test_deadline_policy_sheds_predicted_miss(served_models, reg_runtime):
    """With a 10 ms dispatch hint, a 5 ms deadline is predicted dead on
    arrival and sheds; a 50 ms deadline is admitted."""
    X, _, _, _ = served_models
    mb = MicroBatcher(reg_runtime, max_batch=4, max_delay_ms=0.0,
                      clock=_Clock(), shed_policy="deadline",
                      service_time_hint_ms=10.0)
    doomed = mb.submit(X[0], timeout_ms=5.0)
    assert doomed.done
    with pytest.raises(Overloaded, match="predicted queue wait"):
        doomed.result()
    fine = mb.submit(X[1], timeout_ms=50.0)
    assert not fine.done
    assert mb.predicted_wait_s() > 0.0


def test_shed_policy_off_admits_everything(served_models, reg_runtime):
    X, _, _, _ = served_models
    mb = MicroBatcher(reg_runtime, max_batch=8, max_delay_ms=1e6,
                      clock=_Clock(), max_queue_depth=2,
                      shed_policy="off", service_time_hint_ms=100.0)
    hs = [mb.submit(X[i], timeout_ms=0.001) for i in range(5)]
    assert not any(h.done for h in hs)        # nothing shed
    assert mb.pending_count() == 5


def test_deadline_model_inactive_under_mocked_clock(served_models,
                                                    reg_runtime):
    """Default policy + mocked clock (dt == 0 dispatches): the EWMA
    stays 0 and the predictor never sheds — the r6-era tests' contract."""
    X, _, _, _ = served_models
    mb = MicroBatcher(reg_runtime, max_batch=2, max_delay_ms=0.0,
                      clock=_Clock(), timeout_ms=0.01)
    hs = [mb.submit(X[i]) for i in range(4)]
    assert not any(h.done for h in hs)
    mb.pump()
    assert all(h.done for h in hs)
    assert mb.predicted_wait_s() == 0.0


def test_ewma_learns_dispatch_time_through_clock(served_models,
                                                 reg_runtime):
    X, _, _, _ = served_models

    class _Ticking(_Clock):
        def __call__(self):
            self.t += 0.001               # every read advances 1 ms
            return self.t

    mb = MicroBatcher(reg_runtime, max_batch=2, max_delay_ms=0.0,
                      clock=_Ticking())
    mb.submit(X[0])
    mb.submit(X[1])
    mb.pump()
    assert mb.predicted_wait_s() > 0.0    # measured a nonzero dispatch


def test_invalid_admission_config_rejected(reg_runtime):
    with pytest.raises(ValueError, match="shed_policy"):
        MicroBatcher(reg_runtime, shed_policy="sometimes")
    with pytest.raises(ValueError, match="max_queue_depth"):
        MicroBatcher(reg_runtime, max_queue_depth=0)


# ---------------------------------------------------------------------------
# heap-ordered deadline expiry
# ---------------------------------------------------------------------------
def test_heap_expiry_pops_only_due_requests(served_models, reg_runtime):
    """30 staggered deadlines; advancing past 15 of them expires exactly
    those 15 (heap pops, no whole-queue scan) and the remainder serve in
    order."""
    X, b, _, _ = served_models
    clk = _Clock()
    mb = MicroBatcher(reg_runtime, max_batch=64, max_delay_ms=1e6,
                      clock=clk)
    hs = [mb.submit(X[i], timeout_ms=float(i + 1)) for i in range(30)]
    t0 = reg_runtime.stats.snapshot()["timeouts"]
    clk.t = 0.0155                        # deadlines 1..15 ms are due
    assert mb.pump() == 0
    assert reg_runtime.stats.snapshot()["timeouts"] - t0 == 15
    assert mb.pending_count() == 15
    assert not mb._exp_heap or mb._exp_heap[0][0] >= clk.t
    mb.flush()
    for i, h in enumerate(hs):
        if i < 15:
            with pytest.raises(RequestTimeout):
                h.result()
        else:
            assert abs(h.result() - b.predict(X[i:i + 1])[0]) <= TOL


def test_expiry_tombstones_never_double_count(served_models, reg_runtime):
    X, _, _, _ = served_models
    clk = _Clock()
    mb = MicroBatcher(reg_runtime, max_batch=4, max_delay_ms=1e6,
                      clock=clk)
    mb.submit(X[0], timeout_ms=1.0)
    hs = [mb.submit(X[i], timeout_ms=1e6) for i in range(1, 5)]
    clk.t = 0.002
    mb.pump()                             # expires 1, dispatches the 4
    assert all(h.done for h in hs)
    assert mb.pending_count() == 0
    assert mb.pump() == 0 and mb.flush() == 0     # queue + heap drained


def test_clock_skew_fault_drives_expiry(served_models, reg_runtime):
    """The ``clock`` fault site: a skew injected between submit and pump
    expires in-queue requests — time discontinuities degrade to typed
    timeouts, not wrong answers."""
    X, _, _, _ = served_models
    inj = FaultInjector()
    clk = _Clock()
    mb = MicroBatcher(reg_runtime, max_batch=8, max_delay_ms=1e6,
                      timeout_ms=5.0, clock=inj.wrap_clock(clk))
    h = mb.submit(X[0])
    inj.arm("clock", after=inj.hits["clock"], times=-1, skew_s=60.0)
    mb.pump()
    with pytest.raises(RequestTimeout):
        h.result()


# ---------------------------------------------------------------------------
# stats under concurrent writers
# ---------------------------------------------------------------------------
def test_stats_concurrent_writers_exact_counts():
    stats = ServingStats()
    n, workers = 500, 8
    errors = []

    def hammer(k):
        try:
            for i in range(n):
                stats.record_request()
                stats.record_dispatch(bucket=1 << (k % 4), rows=1,
                                      padded=1, latency_s=1e-4)
                stats.record_cache(bucket=1 << (k % 4), hit=i % 2 == 0)
                stats.record_shed()
                stats.record_timeout()
                stats.record_fallback()
                stats.record_batch(queue_latency_s=1e-4)
                if i % 50 == 0:
                    json.dumps(stats.snapshot())   # reader mid-write
        except Exception as e:                     # noqa: BLE001
            errors.append(e)

    ts = [threading.Thread(target=hammer, args=(k,))
          for k in range(workers)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errors
    snap = stats.snapshot()
    total = n * workers
    assert snap["requests"] == total
    assert snap["sheds"] == total
    assert snap["timeouts"] == total
    assert snap["fallbacks"] == total
    assert snap["batched_dispatches"] == total
    assert sum(b["dispatches"] for b in snap["buckets"]) == total
    assert sum(b["rows"] for b in snap["buckets"]) == total
    hits = sum(b["cache_hits"] for b in snap["buckets"])
    misses = sum(b["cache_misses"] for b in snap["buckets"])
    assert hits + misses == total


# ---------------------------------------------------------------------------
# ModelBank: tenancy, hot swap, rollback
# ---------------------------------------------------------------------------
def test_bank_deploy_predict_and_snapshot(served_models):
    X, b, v1, _ = served_models
    bank = _bank()
    rep = bank.deploy("m", v1)
    assert rep["ok"] and rep["version"] == "v1"
    assert rep["canary"]["rows"] == 4
    assert np.abs(bank.predict("m", X[:20]) - b.predict(X[:20])).max() \
        <= TOL
    assert bank.names() == ["m"] and bank.version("m") == "v1"
    snap = bank.snapshot()
    assert snap["models"]["m"]["deploys"] == 1
    assert snap["models"]["m"]["swap_history"][-1]["stage"] == "flipped"
    json.dumps(snap)
    with pytest.raises(KeyError, match="no model"):
        bank.runtime("ghost")


_CORRUPTIONS = {
    "cycle": lambda p: p.left.__setitem__((0, 0), 0),
    "dangling": lambda p: p.left.__setitem__((0, 0),
                                             p.left.shape[1] + 9),
    "bad_feature": lambda p: p.split_feature.__setitem__(
        (0, 0), p.num_feature() + 3),
    "nonfinite_leaf": lambda p: p.leaf_value.__setitem__(
        (0, int(np.argmax(p.is_leaf[0]))), np.nan),
}


@pytest.mark.parametrize("field", sorted(_CORRUPTIONS))
def test_ingest_rejection_rollback_roundtrip(served_models, tmp_path,
                                             field):
    """Satellite 4: corrupt each validated field, attempt the swap, and
    assert the PREVIOUS version keeps serving bit-identically."""
    import copy

    X, _, v1, _ = served_models
    bank = _bank()
    bank.deploy("m", v1)
    probe = X[:16]
    baseline = bank.predict("m", probe)

    bad = copy.deepcopy(PackedForest.load(v1))
    _CORRUPTIONS[field](bad)
    bad_path = str(tmp_path / f"bad_{field}.npz")
    bad.save(bad_path)                    # save() does not re-validate
    with pytest.raises(SwapRejected) as ei:
        bank.deploy("m", bad_path)
    assert ei.value.stage == "ingest"
    assert bank.version("m") == "v1"
    assert np.array_equal(bank.predict("m", probe), baseline)
    hist = bank.snapshot()["models"]["m"]["swap_history"]
    assert hist[-1]["ok"] is False and "error" in hist[-1]


def test_bank_feature_count_mismatch_rejected(served_models, tmp_path):
    X, _, v1, _ = served_models
    bank = _bank()
    bank.deploy("m", v1)
    rng = np.random.default_rng(0)
    Xw = rng.normal(size=(300, X.shape[1] + 2))
    bw = lgb.train({"objective": "regression", "num_leaves": 7,
                    "verbosity": -1},
                   lgb.Dataset(Xw, label=Xw[:, 0]), num_boost_round=3)
    wide = str(tmp_path / "wide.npz")
    pack_booster(bw).save(wide)
    with pytest.raises(SwapRejected, match="feature count changed"):
        bank.deploy("m", wide)
    assert bank.version("m") == "v1"


def test_bank_artifact_load_fault_rejects(served_models):
    _, _, v1, _ = served_models
    inj = FaultInjector()
    bank = _bank(faults=inj)
    bank.deploy("m", v1)
    baseline_rt = bank.runtime("m")
    inj.arm("artifact_load", message="disk ate the npz")
    with pytest.raises(SwapRejected, match="disk ate the npz"):
        bank.deploy("m", v1)
    assert bank.runtime("m") is baseline_rt


def test_bank_canary_catches_device_fault(served_models):
    """A device fault during the post-build canary rejects the swap —
    the new runtime never sees traffic, the old one never stopped."""
    X, _, v1, v2 = served_models
    inj = FaultInjector()
    bank = _bank(faults=inj)
    bank.deploy("m", v1)
    baseline = bank.predict("m", X[:8])
    inj.arm("device_predict", times=-1, message="canary died")
    with pytest.raises(SwapRejected) as ei:
        bank.deploy("m", v2)
    assert ei.value.stage == "canary"
    inj.disarm_all()
    assert bank.version("m") == "v1"
    assert np.array_equal(bank.predict("m", X[:8]), baseline)


def test_bank_stalled_compile_aborts_swap(served_models):
    _, _, v1, v2 = served_models
    inj = FaultInjector()
    bank = _bank(faults=inj, compile_timeout_s=0.5, clock=_Clock(),
                 canary_rows=0)
    bank.deploy("m", v1)                  # clean: 0 elapsed on the mock
    inj.arm("compile", stall_s=10.0)
    with pytest.raises(SwapRejected, match="compile stalled"):
        bank.deploy("m", v2)
    assert bank.version("m") == "v1"


def test_bank_hot_swap_atomic_for_queued_traffic(served_models):
    """Requests queued BEFORE the flip dispatch on the runtime resolved
    AT dispatch time — the bank-provider MicroBatcher is the swap point,
    and nothing in flight errors."""
    X, _, v1, v2 = served_models
    bank = _bank()
    bank.deploy("m", v1)
    v2_ref = PredictorRuntime(PackedForest.load(v2), max_bucket=16)
    mb = bank.batcher("m", max_batch=4, max_delay_ms=0.0, clock=_Clock())
    hs = [mb.submit(X[i]) for i in range(3)]
    bank.deploy("m", v2)                  # flip while 3 are queued
    assert mb.pump() == 1
    got = np.array([h.result() for h in hs])
    assert np.array_equal(got, v2_ref.predict(X[:3]))   # served on v2
    with pytest.raises(KeyError):
        bank.batcher("ghost")


def test_bank_rollback_bit_identical(served_models):
    X, _, v1, v2 = served_models
    bank = _bank()
    bank.deploy("m", v1)
    probe = X[:16]
    baseline = bank.predict("m", probe)
    bank.deploy("m", v2)
    assert bank.version("m") == "v2"
    assert not np.array_equal(bank.predict("m", probe), baseline)
    rep = bank.rollback("m")
    assert rep["version"] == "v1"
    # the v1 runtime (and compiled programs) never went away: outputs
    # are byte-for-byte the pre-swap ones
    assert np.array_equal(bank.predict("m", probe), baseline)
    bank.rollback("m")                    # flip-flop back to v2
    assert bank.version("m") == "v2"


def test_bank_rollback_without_previous_rejected(served_models):
    _, _, v1, _ = served_models
    bank = _bank()
    bank.deploy("m", v1)
    with pytest.raises(SwapRejected, match="no previous version"):
        bank.rollback("m")


def test_bank_multi_tenancy_isolated_stats(served_models):
    X, _, v1, v2 = served_models
    bank = _bank()
    bank.deploy("a", v1)
    bank.deploy("b", v2)
    bank.predict("a", X[:4])
    snap = bank.snapshot()
    a, b = snap["models"]["a"]["stats"], snap["models"]["b"]["stats"]
    assert sum(e["dispatches"] for e in a["buckets"]) >= 1
    assert sum(e["dispatches"] for e in b["buckets"]) == 1   # canary only
    assert sorted(snap["models"]) == ["a", "b"]


# ---------------------------------------------------------------------------
# warm restarts: manifest + persistent compile cache
# ---------------------------------------------------------------------------
def test_warm_manifest_roundtrip(served_models, tmp_path):
    X, _, v1, _ = served_models
    bank = _bank(max_bucket=8, warm_on_deploy=True)
    bank.deploy("m", v1)
    want = bank.predict("m", X[:8])
    manifest = str(tmp_path / "warm.json")
    bank.save_warm_manifest(manifest)

    bank2 = _bank(max_bucket=8)
    rep = bank2.restore_warm_manifest(manifest)
    assert rep["models"] == 1 and rep["skipped"] == []
    rt2 = bank2.runtime("m")
    assert len(rt2._cache) == len(rt2.buckets)     # ladder is warm
    n = rt2.num_compiles
    got = bank2.predict("m", X[:8])
    assert rt2.num_compiles == n                   # zero traffic compiles
    assert np.abs(got - want).max() <= TOL
    assert bank2.version("m") == "v1"


def test_warm_manifest_version_gate(tmp_path):
    p = str(tmp_path / "future.json")
    with open(p, "w") as f:
        json.dump({"format_version": 99, "models": []}, f)
    with pytest.raises(ValueError, match="newer than supported"):
        _bank().restore_warm_manifest(p)


@pytest.mark.parametrize("env_dir", [None, "/some/dir"])
def test_one_compile_cache_rule(tmp_path, monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins and nothing sets another; unset,
    the cache is <checkout>/.jaxcache — enable_persistent_cache and
    ModelBank(cache_dir=) only report the directory in force."""
    import jax

    import lightgbm_tpu
    from lightgbm_tpu.utils.compile_cache import (ENV_VAR,
                                                  compile_cache_dir)

    checkout = os.path.dirname(os.path.dirname(lightgbm_tpu.__file__))
    if env_dir is None:
        monkeypatch.delenv(ENV_VAR, raising=False)
        want = os.path.join(checkout, ".jaxcache")
    else:
        monkeypatch.setenv(ENV_VAR, env_dir)
        want = env_dir
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache_dir() == want
        with pytest.warns(UserWarning, match="ignored"):
            assert enable_persistent_cache(str(tmp_path / "other")) == want
        assert _bank().cache_dir == want
        if env_dir is None:
            assert jax.config.jax_compilation_cache_dir == want
        else:   # the variable is JAX's to read: no code path set a dir
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# ---------------------------------------------------------------------------
# serve CLI: key validation, control lines, SIGTERM drain
# ---------------------------------------------------------------------------
def _run_serve(path, cfg, lines):
    from lightgbm_tpu.__main__ import _serve

    out, err = io.StringIO(), io.StringIO()
    rc = _serve(path, dict(cfg), stdin=iter(lines), stdout=out,
                stderr=err)
    return rc, out.getvalue().splitlines(), err.getvalue()


def test_cli_serve_rejects_unknown_and_invalid_keys(served_models):
    from lightgbm_tpu.__main__ import _serve

    _, _, v1, _ = served_models
    for cfg, msg in (
            ({"max_queue_dpeth": "4"}, "unknown key"),
            ({"shed_policy": "sometimes"}, "shed_policy"),
            ({"max_queue_depth": "0"}, "max_queue_depth"),
            ({"max_queue_depth": "lots"}, "max_queue_depth"),
            ({"canary_rows": "-1"}, "canary_rows"),
    ):
        with pytest.raises(SystemExit, match=msg):
            _serve(v1, cfg, stdin=iter(()), stdout=io.StringIO(),
                   stderr=io.StringIO())


def test_cli_serve_control_lines_swap_rollback_stats(served_models):
    X, _, v1, v2 = served_models
    row = ",".join(f"{x:.8g}" for x in X[0])
    # max_batch=1: each row dispatches (and binds to the ACTIVE version)
    # before the next control line is read
    rc, out, err = _run_serve(v1, {"canary_rows": "4",
                                   "max_batch": "1"}, [
        f"{row}\n",
        "!stats\n",
        f"!swap {v2}\n",
        f"{row}\n",
        "!rollback\n",
        f"{row}\n",
        "!frobnicate\n",
    ])
    assert rc == 0
    assert len(out) == 3
    assert out[0] != out[1]               # v2 answers differently
    assert out[0] == out[2]               # rollback restores exactly
    assert "swapped default -> v2" in err
    assert "rolled back default -> v1" in err
    assert "unknown control" in err
    stats_line = [ln for ln in err.splitlines()
                  if ln.startswith("{")][0]
    assert "requests" in json.loads(stats_line)


def test_cli_serve_rejected_swap_keeps_serving(served_models, tmp_path):
    import copy

    X, _, v1, _ = served_models
    bad = copy.deepcopy(PackedForest.load(v1))
    _CORRUPTIONS["cycle"](bad)
    bad_path = str(tmp_path / "bad.npz")
    bad.save(bad_path)
    row = ",".join(f"{x:.8g}" for x in X[0])
    rc, out, err = _run_serve(v1, {}, [
        f"{row}\n",
        f"!swap {bad_path}\n",
        f"{row}\n",
    ])
    assert rc == 0
    assert out[0] == out[1]               # old version never blinked
    assert "swap rejected at ingest" in err


def test_cli_serve_sigterm_drains_gracefully(served_models):
    """SIGTERM mid-stream: stop admitting, flush in-flight, final stats
    snapshot — the admitted requests are answered, the post-signal line
    is not."""
    X, _, v1, _ = served_models
    rows = [",".join(f"{x:.8g}" for x in X[i]) for i in range(3)]

    def feed():
        yield rows[0] + "\n"
        yield rows[1] + "\n"
        signal.raise_signal(signal.SIGTERM)
        yield rows[2] + "\n"              # read while draining: dropped

    rc, out, err = _run_serve(v1, {}, feed())
    assert rc == 0
    assert len(out) == 2                  # both admitted requests answered
    assert "ERROR" not in "".join(out)
    assert "drained on SIGTERM" in err
    final = json.loads(err.splitlines()[-1])
    assert final["requests"] == 2
    # the process-level handler is restored after the drain
    assert signal.getsignal(signal.SIGTERM) != signal.SIG_IGN


# ---------------------------------------------------------------------------
# SLO budget models (pure arithmetic; also run in the default lint pass)
# ---------------------------------------------------------------------------
def test_serve_queue_model_regimes():
    from lightgbm_tpu.analysis.budgets import serve_queue_model

    stable = serve_queue_model(1000.0, dispatch_ms=2.0, max_batch=128)
    assert stable["utilization"] < 1.0
    assert stable["miss_frac"] == 0.0 and stable["shed_frac"] == 0.0
    over_off = serve_queue_model(2 * 64000.0, 2.0, shed_policy="off")
    assert over_off["miss_frac"] == 1.0 and over_off["shed_frac"] == 0.0
    over_on = serve_queue_model(2 * 64000.0, 2.0, shed_policy="deadline")
    assert over_on["miss_frac"] == 0.0
    assert abs(over_on["shed_frac"] - 0.5) < 1e-9   # 1 - 1/util at 2x
    assert abs(over_on["served_frac"] - 0.5) < 1e-9


def test_serve_fault_p99_capped_by_shedding():
    from lightgbm_tpu.analysis.budgets import serve_fault_p99_model

    shed = serve_fault_p99_model(shedding=True)
    unshed = serve_fault_p99_model(shedding=False)
    assert shed["fault_p99_ms"] < unshed["fault_p99_ms"]
    assert shed["fault_p99_ms"] == pytest.approx(52.0)   # deadline+dispatch
    assert shed["inflation_x"] <= 8.0


def test_serve_slo_budgets_all_green_and_wired():
    from lightgbm_tpu.analysis.budgets import (SERVE_SLO_BUDGETS,
                                               check_serve_slo_budgets,
                                               serve_slo_budget_by_name)

    res = check_serve_slo_budgets()
    assert len(res) == len(SERVE_SLO_BUDGETS) == 12
    assert all(r["ok"] for r in res)
    names = {r["name"] for r in res}
    assert {"serve_shed_before_miss", "serve_fault_p99_inflation",
            "serve_int8_models_per_byte", "serve_dp_speedup_d4",
            "serve_fused_launch_drop", "serve_fused_vmem_int8",
            "serve_fused_no_f32_table_int8"} \
        <= names
    assert serve_slo_budget_by_name(
        "serve_shed_before_miss").check()["ok"]
    with pytest.raises(KeyError):
        serve_slo_budget_by_name("nope")
