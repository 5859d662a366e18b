"""The program's one recorder (``utils/profiling.py``): spans, self time,
the ring, build seconds by span, the profiler's clock, and the spans the
program itself opens around binning and training."""

import glob
import os
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.utils import profiling


class FakeClock:
    """Every read advances by one second."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


def _tiny_data(rows=3000, features=6, seed=1):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, features)).astype(np.float32)
    return X, (X[:, 0] + X[:, 1] > 0).astype(np.float32)


def test_nesting_and_self_time_on_an_injected_clock():
    rec = profiling.Recorder(clock=FakeClock())
    with rec.span("outer", rows=5) as fields:      # starts at 1
        with rec.span("inner"):                    # 2 .. 3
            pass
        with rec.span("inner"):                    # 4 .. 5
            pass
        fields["late"] = True
    snap = rec.snapshot()                          # outer ends at 6
    outer, inner = snap["spans"]["outer"], snap["spans"]["inner"]
    assert (outer["count"], outer["total_s"], outer["self_s"]) == (1, 5, 3)
    assert (inner["count"], inner["total_s"], inner["self_s"]) == (2, 2, 2)
    assert inner["max_s"] == 1.0 and outer["build_s"] == 0.0
    ring = snap["ring"]
    assert [r["name"] for r in ring] == ["inner", "inner", "outer"]
    assert ring[2]["parent"] is None
    assert ring[0]["parent"] == ring[1]["parent"] == ring[2]["id"]
    assert (ring[2]["start"], ring[2]["end"]) == (1.0, 6.0)
    assert ring[2]["fields"] == {"rows": 5, "late": True}


def test_a_span_that_raises_is_closed_and_recorded():
    rec = profiling.Recorder(clock=FakeClock())
    with pytest.raises(ZeroDivisionError):
        with rec.span("outer"):
            with rec.span("inner"):
                1 / 0
    with rec.span("after"):
        pass
    snap = rec.snapshot()
    assert snap["spans"]["inner"]["count"] == 1
    assert snap["ring"][-1]["name"] == "after"
    assert snap["ring"][-1]["parent"] is None      # the stack was unwound


def test_ring_is_bounded_and_aggregates_are_not():
    rec = profiling.Recorder(clock=FakeClock(), ring=8)
    for i in range(20):
        with rec.span("s", i=i):
            pass
    snap = rec.snapshot()
    assert len(snap["ring"]) == 8
    assert [r["fields"]["i"] for r in snap["ring"]] == list(range(12, 20))
    assert snap["spans"]["s"]["count"] == 20
    assert profiling.RING_SPANS == 4096


def test_facts_counts_and_reset():
    rec = profiling.Recorder(clock=FakeClock())
    rec.note("train.wave_width", 42)
    rec.note("train.wave_width", 21)               # idempotent: last wins
    rec.add("rows", 5)
    rec.add("rows", 7)
    with rec.span("s"):
        pass
    snap = rec.snapshot()
    assert snap["facts"] == {"train.wave_width": 21}
    assert snap["counts"] == {"rows": 12}
    snap["spans"]["s"]["count"] = 99               # a copy, not the state
    assert rec.snapshot()["spans"]["s"]["count"] == 1
    rec.reset()
    assert rec.snapshot() == {"spans": {}, "facts": {}, "counts": {},
                              "ring": [], "arrays": {}}


def test_build_seconds_land_on_the_span_that_was_open():
    rec = profiling.Recorder()
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum() * 3.0)
    x, smaller = jnp.ones((64, 64)), jnp.ones((32, 32))
    with rec.span("outer"):
        with rec.span("first_call"):
            f(x).block_until_ready()
        with rec.span("second_call"):
            f(x).block_until_ready()
    spans = rec.snapshot()["spans"]
    first, second = spans["first_call"], spans["second_call"]
    assert first["builds"] == 1 and 0 < first["build_s"] <= first["total_s"]
    assert second["builds"] == 0 and second["build_s"] == 0.0
    # the innermost open span takes them, its parent does not
    assert spans["outer"]["builds"] == 0 and spans["outer"]["build_s"] == 0.0
    with rec.span("new_shape"):
        f(smaller).block_until_ready()
    assert rec.snapshot()["spans"]["new_shape"]["builds"] == 1


def test_threads_keep_their_own_stack_and_share_the_aggregates():
    rec = profiling.Recorder(ring=100_000)
    workers, per_worker = 8, 300
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(i):
            for _ in range(per_worker):
                with rec.span(f"outer.{i}"):
                    with rec.span("inner", worker=i):
                        rec.add("n")

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    snap = rec.snapshot()
    assert snap["counts"]["n"] == workers * per_worker
    assert snap["spans"]["inner"]["count"] == workers * per_worker
    by_id = {r["id"]: r for r in snap["ring"]}
    assert len(by_id) == 2 * workers * per_worker      # ids never repeat
    for r in snap["ring"]:
        if r["name"] == "inner":       # parent: this thread's own outer
            assert by_id[r["parent"]]["name"] == f"outer.{r['fields']['worker']}"


def test_spans_lie_on_the_profilers_clock(tmp_path):
    """Under a profiler session the program's spans are in the trace,
    nested inside the caller's own annotation, with the device's
    operations inside them: one clock for host spans and device events."""
    from jax.profiler import ProfileData

    X, y = _tiny_data()
    booster = lgb.Booster({"objective": "binary", "num_leaves": 7,
                           "verbosity": -1}, lgb.Dataset(X, label=y))
    booster.update_many(1)                         # compiles
    jax.block_until_ready(booster._pred_train)
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("test.outer"):
            booster.update_many(1)
            jax.block_until_ready(booster._pred_train)
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    spans, device = {}, []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                at = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name == "test.outer" or ev.name.startswith("lgbtpu."):
                    spans[ev.name] = at
                elif line.name.startswith("tf_XLA"):
                    device.append(at)

    def inside(a, b):
        return spans[b][0] <= spans[a][0] and spans[a][1] <= spans[b][1]

    assert inside("lgbtpu.train.update_many", "test.outer")
    for child in ("segment", "dispatch", "commit"):
        assert inside("lgbtpu.train." + child, "lgbtpu.train.update_many")
    assert any(spans["lgbtpu.train.dispatch"][0] <= s
               and e <= spans["test.outer"][1] for s, e in device)


def test_dataset_construct_spans_sum_to_it():
    profiling.reset()
    X, y = _tiny_data(rows=20_000, features=8)
    ds = lgb.Dataset(X, label=y).construct()
    snap = profiling.snapshot()
    spans = snap["spans"]
    whole = spans["lgbtpu.dataset.construct"]
    parts = [spans["lgbtpu.dataset." + k]
             for k in ("to_float", "edges", "codes", "bundle", "put")]
    assert whole["count"] == 1 and all(p["count"] == 1 for p in parts)
    assert (sum(p["total_s"] for p in parts) + whole["self_s"]
            == pytest.approx(whole["total_s"], rel=1e-9))
    assert whole["self_s"] < 0.25 * whole["total_s"]    # the parts cover it
    top = [r for r in snap["ring"] if r["name"] == "lgbtpu.dataset.construct"]
    assert top[0]["fields"] == {"rows": 20_000, "features": ds.num_feature_}
    kids = {r["name"] for r in snap["ring"] if r["parent"] == top[0]["id"]}
    assert {"lgbtpu.dataset.edges", "lgbtpu.dataset.codes",
            "lgbtpu.dataset.put"} <= kids
    ds.construct()                                 # constructed: no new span
    assert profiling.snapshot()["spans"][
        "lgbtpu.dataset.construct"]["count"] == 1


def test_training_spans_and_facts():
    profiling.reset()
    X, y = _tiny_data(rows=6000)
    booster = lgb.Booster({"objective": "binary", "num_leaves": 31,
                           "verbosity": -1}, lgb.Dataset(X, label=y))
    booster.update_many(2)
    booster.update_many(2)
    booster.update()
    snap = profiling.snapshot()
    spans, facts = snap["spans"], snap["facts"]
    assert spans["lgbtpu.train.setup"]["count"] == 1
    assert spans["lgbtpu.train.update_many"]["count"] == 2
    assert spans["lgbtpu.train.update"]["count"] == 1
    for child in ("segment", "dispatch", "commit"):
        assert spans["lgbtpu.train." + child]["count"] == 2
    # the round program is built once, inside the first dispatch
    assert spans["lgbtpu.train.dispatch"]["builds"] == 1
    assert spans["lgbtpu.train.update_many"]["builds"] == 0
    calls = [r for r in snap["ring"]
             if r["name"] == "lgbtpu.train.update_many"]
    assert [r["fields"] for r in calls] == [{"rounds": 2}, {"rounds": 2}]
    assert facts["train.wave_width"] == 30 and facts["train.features"] == 6
    assert facts["train.wave_tail"] == "exact"
    assert facts["train.overgrow_leaves"] > 31
    assert facts["train.hist_dtype"] == "f32"
    assert facts["train.rows_padded"] == 6144
    assert facts["train.num_bins"] == booster._num_bins
