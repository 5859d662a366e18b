"""The readers of what the program names: kernels by role name from the
trace, host spans and facts from the program's own recorder.  Hand-built
traces and snapshots, then the tiny cell end to end on the CPU."""

import json
import os

import pytest

from benchmark.readers import (named_roofline, program_span, trace_named,
                               trace_roofline)
from benchmark.reduce import trace as tr
from benchmark.reduce import work
from bench_helpers import REPO

WAVE = ('%lgbtpu_hist_wave.10 = (f32[28,255,126]{2,1,0:T(8,128)S(1)}, '
        's32[1,1024]{1,0}) custom-call(s32[28,1024]{1,0} %a), '
        'custom_call_target="tpu_custom_call", frontend_attributes={}')
ROOT = ('%lgbtpu_hist_root.2 = f32[28,255,3]{2,1,0:T(8,128)S(1)} '
        'custom-call(s32[28,1024]{1,0} %pad.74), '
        'custom_call_target="tpu_custom_call"')
PEAKS = {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
         "hbm_bytes_per_s": 819e9}
FACTS = {"train.wave_width": 42, "train.rows_padded": 1024,
         "train.features": 28, "train.num_bins": 255,
         "train.hist_dtype": "bf16"}
NEW_METRICS = ["hist_wave_roofline", "hist_root_roofline",
               "wave_passes_per_round", "grower_xla_pct", "binning_edges_s",
               "binning_codes_s", "binning_other_s", "update_many_host_ms",
               "program_build_s"]


def named_trace():
    """One chip, two rounds: a ``while`` from 1.0 to 9.0 holding three wave
    passes and a fusion, a root pass outside it; busy 8.2 of 10."""
    ops = [("%while.71 = (f32[8]) while(%tuple), body=%b", 1.0, 9.0),
           (WAVE, 1.0, 3.0), (WAVE, 3.5, 5.5), (WAVE, 5.5, 7.5),
           ("%fusion.245 = f32[8,1024]{1,0} fusion(%c), kind=kLoop",
            7.5, 8.0),
           (ROOT, 9.5, 9.7)]
    return tr.Trace({"/device:TPU:0": ops}, [("bench.window", 0.0, 10.0)],
                    chips=1)


def unnamed_trace():
    """What the parent commit's program leaves: no name of the program's."""
    ops = [("%body.10 = (f32[28,255,126]{2,1,0}) custom-call(%a), "
            'custom_call_target="tpu_custom_call"', 1.0, 3.0)]
    return tr.Trace({"/device:TPU:0": ops}, [("bench.window", 0.0, 10.0)],
                    chips=1)


def _ctx(trace=None, peaks=PEAKS, **counters):
    return {"trace": trace, "peaks": peaks, "counters": counters,
            "config": {}, "traffic": {}, "window": {"window_s": 10.0}}


def _spec(name):
    with open(os.path.join(REPO, "benchmark", "metrics",
                           name + ".json")) as f:
        return json.load(f)


def _agg(count, total_s, self_s=None, build_s=0.0):
    return {"count": count, "total_s": total_s, "max_s": total_s,
            "self_s": total_s if self_s is None else self_s,
            "build_s": build_s, "builds": int(build_s > 0)}


SNAPSHOT = {
    "spans": {
        "lgbtpu.dataset.construct": _agg(1, 32.0, self_s=0.25),
        "lgbtpu.dataset.to_float": _agg(1, 2.0),
        "lgbtpu.dataset.edges": _agg(1, 1.5),
        "lgbtpu.dataset.codes": _agg(1, 27.0),
        "lgbtpu.dataset.put": _agg(1, 1.25),
        "lgbtpu.train.setup": _agg(1, 0.5, build_s=0.125),
        "lgbtpu.train.update_many": _agg(5, 4.02),
        "lgbtpu.train.dispatch": _agg(5, 4.0, build_s=3.5),
        "lgbtpu.train.segment": _agg(5, 0.01),
    },
    "facts": FACTS,
    "counts": {},
    # the first call built the program; the window's two did not
    "ring": [{"id": i, "parent": None, "name": "lgbtpu.train.update_many",
              "start": s, "end": e, "fields": {}}
             for i, (s, e) in enumerate([(0.0, 4.0), (5.0, 5.004),
                                         (6.0, 6.008)])],
}


@pytest.fixture
def snapshot(monkeypatch):
    """The readers read ``snap`` in place of the process's recorder."""
    def use(snap):
        monkeypatch.setattr(program_span, "snapshot", lambda: snap)
        monkeypatch.setattr(named_roofline, "snapshot", lambda: snap)
    use(SNAPSHOT)
    return use


def test_program_span_by_hand(snapshot):
    read = lambda name, **c: program_span.read(_ctx(**c), _spec(name))
    assert read("binning_edges_s") == 1.5
    assert read("binning_codes_s") == 27.0
    # construct's self time + to_float + put (bundling did not run)
    assert read("binning_other_s") == 0.25 + 2.0 + 1.25
    assert (read("binning_edges_s") + read("binning_codes_s")
            + read("binning_other_s")) == 32.0
    assert read("program_build_s") == 0.125 + 3.5
    # the window's two calls, not set-up's compiling one
    assert read("update_many_host_ms", window_calls=2) == pytest.approx(6.0)
    assert read("update_many_host_ms") is None         # no such counter
    every = {"terms": [{"pattern": "update_many$", "field": "total_s"}],
             "per": "count", "scale": 1000}
    assert program_span.read(_ctx(), every) == pytest.approx(804.0)


def test_program_span_with_nothing_to_read(snapshot):
    none = {"terms": [{"pattern": "^lgbtpu\\.serving\\.", "field": "total_s"}]}
    assert program_span.read(_ctx(), none) is None     # never 0
    snapshot({})                 # a program that has no recorder at all
    for name in NEW_METRICS[4:]:
        assert program_span.read(_ctx(window_calls=2), _spec(name)) is None


def test_snapshot_of_a_program_without_a_recorder(monkeypatch):
    import lightgbm_tpu.utils.profiling as profiling

    assert set(program_span.snapshot()) >= {"spans", "facts", "ring"}
    monkeypatch.delattr(profiling, "snapshot")         # the parent commit's
    assert program_span.snapshot() == {}


def test_trace_named_by_hand():
    ctx = _ctx(named_trace(), window_rounds=2)
    assert trace_named.read(ctx, _spec("wave_passes_per_round")) == 1.5
    # busy 8.2 s, the named kernels 6.2 s of it
    assert trace_named.read(ctx, _spec("grower_xla_pct")) == pytest.approx(
        100 * (8.2 - 6.2) / 8.2)
    one = {"patterns": ["^%lgbtpu_hist_root"], "take": "seconds"}
    assert trace_named.read(ctx, one) == pytest.approx(0.2)
    assert trace_named.read(ctx, dict(one, over="window", scale=100)) == \
        pytest.approx(2.0)


@pytest.mark.parametrize("name", NEW_METRICS[:4])
def test_trace_metrics_with_nothing_to_read(name, snapshot):
    """No trace, no event of that name (the parent's program), and for a
    share of a roofline no table of peaks (a CPU) or no fact; for a count
    per round no such counter: ``None``, never 0, never raised."""
    spec = _spec(name)
    reader = {"named_roofline": named_roofline,
              "trace_named": trace_named}[spec["reader"]]
    assert reader.read(_ctx(None, window_rounds=2), spec) is None
    assert reader.read(_ctx(unnamed_trace(), window_rounds=2), spec) is None
    if reader is named_roofline:
        assert reader.read(_ctx(named_trace(), peaks=None), spec) is None
        snapshot({})
        assert reader.read(_ctx(named_trace()), spec) is None
    elif "over" in spec and spec["over"].startswith("counter:"):
        assert reader.read(_ctx(named_trace()), spec) is None
        assert reader.read(_ctx(named_trace(), window_rounds=0), spec) is None


def test_named_roofline_by_hand(snapshot):
    ctx = _ctx(named_trace())
    shapes = {"rows": 1024, "features": 28, "bins": 255, "code_bytes": 4}
    wave = work.least_seconds(work.hist_onehot_call(
        dict(shapes, segments=42)), PEAKS)[0]
    root = work.least_seconds(work.hist_onehot_call(
        dict(shapes, segments=1)), PEAKS)[0]
    got_wave = named_roofline.read(ctx, _spec("hist_wave_roofline"))
    got_root = named_roofline.read(ctx, _spec("hist_root_roofline"))
    assert got_wave == pytest.approx(100 * 3 * wave / 6.0)
    assert got_root == pytest.approx(100 * root / 0.2)
    # the two agree with trace_roofline's arithmetic: their time-weighted
    # mean is what one spec of both patterns reads on the same events
    both = {"calls": [
        {"pattern": "^%lgbtpu_hist_wave", "work": "hist_onehot_call",
         "shapes": dict(shapes, segments=42)},
        {"pattern": "^%lgbtpu_hist_root", "work": "hist_onehot_call",
         "shapes": dict(shapes, segments=1)}]}
    assert trace_roofline.read(ctx, both) == pytest.approx(
        (6.0 * got_wave + 0.2 * got_root) / 6.2)
    # the width is the program's, not a constant of the metric's
    snapshot(dict(SNAPSHOT, facts=dict(FACTS, **{"train.wave_width": 21})))
    assert named_roofline.read(ctx, _spec("hist_wave_roofline")) == \
        pytest.approx(100 * 3 * work.least_seconds(work.hist_onehot_call(
            dict(shapes, segments=21)), PEAKS)[0] / 6.0)
    # a precision the work function has no peak for: nothing to read
    snapshot(dict(SNAPSHOT, facts=dict(FACTS, **{"train.hist_dtype": "f32"})))
    assert named_roofline.read(ctx, _spec("hist_wave_roofline")) is None


def test_new_metrics_are_listed_for_the_training_cell():
    """PR 26's nine metrics, found by name: each lists the cell it was
    made for (later cells and later metrics come and go beside them)."""
    doc = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    listed = {m["name"]: m for m in doc["per_layer"]}
    for name in NEW_METRICS:
        assert "higgs-10m5.train" in listed[name]["workloads"]
        assert _spec(name)["what"]


NARROW = ('%lgbtpu_hist_narrow.8 = (f32[28,48,255]{2,1,0:T(8,128)S(1)}, '
          's32[1,1024]{1,0}) custom-call(s32[28,1024]{1,0} %a), '
          'custom_call_target="tpu_custom_call", frontend_attributes={}')


def narrow_trace():
    """``named_trace`` with two passes of the narrow role, one in the
    loop's gap and one after it: busy 8.2 + 0.5 = 8.7 of 10."""
    ops = [("%while.71 = (f32[8]) while(%tuple), body=%b", 1.0, 9.0),
           (WAVE, 1.0, 3.0), (NARROW, 3.0, 3.5), (WAVE, 3.5, 5.5),
           (WAVE, 5.5, 7.5),
           ("%fusion.245 = f32[8,1024]{1,0} fusion(%c), kind=kLoop",
            7.5, 8.0),
           (NARROW, 9.0, 9.5), (ROOT, 9.5, 9.7)]
    return tr.Trace({"/device:TPU:0": ops}, [("bench.window", 0.0, 10.0)],
                    chips=1)


def test_grower_xla_pct_leaves_out_the_narrow_role():
    """The XLA stages' share is the busy time outside ALL three kernel
    roles: a narrow pass moves it only through the busy time."""
    spec = _spec("grower_xla_pct")
    ctx = _ctx(narrow_trace(), window_rounds=2)
    # the while's own time: 8.0 - 6.0 (waves) - 0.5 (the narrow pass it
    # holds) - 0.5 (fusion) = 1.0, plus the fusion 0.5: 1.5 of 8.7 busy
    assert trace_named.read(ctx, spec) == pytest.approx(100 * 1.5 / 8.7)
    old = dict(spec, patterns=spec["patterns"][:2])
    assert trace_named.read(ctx, old) == pytest.approx(100 * 2.5 / 8.7)
    assert trace_named.read(ctx, _spec("hist_narrow_busy_pct")) == \
        pytest.approx(100 * 1.0 / 8.7)
    assert trace_named.read(ctx, _spec("narrow_calls_per_round")) == 1.0
    assert trace_named.read(ctx, _spec("wave_passes_per_round")) == 1.5
    for name in ("wave_passes_per_round", "wave_calls_per_round"):
        assert "FULL-WIDTH" in _spec(name)["what"]


@pytest.mark.parametrize("name, facts", [
    ("hist_narrow_roofline", {}),
    # hi/lo: the program's precision is f32, each event one bf16 pass
    ("hist_narrow_hilo_roofline", {"train.hist_dtype": "f32"}),
])
def test_narrow_rooflines_by_hand(name, facts, snapshot):
    snapshot(dict(SNAPSHOT, facts=dict(
        FACTS, **{"train.wave_narrow_width": 16}, **facts)))
    ctx = _ctx(narrow_trace())
    least = work.least_seconds(work.hist_onehot_call(
        {"rows": 1024, "features": 28, "bins": 255, "code_bytes": 4,
         "segments": 16}), PEAKS)[0]
    # two events of 0.5 s, each credited with 16 segments' work
    assert named_roofline.read(ctx, _spec(name)) == \
        pytest.approx(100 * 2 * least / 1.0)
    # the wave's roofline does not see them, nor they the wave's events
    if not facts:
        assert named_roofline.read(ctx, _spec("hist_wave_roofline")) == \
            named_roofline.read(_ctx(named_trace()),
                                _spec("hist_wave_roofline"))
    # no narrow event (a program before PR 32), no such fact, no peaks
    assert named_roofline.read(_ctx(named_trace()), _spec(name)) is None
    assert named_roofline.read(_ctx(narrow_trace(), peaks=None),
                               _spec(name)) is None
    snapshot(SNAPSHOT)                     # no train.wave_narrow_width
    assert named_roofline.read(ctx, _spec(name)) is None


def test_narrow_rooflines_are_listed_once_each():
    """The bf16 cells list the one, the cell whose program resolves f32
    (hi/lo) the other, and no cell both."""
    doc = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    listed = {m["name"]: m for m in doc["per_layer"]}
    bf16 = set(listed["hist_narrow_roofline"]["workloads"])
    hilo = set(listed["hist_narrow_hilo_roofline"]["workloads"])
    assert {"higgs-10m5.train", "mslr-web30k.train"} <= bf16
    assert "epsilon-400k.train" in hilo
    assert not bf16 & hilo
    for name in ("hist_narrow_roofline", "hist_narrow_hilo_roofline"):
        assert listed[name]["layer"] == _spec(name)["layer"] == "kernels"
        assert listed[name]["moves"] == "train_rows_rounds_per_s"


def test_tiny_cell_prints_the_host_side_metrics(bench_copy, capsys):
    from bench_helpers import TINY_CONFIG

    cell = bench_copy.add_cell("tiny", TINY_CONFIG, like="every")
    # the recorder is the process's: a run of the command is a process of
    # its own, a test shares its worker with the tests before it
    from lightgbm_tpu.utils import profiling

    profiling.reset()
    res, _ = bench_copy.run(capsys, cell, trace=1)
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert res["correct"] is True
    for name in NEW_METRICS[4:]:
        assert got[name] > 0, name
    assert (got["binning_edges_s"] + got["binning_codes_s"]
            + got["binning_other_s"]) <= got["binning_s"]
    assert got["update_many_host_ms"] < 1000 * res["window_s"]
    # interpret mode leaves no named custom call, a CPU has no peaks
    for name in NEW_METRICS[:4]:
        assert name not in got
    assert [res["metrics"][n]["unit"] for n in NEW_METRICS[4:]] == \
        ["s", "s", "s", "ms", "s"]
