"""The trace reduction and the work functions against hand-worked numbers."""

import pytest

from benchmark.reduce import trace as tr
from benchmark.reduce import work

HIST = ('%body.10 = (f32[28,255,126]{2,1,0:T(8,128)S(1)}, s32[1,1024]{1,0}) '
        'custom-call(s32[28,1024]{1,0} %a, f32[3,1024]{1,0} %b), '
        'custom_call_target="tpu_custom_call", frontend_attributes={}')
ROOT = ('%closed_call.2 = f32[28,255,3]{2,1,0:T(8,128)S(1)} custom-call('
        's32[28,1024]{1,0} %pad.74), custom_call_target="tpu_custom_call"')
# a roofline of both histogram kernels found by their shapes alone: f32
# [F, B, 126] for a wave pass of 42 segments, f32 [F, B, 3] for the root
HIST_SPEC = {"layer": "kernels", "calls": [
    {"pattern": '^%[^ ]+ = \\(f32\\[\\d+,\\d+,126\\].*'
                'custom_call_target="tpu_custom_call"',
     "work": "hist_onehot_call",
     "shapes": {"rows": "counter:rows_padded", "features": "config:features",
                "bins": 255, "segments": 42, "code_bytes": 4,
                "dtype": "bf16"}},
    {"pattern": '^%[^ ]+ = f32\\[\\d+,\\d+,3\\].*'
                'custom_call_target="tpu_custom_call"',
     "work": "hist_onehot_call",
     "shapes": {"rows": "counter:rows_padded", "features": "config:features",
                "bins": 255, "segments": 1, "code_bytes": 4,
                "dtype": "bf16"}}]}
CONCAT = ('%custom-call.68 = f32[1024]{0} custom-call(f32[512]{0} %x, '
          'f32[512]{0} %y), custom_call_target="ConcatBitcast"')


def hand_trace():
    """One chip: a ``while`` from 1.0 to 9.0 holding two kernels and a
    fusion, idle before, between 9.0 and 9.5, and after 9.7."""
    ops = [
        ("%while.71 = (f32[8]) while(%tuple), body=%b", 1.0, 9.0),
        (HIST, 1.0, 4.0),
        (HIST, 4.5, 7.5),
        ("%fusion.245 = f32[8,1024]{1,0} fusion(bf16[42,8]{0,1} %c), "
         "kind=kLoop", 7.5, 8.0),
        (ROOT, 9.5, 9.7),
        (CONCAT, 9.7, 9.7),
    ]
    spans = [("bench.window", 0.0, 10.0),
             ("bench.update_many", 0.0, 0.9),
             ("bench.block_until_ready", 0.9, 10.0)]
    return tr.Trace({"/device:TPU:0": ops}, spans, chips=1)


def test_union_and_gaps():
    assert tr.union_length([]) == 0.0
    assert tr.union_length([(0, 2), (1, 3), (5, 6), (5.5, 5.8)]) == 4.0
    assert tr.gaps_of([(1, 3), (2, 4), (6, 7)], 0, 10) == \
        [(0, 1), (4, 6), (7, 10)]
    assert tr.gaps_of([], 2, 5) == [(2, 5)]
    assert tr.gaps_of([(0, 10)], 2, 5) == []


def test_busy_idle_and_window():
    t = hand_trace()
    assert t.window_s == 10.0
    assert t.busy_s == pytest.approx(8.0 + 0.2)
    assert t.idle_share() == pytest.approx(0.18)
    # no window span: first device start to last device end
    t2 = tr.Trace(t.device_ops, [], chips=1)
    assert (t2.start, t2.end) == (1.0, 9.7)
    # two chips: busy is the mean over the chips used
    t4 = tr.Trace({"a": [("x", 0.0, 1.0)], "b": [("x", 0.0, 3.0)]},
                  [("bench.window", 0.0, 4.0)], chips=2)
    assert t4.busy_s == 2.0 and t4.idle_share() == 0.5
    assert tr.Trace({}, [], chips=1).idle_share() is None


def test_events_are_clipped_to_the_window():
    t = tr.Trace({"d": [("k", 0.0, 4.0), ("k", 9.0, 12.0)]},
                 [("bench.window", 2.0, 10.0)], chips=1)
    assert t.busy_s == 3.0
    assert t.matching("^k$") == (3.0, 2.0)


def test_pattern_sums():
    t = hand_trace()
    wave, root = (c["pattern"] for c in HIST_SPEC["calls"])
    assert t.matching(wave) == (6.0, 2.0)
    assert t.matching(root) == (pytest.approx(0.2), 1.0)
    assert t.matching("no such kernel") == (0.0, 0.0)


def test_self_times_and_labels():
    t = hand_trace()
    top = dict(t.top_ops())
    label = "custom-call:tpu_custom_call %body.10 (f32[28,255,126], s32[1,1024])"
    assert top[label] == pytest.approx(6.0)
    # the while is charged only what its children leave: 8 - 3 - 3 - 0.5
    assert top["while %while.71 (f32[8])"] == pytest.approx(1.5)
    assert top["fusion %fusion.245 f32[8,1024]"] == pytest.approx(0.5)
    assert list(top)[0] == label
    assert tr.short_label("ReadSyncFlag") == "ReadSyncFlag"
    assert len(t.top_ops(n=2)) == 2


def test_gap_attribution():
    t = hand_trace()
    gaps = dict(t.idle_gaps())
    assert gaps["bench.update_many"] == pytest.approx(1.0)      # 0.0 - 1.0
    assert gaps["bench.block_until_ready"] == pytest.approx(0.8)
    bd = t.breakdown()
    assert set(bd) == {"device_ops", "idle_gaps"}
    t2 = tr.Trace({"d": [("k", 1.0, 2.0)]}, [("bench.window", 0.0, 3.0)], 1)
    assert dict(t2.idle_gaps()) == {"host:unnamed": 2.0}


def test_read_a_recorded_cpu_trace(tmp_path):
    """The reader on a real (tiny, CPU) ``.xplane.pb``."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                f(x).block_until_ready()
    t = tr.reduce_dir(str(tmp_path), 1, platform="cpu")
    assert t.window_s > 0 and 0 < t.busy_s <= t.window_s
    assert t.top_ops()
    with pytest.raises(SystemExit):
        tr.reduce_dir(str(tmp_path / "nothing"), 1)
    with pytest.raises(SystemExit):        # no TPU plane in a CPU trace
        tr.reduce_dir(str(tmp_path), 1, platform="tpu")


def test_hbm_floor_by_hand():
    w = work.hbm_floor_round({"rows": 10_500_000, "features": 28})
    assert w["bytes"] == 10_500_000 * 28 + 10_500_000 * 8 == 378_000_000
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    s, bound = work.least_seconds(w, peaks)
    assert bound == "bytes" and s == pytest.approx(378e6 / 819e9)


def test_hist_work_by_hand():
    shapes = {"rows": 1000, "features": 28, "bins": 255, "segments": 42,
              "code_bytes": 4, "dtype": "bf16"}
    w = work.hist_onehot_call(shapes)
    assert w["ops"] == 2 * 1000 * 28 * 255 * 3 * 42 == 1_799_280_000
    assert w["bytes"] == 1000 * (28 * 4 + 16) + 42 * 28 * 255 * 12
    assert w["peak"] == "bf16_flops_per_s"
    assert work.hist_onehot_call(dict(shapes, dtype="int8"))["peak"] == \
        "int8_ops_per_s"
    peaks = {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
             "hbm_bytes_per_s": 819e9}
    big = work.hist_onehot_call(dict(shapes, rows=10_500_096))
    s, bound = work.least_seconds(big, peaks)
    assert bound == "ops" and s == pytest.approx(0.09590, rel=1e-3)
    one = work.hist_onehot_call(dict(shapes, rows=10_500_096, segments=1))
    assert work.least_seconds(one, peaks)[1] == "ops"


def test_peaks_table():
    from benchmark.device import peaks_for

    p = peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    for unknown in ("TPU v9", "cpu", "source"):
        with pytest.raises(SystemExit):
            peaks_for(unknown)


def _ctx(trace=None, peaks=None, **counters):
    return {"trace": trace, "peaks": peaks, "counters": counters,
            "config": {"rows": 1000, "features": 28}, "traffic": {},
            "window": {"window_s": 10.0}}


PEAKS = {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
         "hbm_bytes_per_s": 819e9}


def test_readers():
    from benchmark.readers import counter, floor_share, trace_idle, \
        trace_roofline

    assert counter.read(_ctx(binning_s=2.5), {"counter": "binning_s"}) == 2.5
    assert counter.read(_ctx(), {"counter": "binning_s"}) is None
    ratio = {"num": ["padded_rows"], "den": ["rows", "padded_rows"],
             "scale": 100}
    assert counter.read(_ctx(rows=75, padded_rows=25), ratio) == 25.0
    assert counter.read(_ctx(rows=0, padded_rows=0), ratio) is None
    assert counter.read(_ctx(rows=1), ratio) is None

    assert trace_idle.read(_ctx(hand_trace()), {}) == pytest.approx(18.0)
    assert trace_idle.read(_ctx(None), {}) is None

    spec = {"count": "counter:window_rounds", "work": "hbm_floor_round",
            "shapes": {"rows": "config:rows", "features": "config:features",
                       "code_bytes": "counter:code_bytes"}}
    got = floor_share.read(_ctx(peaks=PEAKS, window_rounds=4, code_bytes=1),
                           spec)
    assert got == pytest.approx(100 * 4 * (1000 * 36 / 819e9) / 10.0)
    assert floor_share.read(_ctx(peaks=None, window_rounds=4, code_bytes=1),
                            spec) is None              # no peaks: no share
    assert floor_share.read(_ctx(peaks=PEAKS, code_bytes=1), spec) is None

    hist = HIST_SPEC
    ctx = _ctx(hand_trace(), PEAKS, rows_padded=1024)
    got = trace_roofline.read(ctx, hist)
    wave = work.least_seconds(work.hist_onehot_call(
        {"rows": 1024, "features": 28, "bins": 255, "segments": 42,
         "code_bytes": 4}), PEAKS)[0]
    root = work.least_seconds(work.hist_onehot_call(
        {"rows": 1024, "features": 28, "bins": 255, "segments": 1,
         "code_bytes": 4}), PEAKS)[0]
    assert got == pytest.approx(100 * (2 * wave + root) / 6.2)
    assert ctx["notes"]["kernels"][0]["bound"] in ("ops", "bytes")
    # a kernel that left the path: nothing to read, never 0
    empty = tr.Trace({"d": [("x", 0.0, 1.0)]}, [], 1)
    assert trace_roofline.read(_ctx(empty, PEAKS, rows_padded=1024),
                               hist) is None
    assert trace_roofline.read(_ctx(hand_trace(), None, rows_padded=1024),
                               hist) is None
