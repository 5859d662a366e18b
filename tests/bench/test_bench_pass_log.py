"""The reader of the program's pass log (PR 39): the newest window's rounds,
only the passes that ran, one role at a time; nothing to read where the
program logged nothing.  Then the tiny cell end to end on the CPU."""

import json
import os

import numpy as np
import pytest

from benchmark.readers import pass_log
from bench_helpers import REPO

NEW_METRICS = ["wide_pass_parent_rows_pct", "wide_pass_direct_rows_pct",
               "narrow_pass_parent_rows_pct", "narrow_pass_direct_rows_pct"]
CELLS = ["higgs-10m5.train", "epsilon-400k.train", "mslr-web30k.train"]


def _spec(name):
    with open(os.path.join(REPO, "benchmark", "metrics",
                           name + ".json")) as f:
        return json.load(f)


def _round(*passes, width=6):
    """One round's log ``[5, width]``: ``passes`` are (role, splits,
    streamed, parents, direct) columns, the rest zero."""
    out = np.zeros((5, width), np.float32)
    for i, col in enumerate(passes):
        out[:, i] = col
    return out


# an old round (before the window) that would change every share, then two
# window rounds: narrow passes of 100 rows, full-width passes of 100
OLD = _round((0, 1, 100, 100, 10), (1, 9, 100, 10, 1))
WINDOW = [_round((0, 1, 100, 100, 40), (0, 2, 100, 100, 30),
                 (1, 8, 100, 60, 20), (1, 4, 100, 30, 10)),
          _round((0, 1, 100, 100, 50), (1, 16, 100, 50, 10))]


@pytest.fixture
def snapshot(monkeypatch):
    def use(snap):
        monkeypatch.setattr(pass_log, "snapshot", lambda: snap)
    # two segments: one of one round, one of two
    use({"arrays": {"train.passes": [OLD[None], np.stack(WINDOW)]}})
    return use


def _ctx(**counters):
    return {"trace": None, "peaks": None, "counters": counters,
            "config": {}, "traffic": {}, "window": {"window_s": 10.0}}


def test_the_newest_rounds_and_only_the_passes_that_ran(snapshot):
    read = lambda name, **c: pass_log.read(_ctx(**c), _spec(name))
    assert read("wide_pass_parent_rows_pct", window_rounds=2) == \
        pytest.approx(100 * (60 + 30 + 50) / 300)
    assert read("wide_pass_direct_rows_pct", window_rounds=2) == \
        pytest.approx(100 * (20 + 10 + 10) / 300)
    assert read("narrow_pass_parent_rows_pct", window_rounds=2) == 100.0
    assert read("narrow_pass_direct_rows_pct", window_rounds=2) == \
        pytest.approx(100 * 120 / 300)
    # the newest round alone; then all three, the old one across segments
    assert read("wide_pass_parent_rows_pct", window_rounds=1) == 50.0
    assert read("wide_pass_direct_rows_pct", window_rounds=3) == \
        pytest.approx(100 * 41 / 400)
    # more rounds asked for than the ring holds: what it holds
    assert read("wide_pass_direct_rows_pct", window_rounds=50) == \
        read("wide_pass_direct_rows_pct", window_rounds=3)


def test_nothing_to_read_is_none(snapshot):
    for name in NEW_METRICS:
        spec = _spec(name)
        assert pass_log.read(_ctx(), spec) is None          # no counter
        assert pass_log.read(_ctx(window_rounds=0), spec) is None
    snapshot({"spans": {}, "facts": {}})       # the parent commit's program
    for name in NEW_METRICS:
        assert pass_log.read(_ctx(window_rounds=2), _spec(name)) is None
    snapshot({"arrays": {}})                   # a path that drops the log
    assert pass_log.read(_ctx(window_rounds=2),
                         _spec(NEW_METRICS[0])) is None
    # rounds of no pass of the role (the CPU's program has no narrow loop)
    snapshot({"arrays": {"train.passes": [
        _round((1, 8, 100, 60, 20))[None]]}})
    assert pass_log.read(_ctx(window_rounds=1),
                         _spec("narrow_pass_parent_rows_pct")) is None


def test_another_boosters_log_is_not_taken(snapshot):
    """The newest booster's shape only: an older log of another tree size
    stops the walk back."""
    other = np.ones((4, 5, 9), np.float32)
    snapshot({"arrays": {"train.passes": [other, np.stack(WINDOW)]}})
    assert pass_log.read(_ctx(window_rounds=5),
                         _spec("wide_pass_parent_rows_pct")) == \
        pytest.approx(100 * 140 / 300)


def test_the_reader_knows_the_programs_columns():
    from lightgbm_tpu.models.tree import _PASS

    assert (pass_log.ROLE, pass_log.SPLITS, pass_log.STREAMED) == \
        (_PASS.ROLE, _PASS.SPLITS, _PASS.STREAMED)
    assert pass_log.ROWS == {"parents": _PASS.PARENTS,
                             "direct": _PASS.DIRECT}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_each_metric_names_the_three_cells(name, manifest):
    listed = {m["name"]: m for m in manifest.doc["per_layer"]}[name]
    assert set(CELLS) <= set(listed["workloads"])
    spec = manifest.metric_spec(name)
    for key in ("layer", "unit", "moves", "source"):
        assert listed[key] == spec[key]
    assert (spec["layer"], spec["unit"], listed["better"]) == \
        ("growers", "%", "higher")
    assert spec["reader"] == "pass_log" and spec["what"]


def test_tiny_cell_prints_the_wide_shares(bench_copy, capsys):
    from bench_helpers import TINY_CONFIG

    # the tiny cell's 15 leaves grow strict unless told (no pass to log)
    cell = bench_copy.add_tiny_cell(config=dict(TINY_CONFIG, params=dict(
        TINY_CONFIG["params"], grow_policy="frontier")))
    doc = json.loads((bench_copy.root / "BENCHMARK.json").read_text())
    for m in doc["per_layer"]:
        if m["name"] in NEW_METRICS:
            m["workloads"].append(cell)
    (bench_copy.root / "BENCHMARK.json").write_text(json.dumps(doc))
    from lightgbm_tpu.utils import profiling

    profiling.reset()
    res, _ = bench_copy.run(capsys, cell, trace=1)
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert 0 < got["wide_pass_parent_rows_pct"] <= 100
    assert 0 < got["wide_pass_direct_rows_pct"] <= \
        got["wide_pass_parent_rows_pct"] / 2
    # the CPU's grower has no narrow loop
    assert "narrow_pass_parent_rows_pct" not in got
