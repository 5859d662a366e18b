"""The command end to end on the CPU at a tiny size: the result line's
shape, the data-only extension, the control and the planted faults."""

import copy
import json
import os
import subprocess
import sys

import pytest

from bench_helpers import REPO, TINY_CONFIG, order_faults

E2E_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def test_result_line_trace0(bench_copy, capsys):
    cell = bench_copy.add_tiny_cell()
    res, err = bench_copy.run(capsys, cell, seed=2**31 + 12345)
    assert E2E_KEYS <= set(res)
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert set(res["metrics"]) == {"train_rows_rounds_per_s", "setup_s"}
    for m in res["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    assert res["metrics"]["train_rows_rounds_per_s"]["unit"] == "rows.rounds/s"
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert res["device"]["platform"] == "cpu"      # named for what it is
    for name, c in res["checks"].items():
        assert c["value"] <= c["limit"], name
        assert f"[check] {name} value=" in err
    assert err.rstrip().endswith("[check] correct=True")


def test_result_line_trace1(bench_copy, capsys):
    cell = bench_copy.add_cell("tiny", TINY_CONFIG, like="every")
    res, _ = bench_copy.run(capsys, cell, trace=1)
    assert res["correct"] is True
    assert res["device"]["busy_s"] > 0
    assert res["device"]["window_s"] >= res["device"]["busy_s"] * 0.5
    # no table of peaks for a CPU: shares of a peak are left out, never 0
    assert "hist_wave_roofline" not in res["metrics"]
    assert "train_floor_mfu_pct" not in res["metrics"]
    assert res["metrics"]["binning_s"]["value"] > 0
    assert 0 <= res["metrics"]["device_idle_pct.train"]["value"] <= 100
    bd = res["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert all(len(row) == 2 and row[1] >= 0 for row in bd["device_ops"])
    assert "setup_s" not in res["metrics"]


def test_same_seed_same_inputs():
    import numpy as np

    from benchmark import datagen

    a = datagen.higgs_like(500, 28, 2**31 + 7)
    b = datagen.higgs_like(500, 28, 2**31 + 7)
    c = datagen.higgs_like(500, 28, 2**31 + 8)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    assert a[0].dtype == np.float32 and 0.3 < a[1].mean() < 0.7


def test_table_is_one_table_in_the_seeds_column_order():
    """``datagen.table``: the rows and labels of ``table_seed``, the columns
    in an order drawn from the seed; nothing else of the seed's."""
    import numpy as np

    from benchmark import datagen

    X0, y0 = datagen.higgs_like(3000, 28, 11)
    seed = 2**31 + 77
    X, y = datagen.table(3000, 28, 11, seed)
    order = np.random.default_rng(seed).permutation(28)
    assert np.array_equal(X, X0[:, order]) and np.array_equal(y, y0)
    again = datagen.table(3000, 28, 11, seed)
    assert np.array_equal(again[0], X) and np.array_equal(again[1], y)
    other, y_other = datagen.table(3000, 28, 11, seed + 1)
    assert not np.array_equal(other, X) and np.array_equal(y_other, y0)
    assert np.array_equal(np.sort(other, axis=1), np.sort(X, axis=1))
    # more rows than one block of the in-place permutation holds
    wide, _ = datagen.table(700, 2000, 5, seed)
    wide0, _ = datagen.higgs_like(700, 2000, 5)
    assert np.array_equal(
        wide, wide0[:, np.random.default_rng(seed).permutation(2000)])


def test_every_seed_grows_the_tables_trees(bench_copy, capsys):
    """A configuration that names a ``table_seed`` gives every seed the same
    work: the same trees on other column numbers, so the reference reads
    the same losses and the same leaf statistics; without it the seed draws
    a table of its own."""
    fixed = bench_copy.add_tiny_cell(
        "tiny-table", dict(copy.deepcopy(TINY_CONFIG), table_seed=2136000008))
    free = bench_copy.add_tiny_cell("tiny-free")

    def losses(cell, seed):
        res, _ = bench_copy.run(capsys, cell, seed=seed)
        assert res["correct"] is True
        return res["counters"]["reference_loss"]

    a, b = losses(fixed, 2**31 + 1), losses(fixed, 2**31 + 2)
    assert a == pytest.approx(b, rel=1e-9)
    assert losses(free, 2**31 + 1) != pytest.approx(
        losses(free, 2**31 + 2), rel=1e-4)


def test_extension_is_data_only(bench_copy, capsys):
    """A new cell, a new configuration and a new per-layer metric (with a
    reader of its own) are new files plus entries in BENCHMARK.json."""
    before = {p: p.read_bytes() for p in bench_copy.root.rglob("*")
              if p.is_file() and p.name != "BENCHMARK.json"}
    cell = bench_copy.add_tiny_cell("tiny2")
    bench_copy.add(
        files={
            "benchmark/metrics/rounds_per_call_x.json": {
                "layer": "entry", "unit": "rounds", "reader": "counter",
                "moves": "train_rows_rounds_per_s", "num": ["window_rounds"],
                "den": ["window_calls"]},
            "benchmark/metrics/first_rounds_s.json": {
                "layer": "entry", "unit": "s", "moves": "setup_s"},
            "benchmark/metrics/first_rounds_s.py":
                "def read(ctx, spec):\n"
                "    return ctx['counters'].get('first_rounds_s')\n",
        },
        per_layer=[
            {"name": "rounds_per_call_x", "unit": "rounds", "better": "higher",
             "source": "program_counter", "layer": "entry",
             "moves": "train_rows_rounds_per_s", "workloads": [cell]},
            {"name": "first_rounds_s", "unit": "s", "better": "lower",
             "source": "host_clock", "layer": "entry", "moves": "setup_s",
             "workloads": [cell]}])
    res, _ = bench_copy.run(capsys, cell, trace=1)
    assert res["correct"] is True
    assert res["metrics"]["rounds_per_call_x"]["value"] == 1.0
    assert res["metrics"]["first_rounds_s"]["value"] > 0
    for p, content in before.items():
        assert p.read_bytes() == content, f"{p} was edited"


def test_control_comes_out_not_correct(bench_copy, capsys):
    """The configuration's control (the next precision down, switched on
    through ``control.params``) has to fail one of the cell's numbers."""
    from benchmark.readings import variant

    cfg, fault = variant(copy.deepcopy(TINY_CONFIG), "control")
    assert fault is None and cfg["params"]["hist_dtype"] == "bf16"
    cell = bench_copy.add_tiny_cell("tiny-control", cfg)
    res, err = bench_copy.run(capsys, cell)
    assert res["correct"] is False
    assert res["checks"]["leaf_value_worst"]["value"] > \
        res["checks"]["leaf_value_worst"]["limit"]
    assert "OVER" in err and err.rstrip().endswith("[check] correct=False")


@pytest.mark.parametrize("fault,number", [
    ("state_unchanged", "trees_missing"),
    ("half_batch", "leaf_count_off"),
    ("altered_answer", "leaf_value_worst"),
    ("fewer_leaves", "leaves_off"),
    ("restricted_features", "split_gain_short"),
])
def test_planted_fault_is_not_correct(bench_copy, capsys, fault, number):
    """The rest of a run, with the timed path broken underneath."""
    cell = bench_copy.add_tiny_cell()
    res, _ = bench_copy.run(capsys, cell, fault=fault)
    assert res["correct"] is False
    assert res["checks"][number]["value"] > res["checks"][number]["limit"]


def test_a_fault_the_kind_does_not_have_is_refused():
    """``readings.py`` takes its modes from the command line: a name that
    is no fault of the kind would read as a sound run."""
    from benchmark.kinds import train_window, train_window_rank

    with pytest.raises(ValueError, match="no fault 'pointwise'"):
        train_window.Cell(TINY_CONFIG, {}, 1, None, fault="pointwise")
    assert train_window_rank.Cell(
        dict(TINY_CONFIG, queries=1), {}, 1, None,
        fault="pointwise").fault == "pointwise"


def test_growth_without_the_replay_is_not_correct(bench_copy, capsys):
    """Wave growth with the exact tail passes; the same run with the
    replay of strict best-first order left out (``wave_tail=greedy``, a
    path of the program's own that saves histogram passes) keeps exact
    leaf statistics and best splits, and fails ``order_excess`` alone."""
    cfg = copy.deepcopy(TINY_CONFIG)
    cfg.update(rows=20_000)
    cfg["params"].update(num_leaves=31, wave_tail="exact")
    cfg["reference"].update(num_leaves=31)
    cell = bench_copy.add_tiny_cell("tiny-exact", cfg)
    res, _ = bench_copy.run(capsys, cell, seed=1)
    assert res["correct"] is True
    res, _ = bench_copy.run(capsys, cell, seed=1, fault="greedy_tail")
    over = {n for n, c in res["checks"].items() if c["value"] > c["limit"]}
    assert res["correct"] is False and over == {"order_excess"}


def _command(cwd, workload="higgs-10m5.train"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    doc = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    return subprocess.run(
        doc["command"] + ["--workload", workload, "--seed", "1",
                          "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_fails_and_prints_no_result():
    p = _command(REPO)
    assert p.returncode != 0
    assert "not a TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_bare_directory_fails(bench_copy):
    """Only BENCHMARK.json and the files under ``paths``: no program."""
    p = _command(str(bench_copy.root))
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_manifest_names_files_that_exist(manifest):
    man = manifest
    for cell in man.doc["workloads"]:
        assert man.config(cell)["rows"] > 0
        assert man.traffic(cell)["kind"]
        for group in ("end_to_end", "per_layer"):
            assert man.metrics_of(cell["name"], group)
    for m in man.doc["per_layer"]:
        spec = man.metric_spec(m["name"])
        assert spec["layer"] == m["layer"] and spec["unit"] == m["unit"]
        assert spec["moves"] == m["moves"]
        assert callable(man.metric_reader(m["name"], spec))
    for c in man.doc["configs"]:
        cfg = json.load(open(os.path.join(man.root, c["file"])))
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]


def test_metric_lists_follow_the_cells_order(manifest):
    """Every name a metric lists is a cell, listed once, and each list
    keeps the order of the top-level ``workloads``."""
    assert order_faults(manifest.doc) == []


@pytest.mark.parametrize("fault", ["no such cell", "listed twice",
                                   "out of order"])
def test_order_faults_are_found(fault):
    doc = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    rate = next(m for m in doc["end_to_end"]
                if m["name"] == "train_rows_rounds_per_s")
    rate["workloads"] = {
        "no such cell": rate["workloads"] + ["nowhere.train"],
        "listed twice": rate["workloads"] + rate["workloads"][:1],
        "out of order": rate["workloads"][::-1],
    }[fault]
    assert [f[:2] for f in order_faults(doc)] == [
        ("train_rows_rounds_per_s", fault)]
