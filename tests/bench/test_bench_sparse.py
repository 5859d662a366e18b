"""The sparse wide cell (``bosch-1m.train``): its generator, its
reference, its kind end to end at a tiny size on the CPU with the control
and every planted fault, and its entries in the real ``BENCHMARK.json``."""

import copy
import os
import re

import numpy as np
import pytest

from bench_helpers import REPO, TINY_CONFIG

CELL = "bosch-1m.train"
SPARSE_METRICS = ("efb_column_ratio", "efb_member_scan_ms")

# 6,000 parts x the first 120 columns of the layout: a few dozen of
# hessian, so a leaf is asked for 0.5 of it; the split search at the real
# cell's limit (the reference's candidates over 1,000 values a column
# part from the program's bins more than over 200,000)
TINY_SPARSE = dict(
    copy.deepcopy(TINY_CONFIG), rows=6000, features=120,
    params={"objective": "binary", "num_leaves": 15, "learning_rate": 0.1,
            "max_bin": 255, "min_data_in_leaf": 1,
            "min_sum_hessian_in_leaf": 0.5, "lambda_l2": 0.0,
            "enable_bundle": True, "max_conflict_rate": 0.0,
            "hist_dtype": "f32", "verbosity": -1},
    reference={"learning_rate": 0.1, "lambda_l2": 0.0, "num_leaves": 15,
               "max_bin": 255, "min_sum_hessian_in_leaf": 0.5},
    control={"params": {"hist_dtype": "bf16"}},
    limits={"split_gain_short": 0.15, "order_excess": 0.1,
            "leaf_value_worst": 1e-4, "leaf_count_off": 0,
            "score_abs": 1e-5, "final_score_abs": 1e-5,
            "bundle_conflict_rows": 0, "dump_missing": 0,
            "unsplit_leaves": 0})


def add_sparse_cell(bench_copy, name="tiny-sparse", config=None):
    """A tiny sparse configuration and its cell, built as data: files and
    entries, the metrics of the real cell listed for it too."""
    return bench_copy.add_cell(name, config or TINY_SPARSE,
                               "train-window-sparse", like=CELL)


def over(res):
    return {n for n, c in res["checks"].items() if c["value"] > c["limit"]}


# -- the generator -----------------------------------------------------------

def test_datagen_sparse_gives_the_stated_shape():
    from benchmark import datagen_sparse as ds

    assert ds.FEATURES == 968 and ds.STATIONS == 52
    lines = [sum(k for line, k, _ in ds.GROUPS if line == i)
             for i in range(4)]
    assert lines == [24, 2, 3, 23]
    X, y = ds.bosch_like(70_000, 968, 2**31 + 3)
    assert X.shape == (70_000, 968) and X.dtype == np.float32
    missing = np.isnan(X)
    assert 0.78 < missing.mean() < 0.86
    assert missing.mean(axis=0).min() > 0.15
    assert abs(y.mean() - ds.POSITIVE_SHARE) < 0.0015
    # the columns of two stations of one group are never both measured
    station = ds.LAYOUT["col_station"]
    group = np.array([g for g, _ in ds.LAYOUT["stations"]])
    seen = ~missing
    for g in range(len(ds.GROUPS)):
        sts = np.flatnonzero(group == g)
        per = np.stack([seen[:, station == s].any(axis=1) for s in sts])
        assert per.sum(axis=0).max() <= 1
    # half of the columns coarse: few distinct values
    distinct = np.array([len(np.unique(X[~missing[:, f], f]))
                         for f in range(0, 968, 8)])
    assert 0.4 < np.mean(distinct <= 32) < 0.7
    # a narrower table is the wider one's first columns; another seed,
    # another table
    part, _ = ds.bosch_like(70_000, 120, 2**31 + 3)
    assert np.array_equal(part, X[:, :120], equal_nan=True)
    other, _ = ds.bosch_like(70_000, 120, 2**31 + 4)
    assert not np.array_equal(other, part, equal_nan=True)


def test_datagen_sparse_imports_nothing_of_the_program():
    for rel in ("datagen_sparse.py", os.path.join("reference",
                                                  "sparse_check.py")):
        src = open(os.path.join(REPO, "benchmark", rel)).read()
        assert not re.search(r"^\s*(from|import)\s+(lightgbm_tpu|jax)", src,
                             re.M)


# -- the reference -----------------------------------------------------------

def test_reference_edges_hold_the_value_or_nan_split():
    from benchmark.reference import sparse_check

    X = np.full((1000, 2), np.nan, np.float32)
    X[:100, 0] = np.arange(100)
    X[::3, 1] = 7.0
    edges = sparse_check.candidate_edges(X, 255, np.random.default_rng(0))
    assert edges[0][-1] == 99.0 and len(edges[0]) == 100
    assert edges[1].tolist() == [7.0]


def test_reference_routes_nan_right_and_reads_the_dump():
    from benchmark.reference import gbdt_check, sparse_check

    split = {"split_feature": 0, "threshold": 1.5, "decision_type": "<=",
             "missing_type": "NaN", "default_left": False,
             "left_child": {"leaf_value": -1.0},
             "right_child": {"leaf_value": 1.0}}
    tree = gbdt_check.flatten_tree(split)
    X = np.array([[1.0], [2.0], [np.nan]], np.float32)
    leaf = gbdt_check.route(X, tree)
    assert tree["value"][leaf].tolist() == [-1.0, 1.0, 1.0]
    assert sparse_check.dump_missing(split) == 0
    assert sparse_check.dump_missing(dict(split, default_left=True)) == 1


def test_unsplit_leaves_by_hand():
    """A stump on a table whose right side still holds a split worth
    taking: the short tree is short of a leaf; with the budget reached it
    is not."""
    from benchmark.reference import gbdt_check, sparse_check

    rng = np.random.default_rng(1)
    X = np.stack([np.repeat([0.0, 1.0], 500),
                  np.tile([0.0, 1.0], 500)], axis=1).astype(np.float32)
    g = np.where(X[:, 1] > 0, 1.0, -1.0) + rng.normal(0, 0.01, 1000)
    h = np.ones(1000)
    tree = gbdt_check.flatten_tree(
        {"split_feature": 0, "threshold": 0.5, "decision_type": "<=",
         "left_child": {"leaf_value": 0.0},
         "right_child": {"leaf_value": 0.0}})
    leaf = gbdt_check.route(X, tree)
    sums = tuple(np.bincount(leaf, weights=w, minlength=3)
                 for w in (g, h, None))
    edges = [np.array([0.0, 1.0])] * 2
    hyper = {"lambda_l2": 0.0, "min_sum_hessian_in_leaf": 100.0,
             "num_leaves": 4}
    assert sparse_check.unsplit_leaves(X, tree, leaf, g, h, sums, edges,
                                       hyper, 4) == 2
    assert sparse_check.unsplit_leaves(
        X, tree, leaf, g, h, sums, edges, dict(hyper, num_leaves=2), 4) == 0
    assert sparse_check.unsplit_leaves(
        X, tree, leaf, g, h, sums, edges,
        dict(hyper, min_sum_hessian_in_leaf=240.0), 4) == 0


# -- the kind, end to end ----------------------------------------------------

def test_tiny_sparse_cell_is_correct_and_reports_its_bundles(bench_copy,
                                                             capsys):
    from lightgbm_tpu.utils import profiling

    profiling.reset()
    cell = add_sparse_cell(bench_copy)
    res, err = bench_copy.run(capsys, cell, seed=2**31 + 11, trace=1)
    assert res["correct"] is True and not over(res), over(res)
    c = res["counters"]
    assert c["total_rounds"] == 2 + c["window_rounds"]
    assert c["train_features_raw"] == 120 > c["train_features"] == \
        c["bundle_columns"]
    assert c["bundled_features"] > 0 and c["bundle_conflict_rows"] == 0
    assert c["codes_path"] == "host"        # the CPU codes on the host
    assert c["efb_member_scan_ms"] > 0
    assert c["efb_member_scan_s"] * 1000.0 > 5 * c["efb_member_scan_ms"]
    assert c["dump_missing_splits"] == 0
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert got["efb_column_ratio"] == c["train_features"] / 120
    assert got["efb_member_scan_ms"] == c["efb_member_scan_ms"]
    for name in ("bundle_conflict_rows", "dump_missing", "unsplit_leaves"):
        assert name in res["checks"] and f"[check] {name}" in err


def test_two_seeds_of_one_table_grow_the_same_bundles(bench_copy, capsys):
    """``table_seed``: the seed orders the columns; the bundles and the
    trees' statistics are the table's."""
    cell = add_sparse_cell(bench_copy, "tiny-sparse-table",
                           dict(copy.deepcopy(TINY_SPARSE), table_seed=77))

    def run(seed):
        res, _ = bench_copy.run(capsys, cell, seed=seed)
        assert res["correct"] is True, res["checks"]
        return res["counters"]

    a, b = run(2**31 + 1), run(2**31 + 2)
    for key in ("bundle_columns", "bundled_features", "train_features"):
        assert a[key] == b[key]
    # the members of a bundle sit in the column order: a default bin's
    # float32 sum takes another order, nothing more
    assert a["reference_loss"] == pytest.approx(b["reference_loss"],
                                                rel=1e-6)


def test_sparse_control_comes_out_not_correct(bench_copy, capsys):
    from benchmark.readings import variant

    cfg, fault = variant(copy.deepcopy(TINY_SPARSE), "control")
    assert fault is None and cfg["params"]["hist_dtype"] == "bf16"
    cell = add_sparse_cell(bench_copy, "tiny-sparse-control", cfg)
    res, _ = bench_copy.run(capsys, cell, seed=3)
    assert res["correct"] is False
    assert "leaf_value_worst" in over(res)


@pytest.mark.parametrize("fault,number", [
    ("state_unchanged", "trees_missing"),
    ("half_batch", "leaf_count_off"),
    ("altered_answer", "leaf_value_worst"),
    ("restricted_features", "split_gain_short"),
    ("fewer_leaves", "unsplit_leaves"),
])
def test_planted_sparse_fault_is_not_correct(bench_copy, capsys, fault,
                                             number):
    from benchmark.kinds import train_window_sparse
    from benchmark.manifest import Manifest

    cell = add_sparse_cell(bench_copy)
    res, _ = bench_copy.run(capsys, cell, seed=5, fault=fault)
    assert res["correct"] is False
    assert number in over(res), res["checks"]
    man = Manifest(str(bench_copy.root))
    entry = man.cell(cell)
    nxt = train_window_sparse.Cell(man.config(entry), man.traffic(entry), 5,
                                   None)
    assert nxt.fault is None and "greedy_tail" not in nxt.PARAM_FAULTS


def test_a_program_without_the_bundle_facts_is_refused(bench_copy, capsys,
                                                       monkeypatch):
    """The parent commit's program notes no ``train.features_raw`` nor
    the bundles' counts: the run ends before a round is trained."""
    from lightgbm_tpu.utils import profiling

    profiling.reset()
    real = profiling.note

    def no_bundle_facts(name, value):
        if "bundle" not in name and name != "train.features_raw":
            real(name, value)

    monkeypatch.setattr(profiling, "note", no_bundle_facts)
    cell = add_sparse_cell(bench_copy)
    with pytest.raises(SystemExit, match="dataset.bundle_columns"):
        bench_copy.run(capsys, cell)
    assert capsys.readouterr().out.strip() == ""


def test_a_failing_probe_fails_the_run(bench_copy, capsys, monkeypatch):
    from lightgbm_tpu.models import gbdt

    def broken(self):
        raise RuntimeError("the member scan broke")

    monkeypatch.setattr(gbdt.Booster, "_member_scan_call", broken)
    cell = add_sparse_cell(bench_copy)
    with pytest.raises(RuntimeError, match="the member scan broke"):
        bench_copy.run(capsys, cell, seed=7)
    assert capsys.readouterr().out.strip() == ""


def test_real_cell_limits_lie_between_their_readings():
    """``leaf_value_worst``: PERF.md section 2's readings of this cell on
    the chip, the sound runs' 0.004152 and the int8 control's 0.0212; the
    exact limits stay 0."""
    from benchmark.manifest import Manifest

    man = Manifest()
    limits = man.config(man.cell(CELL))["limits"]
    assert 2 * 0.004152 < limits["leaf_value_worst"] < 0.0212 / 2
    assert limits["unsplit_leaves"] == limits["dump_missing"] == 0


# -- the real cell's entries -------------------------------------------------

def test_real_cell_resolves_to_its_three_files(manifest):
    from benchmark.manifest import load_kind

    man = manifest
    cell = man.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "train-window-sparse"
    config, traffic = man.config(cell), man.traffic(cell)
    assert (config["rows"], config["features"]) == (1_000_000, 968)
    assert config["reduced"] == ["num_trees"]
    assert not {"hist_dtype", "wave_tail", "wave_width", "hist_impl"} \
        & set(config["params"])
    assert config["params"]["enable_bundle"] is True
    assert config["params"]["max_conflict_rate"] == 0.0
    assert config["params"]["min_sum_hessian_in_leaf"] == 100.0
    assert config["control"]["params"] == {"hist_dtype": "int8"}
    assert isinstance(config["table_seed"], int)
    assert len(config["source"]) <= 200 and "Bosch" in config["source"]
    assert config["limits"]["bundle_conflict_rows"] == 0
    assert traffic["kind"] == "train_window_sparse"
    assert {k: traffic[k] for k in (
        "rounds_per_call", "checked_rounds", "sample_rows", "split_nodes",
        "order_leaves", "trace_seconds")} == {
            "rounds_per_call": 1, "checked_rounds": 2, "sample_rows": 100000,
            "split_nodes": 4, "order_leaves": 4, "trace_seconds": 20}
    kind = load_kind(traffic["kind"])
    assert {"fewer_leaves", "restricted_features"} <= set(
        kind.Cell.PARAM_FAULTS)
    names = {m["name"] for m in man.metrics_of(CELL, "end_to_end")}
    assert names == {"train_rows_rounds_per_s", "setup_s"}


def test_sparse_metrics_are_listed_for_the_sparse_cell_alone(manifest):
    from benchmark.manifest import Manifest

    man = manifest
    listed = {m["name"]: m for m in man.doc["per_layer"]}
    committed = Manifest(REPO)
    for name in SPARSE_METRICS:
        assert CELL in listed[name]["workloads"]
        for m in committed.doc["per_layer"]:
            if m["name"] == name:
                for cell in m["workloads"]:
                    kind = committed.traffic(committed.cell(cell))["kind"]
                    assert kind == "train_window_sparse", (name, cell)
        assert listed[name]["layer"] == "bundling"
        spec = man.metric_spec(name)
        assert spec["what"] and spec["unit"] == listed[name]["unit"]
        assert not os.path.exists(os.path.join(
            man.root, "benchmark", "metrics", name + ".py"))
    mine = {m["name"] for m in man.metrics_of(CELL, "per_layer")}
    assert set(SPARSE_METRICS) <= mine
    assert {"hist_root_roofline", "hist_narrow_roofline", "grower_xla_pct",
            "train_floor_mfu_pct", "device_idle_pct.train",
            "narrow_calls_per_round", "hist_narrow_busy_pct",
            "update_many_host_ms", "program_build_s", "binning_s",
            "binning_edges_s", "binning_codes_s", "binning_other_s",
            "narrow_pass_parent_rows_pct",
            "narrow_pass_direct_rows_pct"} <= mine
    # a tree of this table (42-48 leaves at min_sum_hessian_in_leaf 100)
    # runs no full-width pass: nothing for the wide pass's metrics to read
    assert not {"hist_wave_roofline", "wave_passes_per_round",
                "wide_pass_parent_rows_pct", "wide_pass_direct_rows_pct",
                "hist_wave_hilo_roofline", "wave_calls_per_round",
                "rank_grad_ms"} & mine
    for cell in ("higgs-10m5.train", "epsilon-400k.train",
                 "mslr-web30k.train"):
        assert not set(SPARSE_METRICS) & {
            m["name"] for m in man.metrics_of(cell, "per_layer")}
