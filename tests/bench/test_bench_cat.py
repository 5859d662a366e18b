"""The categorical cell (``allstate-13m.train``): its generator, its
reference, its kind end to end at a tiny size on the CPU with the control
and every planted fault, and its entries in the real ``BENCHMARK.json``."""

import copy
import os
import re

import numpy as np
import pytest

from bench_helpers import REPO, TINY_CONFIG

CELL = "allstate-13m.train"
CAT_METRICS = ("cat_scan_ms", "cat_split_pct")

# 20,000 rows of the Allstate layout: 0.73 % claims leave about 140 of
# hessian at the root, so a leaf is asked for 1 of it; the categorical
# parameters are LightGBM's defaults, as the real cell's
CAT_PARAMS = {"cat_smooth": 10.0, "cat_l2": 10.0, "max_cat_threshold": 32,
              "max_cat_to_onehot": 4, "min_data_per_group": 100}
TINY_CAT = dict(
    copy.deepcopy(TINY_CONFIG), rows=20000, features=32,
    params=dict({"objective": "binary", "num_leaves": 15,
                 "learning_rate": 0.1, "max_bin": 255, "min_data_in_bin": 3,
                 "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 1.0,
                 "lambda_l2": 0.0, "hist_dtype": "f32", "verbosity": -1},
                **CAT_PARAMS),
    reference=dict({"learning_rate": 0.1, "lambda_l2": 0.0,
                    "num_leaves": 15, "max_bin": 255, "min_data_in_bin": 3,
                    "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 1.0},
                   **CAT_PARAMS),
    control={"params": {"hist_dtype": "bf16"}},
    limits={"leaves_off": 0, "split_gain_short": 0.15, "order_excess": 0.1,
            "leaf_value_worst": 1e-4, "leaf_count_off": 0,
            "score_abs": 1e-5, "final_walk_abs": 1e-5,
            "cat_dump_faults": 0})


def add_cat_cell(bench_copy, name="tiny-cat", config=None):
    """A tiny categorical configuration and its cell, built as data: files
    and entries, the metrics of the real cell listed for it too."""
    return bench_copy.add_cell(name, config or TINY_CAT, "train-window-cat",
                               like=CELL)


def over(res):
    return {n for n, c in res["checks"].items() if c["value"] > c["limit"]}


# -- the generator -----------------------------------------------------------

def test_datagen_cat_gives_the_stated_shape():
    from benchmark import datagen_cat as dc

    assert dc.FEATURES == 32 and len(dc.CATEGORICAL) == 16
    assert [dc.COLUMNS[c] for c in dc.CATEGORICAL[:3]] == [
        "Blind_Make", "Blind_Model", "Blind_Submodel"]
    X, y = dc.allstate_like(300_000, 2**31 + 3)
    assert X.shape == (300_000, 32) and X.dtype == np.float32
    assert abs(y.mean() - dc.POSITIVE_SHARE) < 0.001
    levels = {dc.COLUMNS[c]: len(np.unique(X[~np.isnan(X[:, c]), c]))
              for c in dc.CATEGORICAL}
    assert levels["Blind_Make"] == 75 and levels["NVCat"] == 15
    assert 1000 < levels["Blind_Model"] <= 1300
    assert 1800 < levels["Blind_Submodel"] <= 2700
    assert all(2 <= levels[f"Cat{i}"] <= 10 for i in range(1, 13))
    # the 254 most common submodels leave a real overflow bin
    sub = X[:, 5]
    top = np.sort(np.unique(sub, return_counts=True)[1])[::-1]
    assert 0.5 < top[:254].sum() / len(sub) < 0.8
    # the hierarchy holds: a submodel has one model, a model one make
    for child, parent in ((5, 4), (4, 3)):
        pairs = np.unique(X[:, [child, parent]], axis=0)
        assert len(pairs) == len(np.unique(X[:, child]))
    missing = np.isnan(X).mean(axis=0)
    assert 0.3 < missing[dc.COLUMNS.index("Cat7")] < 0.7
    assert missing[dc.COLUMNS.index("Var1")] == 0.0
    # one table whatever the threads' order; another seed, another table
    again, _ = dc.allstate_like(300_000, 2**31 + 3)
    assert np.array_equal(again, X, equal_nan=True)
    other, _ = dc.allstate_like(300_000, 2**31 + 4)
    assert not np.array_equal(other, X, equal_nan=True)


def test_categorical_after_follows_reorder_columns():
    from benchmark import datagen, datagen_cat as dc

    X, _ = dc.allstate_like(2000, 5)
    moved = X.copy()
    datagen.reorder_columns(moved, 77)
    order = np.random.default_rng(77).permutation(32)
    assert np.array_equal(moved, X[:, order], equal_nan=True)
    cats = dc.categorical_after(order)
    assert sorted(order[cats]) == list(dc.CATEGORICAL)


def test_datagen_cat_and_reference_import_nothing_of_the_program():
    for rel in ("datagen_cat.py", os.path.join("reference", "cat_check.py")):
        src = open(os.path.join(REPO, "benchmark", rel)).read()
        assert not re.search(r"^\s*(from|import)\s+(lightgbm_tpu|jax)", src,
                             re.M)


# -- the reference -----------------------------------------------------------

def test_reference_routes_sets_by_raw_value_and_nan_right():
    from benchmark.reference import cat_check

    X = np.array([[3.0, 0.5], [7.0, 0.5], [12.0, 2.0], [np.nan, 0.5],
                  [99.0, 0.5], [7.0, np.nan]], np.float32)
    table = cat_check.Table(X, [0])
    dump = {"split_feature": 0, "decision_type": "==", "threshold": "3||7",
            "default_left": False,
            "left_child": {"split_feature": 1, "decision_type": "<=",
                           "threshold": 1.0,
                           "left_child": {"leaf_value": -1.0},
                           "right_child": {"leaf_value": -2.0}},
            "right_child": {"leaf_value": 1.0}}
    tree = cat_check.flatten_tree(dump, table)
    leaf = cat_check.route(table, tree)
    assert tree["value"][leaf].tolist() == [-1.0, -1.0, 1.0, 1.0, 1.0, -2.0]
    assert cat_check.parse_set("3||7||-2.5") .tolist() == [3.0, 7.0, -2.5]
    assert cat_check.dump_faults(dump, {0}) == 0
    assert cat_check.dump_faults(dict(dump, default_left=True), {0}) == 1
    numeric = dict(dump, decision_type="<=", threshold=5.0)
    assert cat_check.dump_faults(numeric, {0}) == 1
    with pytest.raises(ValueError, match="not categorical"):
        cat_check.flatten_tree(dict(dump, split_feature=1), table)


def test_reference_keeps_categories_by_lightgbms_rule():
    """By count, at most max_bin - 1, none past the second seen fewer than
    min_data_in_bin times in a 200,000-row sample's proportion."""
    from benchmark.reference import cat_check

    counts = [50, 40, 30, 2, 1, 1]
    X = np.repeat(np.arange(6.0), counts)[:, None].astype(np.float32)
    table = cat_check.Table(X, [0])
    kept = cat_check.kept_categories(table, {"max_bin": 255})[0]
    assert kept.tolist() == [0, 1, 2]
    assert cat_check.kept_categories(
        table, {"max_bin": 3, "min_data_in_bin": 1})[0].tolist() == [0, 1]
    assert cat_check.kept_categories(
        table, {"max_bin": 255, "min_data_in_bin": 1})[0].tolist() == \
        list(range(6))


# -- the kind, end to end ----------------------------------------------------

def test_tiny_cat_cell_is_correct_and_reports_its_facts(bench_copy, capsys):
    """Through the wave grower's partition-fused kernels (interpret mode,
    trees of 31 leaves: waves, the exact tail and its narrow passes): the
    category sets route rows inside them."""
    from lightgbm_tpu.utils import profiling

    profiling.reset()
    fused = copy.deepcopy(TINY_CAT)
    fused["params"].update(hist_impl="pallas", num_leaves=31)
    fused["reference"]["num_leaves"] = 31
    # the exact tail's order at 31 leaves of a few hundred rows each reads
    # 0.44 here through either route, and 0.16 on the numeric tiny table of
    # bench_helpers at 31 leaves: what the tail does at this size, not the
    # routing (the real cell's limit comes from its readings on the chip)
    fused["limits"]["order_excess"] = 1.0
    cell = add_cat_cell(bench_copy, config=fused)
    res, err = bench_copy.run(capsys, cell, seed=2**31 + 11, trace=1)
    assert res["correct"] is True and not over(res), over(res)
    c = res["counters"]
    assert c["total_rounds"] == 3 + c["window_rounds"]
    assert c["categorical_features"] == c["cat_features"] == 16
    assert c["cat_route"] == "fused"
    assert c["codes_path"] == "host"        # the CPU codes on the host
    assert c["cat_scan_ms"] > 0
    assert c["cat_scan_s"] * 1000.0 > 5 * c["cat_scan_ms"]
    assert c["cat_dump_faults"] == 0
    assert 0 < c["cat_split_pct"] <= 100
    assert all(n > 0 for n in c["reference_cat_splits"])
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert got["cat_split_pct"] == c["cat_split_pct"]
    assert got["cat_scan_ms"] == c["cat_scan_ms"]
    for name in ("cat_dump_faults", "final_walk_abs", "leaves_off"):
        assert name in res["checks"] and f"[check] {name}" in err


def test_two_seeds_of_one_table_grow_the_same_trees(bench_copy, capsys):
    """``table_seed``: the seed orders the columns, and the kind follows
    the categorical ones; the trees' statistics are the table's."""
    cell = add_cat_cell(bench_copy, "tiny-cat-table",
                        dict(copy.deepcopy(TINY_CAT), table_seed=77))

    def run(seed):
        res, _ = bench_copy.run(capsys, cell, seed=seed)
        assert res["correct"] is True, res["checks"]
        return res["counters"]

    a, b = run(2**31 + 1), run(2**31 + 2)
    assert a["reference_cat_splits"] == b["reference_cat_splits"]
    assert a["reference_loss"] == pytest.approx(b["reference_loss"],
                                                rel=1e-6)


def test_cat_control_comes_out_not_correct(bench_copy, capsys):
    from benchmark.readings import variant

    cfg, fault = variant(copy.deepcopy(TINY_CAT), "control")
    assert fault is None and cfg["params"]["hist_dtype"] == "bf16"
    cell = add_cat_cell(bench_copy, "tiny-cat-control", cfg)
    res, _ = bench_copy.run(capsys, cell, seed=3)
    assert res["correct"] is False
    assert "leaf_value_worst" in over(res)


@pytest.mark.parametrize("fault,number", [
    ("state_unchanged", "trees_missing"),
    ("half_batch", "leaf_count_off"),
    ("altered_answer", "leaf_value_worst"),
    ("restricted_features", "split_gain_short"),
    ("fewer_leaves", "leaves_off"),
    ("numeric_codes", "cat_dump_faults"),
])
def test_planted_cat_fault_is_not_correct(bench_copy, capsys, fault, number):
    cell = add_cat_cell(bench_copy)
    res, _ = bench_copy.run(capsys, cell, seed=5, fault=fault)
    assert res["correct"] is False
    assert number in over(res), res["checks"]
    if fault == "numeric_codes":
        # thresholds on category numbers: the search finds better sets
        assert "split_gain_short" in over(res)
        assert res["counters"].get("cat_split_pct", 0.0) == 0.0


def test_numeric_codes_in_readings_bins_the_shared_rows_again(bench_copy):
    """``benchmark/readings.py`` makes the rows once a seed; the fault
    bins them again with no categorical column."""
    from benchmark.kinds import train_window_cat
    from benchmark.manifest import Manifest

    cell = add_cat_cell(bench_copy)
    man = Manifest(str(bench_copy.root))
    entry = man.cell(cell)
    config, traffic = man.config(entry), man.traffic(entry)
    base = train_window_cat.Cell(config, traffic, 5, None)
    base.make_inputs()
    run = train_window_cat.Cell(config, traffic, 5, None,
                                fault="numeric_codes")
    run.share_inputs(base)
    assert run.dataset is not base.dataset
    assert not run.dataset.bin_mapper.is_categorical.any()
    assert base.dataset.bin_mapper.is_categorical.sum() == 16


def test_a_program_without_the_cat_facts_is_refused(bench_copy, capsys,
                                                    monkeypatch):
    """The parent commit's program notes no ``train.cat_route``: the run
    ends before a round is trained."""
    import jax

    from lightgbm_tpu.utils import profiling

    profiling.reset()
    real = profiling.note

    def no_cat_facts(name, value):
        if "cat" not in name:
            real(name, value)

    monkeypatch.setattr(profiling, "note", no_cat_facts)
    cell = add_cat_cell(bench_copy)
    try:
        with pytest.raises(SystemExit, match="train.cat_route"):
            bench_copy.run(capsys, cell)
    finally:
        # the grower notes its route as a program is traced: no later test
        # may reuse a trace made here, or one made before the reset
        jax.clear_caches()
    assert capsys.readouterr().out.strip() == ""


def test_a_failing_probe_fails_the_run(bench_copy, capsys, monkeypatch):
    from lightgbm_tpu.models import gbdt

    def broken(self):
        raise RuntimeError("the categorical scan broke")

    monkeypatch.setattr(gbdt.Booster, "_cat_scan_call", broken)
    cell = add_cat_cell(bench_copy)
    with pytest.raises(RuntimeError, match="the categorical scan broke"):
        bench_copy.run(capsys, cell, seed=7)
    assert capsys.readouterr().out.strip() == ""


# -- the real cell's entries -------------------------------------------------

def test_real_cell_limits_lie_between_their_readings():
    """PERF.md section 2's readings of this cell on a TPU v5 lite: the
    largest sound one, the int8 control's smallest and the faults'
    smallest.  The control reads ``leaf_count_off`` 1 and
    ``leaf_value_worst`` 0.01124 on every seed."""
    from benchmark.manifest import Manifest

    man = Manifest()
    limits = man.config(man.cell(CELL))["limits"]
    assert 1.4 * 0.005256 < limits["leaf_value_worst"] < 0.01124 / 1.35
    assert 5 * 0.02633 < limits["split_gain_short"] < 0.8004 / 5
    assert limits["leaf_count_off"] == 0
    assert limits["order_excess"] < 0.5848 / 10


def test_real_cell_resolves_to_its_three_files(manifest):
    from benchmark.manifest import load_kind

    man = manifest
    cell = man.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "train-window-cat"
    config, traffic = man.config(cell), man.traffic(cell)
    assert (config["rows"], config["features"]) == (13_184_290, 32)
    assert config["reduced"] == ["num_trees"]
    assert not {"hist_dtype", "wave_tail", "wave_width", "hist_impl"} \
        & set(config["params"])
    for key, value in CAT_PARAMS.items():
        assert config["params"][key] == config["reference"][key] == value
    assert config["params"]["min_sum_hessian_in_leaf"] == 100.0
    assert config["control"]["params"] == {"hist_dtype": "int8"}
    assert isinstance(config["table_seed"], int)
    assert len(config["source"]) <= 200 and "Allstate" in config["source"]
    assert config["limits"]["cat_dump_faults"] == 0
    assert "final_score_abs" not in config["limits"]
    assert traffic["kind"] == "train_window_cat"
    assert {k: traffic[k] for k in (
        "rounds_per_call", "checked_rounds", "sample_rows", "split_nodes",
        "order_leaves", "trace_seconds")} == {
            "rounds_per_call": 1, "checked_rounds": 3, "sample_rows": 200000,
            "split_nodes": 16, "order_leaves": 16, "trace_seconds": 10}
    kind = load_kind(traffic["kind"])
    assert "numeric_codes" in kind.Cell.TIMED_PATH_FAULTS
    assert {"fewer_leaves", "restricted_features"} <= set(
        kind.Cell.PARAM_FAULTS)
    names = {m["name"] for m in man.metrics_of(CELL, "end_to_end")}
    assert names == {"train_rows_rounds_per_s", "setup_s"}


def test_cat_metrics_are_listed_for_the_cat_cell_alone(manifest):
    from benchmark.manifest import Manifest

    man = manifest
    listed = {m["name"]: m for m in man.doc["per_layer"]}
    committed = Manifest(REPO)
    for name in CAT_METRICS:
        assert CELL in listed[name]["workloads"]
        for m in committed.doc["per_layer"]:
            if m["name"] == name:
                for cell in m["workloads"]:
                    kind = committed.traffic(committed.cell(cell))["kind"]
                    assert kind == "train_window_cat", (name, cell)
        assert listed[name]["layer"] == "growers"
        spec = man.metric_spec(name)
        assert spec["what"] and spec["unit"] == listed[name]["unit"]
        assert spec["counter"] == name
        assert not os.path.exists(os.path.join(
            man.root, "benchmark", "metrics", name + ".py"))
    mine = {m["name"] for m in man.metrics_of(CELL, "per_layer")}
    assert set(CAT_METRICS) <= mine
    assert {"grower_xla_pct", "train_floor_mfu_pct", "device_idle_pct.train",
            "narrow_calls_per_round", "hist_narrow_busy_pct",
            "update_many_host_ms", "program_build_s", "binning_s",
            "binning_edges_s", "binning_codes_s", "binning_other_s",
            "narrow_pass_parent_rows_pct",
            "narrow_pass_direct_rows_pct"} <= mine
    # the kernels' rooflines count full-height one-hots, and this table's
    # are about 0.4 of that: the cell reads none of them
    assert not {"hist_wave_roofline", "hist_root_roofline",
                "hist_narrow_roofline",
                "hist_wave_hilo_roofline", "wave_calls_per_round",
                "rank_grad_ms", "efb_column_ratio",
                "efb_member_scan_ms"} & mine
    for cell in ("higgs-10m5.train", "epsilon-400k.train",
                 "mslr-web30k.train", "bosch-1m.train"):
        assert not set(CAT_METRICS) & {
            m["name"] for m in man.metrics_of(cell, "per_layer")}
