"""The wide cell (``epsilon-400k.train``, PR 28): its files load as data
only, every metric that lists it finds its shapes, and the program's
multi-block route holds against the reference at a small wide size."""

import copy
import json
import re

import pytest

from bench_helpers import TINY_CONFIG

CELL = "epsilon-400k.train"
PATH_PARAMS = {"hist_dtype", "wave_tail", "wave_width", "hist_impl"}

# 4,096 x 60 at 255 bins and 31 leaves: three VMEM feature blocks of 24,
# the last one half padding, through the interpreted Pallas kernels
WIDE_TINY = dict(
    copy.deepcopy(TINY_CONFIG), rows=4096, features=60,
    params={"objective": "binary", "num_leaves": 31, "learning_rate": 0.1,
            "max_bin": 255, "min_data_in_leaf": 1,
            "min_sum_hessian_in_leaf": 10.0, "lambda_l2": 0.0,
            "hist_impl": "pallas", "verbosity": -1},
    reference={"learning_rate": 0.1, "lambda_l2": 0.0, "num_leaves": 31,
               "max_bin": 255, "min_sum_hessian_in_leaf": 10.0})


def _add_wide_cell(bench_copy, name, config):
    """A cell of the new traffic mix on a small wide table."""
    return bench_copy.add_cell(name, config, "train-window-wide", like=CELL)


@pytest.mark.parametrize("hist_dtype,tail", [
    ("f32", "greedy"), ("f32", "exact"), ("bf16", "greedy"),
    ("bf16", "exact")])
def test_multi_block_route_holds_against_the_reference(
        bench_copy, capsys, monkeypatch, hist_dtype, tail):
    """``gbdt_check`` on the trees of the partition-fused multi-block
    kernel, hi/lo float32 and bfloat16, greedy and exact tail, at the
    limits of the harness's tiny cell (bfloat16: the leaf values at the
    limit of the bfloat16 cell; the greedy tail: no order of growth to
    hold it to)."""
    from lightgbm_tpu.ops import histogram_pallas
    from lightgbm_tpu.utils import profiling

    traced = []
    fused = histogram_pallas.hist_partition_fused_pallas

    def spy(bins_t, *args, **kwargs):
        traced.append(tuple(bins_t.shape))
        return fused(bins_t, *args, **kwargs)

    monkeypatch.setattr(histogram_pallas, "hist_partition_fused_pallas", spy)
    cfg = copy.deepcopy(WIDE_TINY)
    cfg["params"]["wave_tail"] = tail
    if hist_dtype == "bf16":
        cfg["params"]["hist_dtype"] = "bf16"
        cfg["limits"]["leaf_value_worst"] = 0.02
    if tail == "greedy":
        del cfg["limits"]["order_excess"]
    cell = _add_wide_cell(bench_copy, f"wide-{hist_dtype}-{tail}", cfg)
    res, _ = bench_copy.run(capsys, cell)
    over = {n: c for n, c in res["checks"].items()
            if c["value"] > c["limit"]}
    assert res["correct"] is True and not over, over
    # the wave pass was the partition-fused kernel over 72 feature rows
    assert traced and {shape[0] for shape in traced} == {72}
    facts = profiling.snapshot()["facts"]
    assert facts["train.hist_dtype"] == hist_dtype
    assert facts["train.wave_tail"] == tail
    assert facts["train.feature_blocks"] == 3
    assert facts["train.features_padded"] == 72 > facts["train.features"]
    assert facts["train.hist_calls_per_pass"] == (
        2 if hist_dtype == "f32" else 1)
    # two checked rounds in set-up, then the window's
    assert res["counters"]["total_rounds"] == \
        2 + res["counters"]["window_rounds"]


def test_wide_cell_is_data_only(manifest):
    """The cell is an entry and three kinds of data file: a configuration
    that selects no path of the program, the kind that is there, and
    metric files that the general readers read."""
    from benchmark.manifest import load_kind

    man = manifest
    cell = man.cell(CELL)
    assert cell["chips"] == 1
    config, traffic = man.config(cell), man.traffic(cell)
    assert (config["rows"], config["features"]) == (400_000, 2000)
    assert not PATH_PARAMS & set(config["params"])
    assert config["reduced"] == ["num_trees"]
    assert set(config["limits"]) == set(
        man.config(man.cell("higgs-10m5.train"))["limits"])
    assert load_kind(traffic["kind"]).Cell
    assert {k: traffic[k] for k in (
        "rounds_per_call", "checked_rounds", "sample_rows", "split_nodes",
        "order_leaves", "trace_seconds")} == {
            "rounds_per_call": 1, "checked_rounds": 2, "sample_rows": 100000,
            "split_nodes": 4, "order_leaves": 4, "trace_seconds": 20}
    reported = {}
    for group in ("end_to_end", "per_layer"):
        names = {m["name"] for m in man.metrics_of(CELL, group)}
        assert names, group
        for m in man.doc[group]:
            if m["name"] in names and "workloads" in m:
                assert CELL in m["workloads"]
        reported[group] = names
    assert reported["end_to_end"] == {"train_rows_rounds_per_s", "setup_s"}
    # the program resolves f32 here: each pass is two bf16 kernel events,
    # which only the hi/lo rooflines and the calls a round count as such
    assert {"hist_wave_hilo_roofline", "hist_root_hilo_roofline",
            "hist_narrow_hilo_roofline", "wave_calls_per_round"} \
        <= reported["per_layer"]
    assert not {"hist_wave_roofline", "hist_root_roofline",
                "hist_narrow_roofline", "wave_passes_per_round"} \
        & reported["per_layer"]


def test_every_metric_of_the_wide_cell_resolves_its_shapes(monkeypatch):
    """Each per-layer metric that lists the cell: a general reader (no
    code of its own), and every name its file asks for is one the run
    has: a fact ``Booster._fused_segment`` notes, a counter of the kind,
    a key of the configuration."""
    import os

    import numpy as np

    import lightgbm_tpu as lgb
    from benchmark.manifest import Manifest
    from benchmark.reduce import work
    from lightgbm_tpu.utils import profiling

    rng = np.random.default_rng(0)
    X = rng.standard_normal((4096, 8)).astype(np.float32)
    booster = lgb.Booster(
        {"objective": "binary", "num_leaves": 31, "verbosity": -1},
        lgb.Dataset(X, label=(X[:, 0] > 0).astype(np.float32)))
    booster._fused_segment(1)
    facts = profiling.snapshot()["facts"]
    for fact in ("feature_blocks", "features_padded", "chunk_rows",
                 "hist_calls_per_pass"):
        assert isinstance(facts["train." + fact], int)

    man = Manifest()
    config = man.config(man.cell(CELL))
    counters = {"rows", "features", "rows_padded", "code_bytes",
                "window_rounds", "window_calls"}
    wanted = man.metrics_of(CELL, "per_layer")
    assert {"hist_wave_hilo_roofline", "hist_root_hilo_roofline",
            "wave_calls_per_round", "train_floor_mfu_pct",
            "grower_xla_pct"} <= {m["name"] for m in wanted}
    for m in wanted:
        spec = man.metric_spec(m["name"])
        assert not os.path.exists(os.path.join(
            man.root, "benchmark", "metrics", m["name"] + ".py"))
        assert callable(man.metric_reader(m["name"], spec))
        for found in re.findall(r'"(program|counter|config):([^"]+)"',
                                json.dumps(spec)):
            where, key = found
            assert key in {"program": facts, "counter": counters,
                           "config": config}[where], (m["name"], found)
        for call in spec.get("calls", []):
            shapes = {k: (facts[v.split(":", 1)[1]]
                          if str(v).startswith("program:") else v)
                      for k, v in call["shapes"].items()}
            if "hilo" in m["name"]:
                assert shapes["dtype"] == "bf16"
            done = getattr(work, call["work"])(shapes)
            assert done["ops"] > 0 and done["bytes"] > 0
