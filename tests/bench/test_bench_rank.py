"""The ranking cell (``mslr-web30k.train``, PR 37): its generator, its
reference, its kind end to end at a tiny size on the CPU with the control
and every planted fault, and its entries in the real ``BENCHMARK.json``."""

import copy
import os

import numpy as np
import pytest

from bench_helpers import REPO, TINY_RANK

CELL = "mslr-web30k.train"
RANK_METRICS = ("rank_grad_ms", "rank_pair_slot_ratio", "rank_pad_ratio",
                "rank_pack_s")


def add_rank_cell(bench_copy, name="tiny-rank", config=None):
    """A tiny ranking configuration and its cell, built as data: files and
    entries, the metrics of the real cell listed for it too."""
    return bench_copy.add_cell(name, config or TINY_RANK,
                               "train-window-rank", like=CELL)


def over(res):
    return {n for n, c in res["checks"].items() if c["value"] > c["limit"]}


# -- the generator -----------------------------------------------------------

def test_datagen_rank_gives_the_stated_shape():
    from benchmark import datagen_rank

    X, y, sizes = datagen_rank.mslr_like(22_703, 136, 189, 2**31 + 5)
    assert X.shape == (22_703, 136) and X.dtype == np.float32
    assert sizes.sum() == 22_703 and len(sizes) == 189
    assert sizes.min() == 1 and sizes.max() == 1251      # both ends present
    assert set(np.unique(y)) == {0.0, 1.0, 2.0, 3.0, 4.0}
    shares = np.bincount(y.astype(int)) / len(y)
    assert np.allclose(shares, datagen_rank.LABEL_SHARES, atol=0.002)
    distinct = np.array([len(np.unique(X[:, j])) for j in range(136)])
    assert (distinct[:15] < 16).all()
    assert (distinct[15:49] < 255).all() and (distinct[15:49] > 16).all()
    assert (distinct[54:] > 10_000).all()                # continuous
    # the query's own columns: constant within nine queries of ten
    start = np.cumsum(sizes) - sizes
    long_ = np.flatnonzero(sizes > 1)
    const = [np.ptp(X[start[q]:start[q] + sizes[q], 50]) == 0 for q in long_]
    assert 0.8 < np.mean(const) < 0.98
    again = datagen_rank.mslr_like(22_703, 136, 189, 2**31 + 5)
    assert all(np.array_equal(a, b) for a, b in zip((X, y, sizes), again))
    other = datagen_rank.mslr_like(22_703, 136, 189, 2**31 + 6)
    assert not np.array_equal(other[2], sizes)


@pytest.mark.parametrize("rows,queries,lo,hi", [
    (2_270_296, 18_919, 1, 1251), (6000, 80, 1, 400), (50, 50, 1, 60),
    (100, 2, 1, 60), (400, 1, 1, 400)])
def test_query_sizes_sum_to_the_rows(rows, queries, lo, hi):
    from benchmark import datagen_rank

    sizes = datagen_rank.query_sizes(
        rows, queries, np.random.default_rng(3), lo, hi)
    assert sizes.sum() == rows and len(sizes) == queries
    assert sizes.min() >= lo and sizes.max() <= hi
    if rows == 2_270_296:
        assert sizes.min() == 1 and sizes.max() == 1251
        assert 85 < np.median(sizes) < 100          # heavy-tailed: mean 120
    with pytest.raises(ValueError):
        datagen_rank.query_sizes(10, 20, np.random.default_rng(0), 1, 5)


# -- the reference -----------------------------------------------------------

def test_reference_lambdas_by_hand():
    """Two documents, labels 1 and 0, equal scores, no norm: one pair, p =
    1/2, dNDCG = (1 - 0) * (1 - 1/log2(3)) / 1."""
    from benchmark.reference import rank_check

    hyper = dict(sigmoid=1.0, lambdarank_truncation_level=30,
                 lambdarank_norm=False)
    g, h = rank_check.lambdas_one_query(np.zeros(2), np.array([1.0, 0.0]),
                                        hyper)
    delta = 1.0 - 1.0 / np.log2(3.0)
    assert g == pytest.approx([-0.5 * delta, 0.5 * delta])
    assert h == pytest.approx([0.25 * delta, 0.25 * delta])
    # with the norm: scores tie, so no division; L = 2 * lambda
    g2, _ = rank_check.lambdas_one_query(
        np.zeros(2), np.array([1.0, 0.0]), dict(hyper, lambdarank_norm=True))
    L = delta
    assert g2 == pytest.approx(g * np.log2(1 + L) / L)
    # scores apart: the 0.01 + |ds| term, and the better one ranked below
    g3, _ = rank_check.lambdas_one_query(
        np.array([0.0, 1.0]), np.array([1.0, 0.0]),
        dict(hyper, lambdarank_norm=True))
    p = 1.0 / (1.0 + np.exp(-1.0))
    lam = p * delta / 1.01
    assert g3 == pytest.approx(
        np.array([-lam, lam]) * np.log2(1 + 2 * lam) / (2 * lam))
    # no relevant document, one document: nothing
    for labels in (np.zeros(5), np.array([3.0])):
        g4, h4 = rank_check.lambdas_one_query(
            np.arange(len(labels), dtype=float), labels, hyper)
        assert not g4.any() and not h4.any()


def test_reference_ndcg_by_hand():
    from benchmark.reference import rank_check

    sizes = np.array([3, 2, 2])
    y = np.array([0.0, 2.0, 1.0, 0.0, 0.0, 1.0, 0.0])
    perfect = rank_check.ndcg_at(y.copy(), y, sizes, 10)
    assert perfect == pytest.approx(1.0)
    s = np.array([3.0, 2.0, 1.0, 0.0, 0.0, 0.0, 1.0])
    q0 = (0 + 3 / np.log2(3) + 1 / 2) / (3 + 1 / np.log2(3))
    q2 = (1 / np.log2(3)) / 1.0
    assert rank_check.ndcg_at(s, y, sizes, 10) == \
        pytest.approx((q0 + 1.0 + q2) / 3)            # no relevant: 1
    assert rank_check.ndcg_at(s, y, sizes, 1) == pytest.approx((0 + 1 + 0) / 3)


def test_reference_reads_what_its_shortcut_hides():
    """``own_walk_gap``: ranking by the reference's own walk (rounded to
    float32) against ranking by the program's stored scores."""
    from benchmark.reference import rank_check

    hyper = dict(sigmoid=1.0, lambdarank_truncation_level=30,
                 lambdarank_norm=True)
    sizes = np.array([3, 1, 4])
    y = np.array([2.0, 0.0, 1.0, 3.0, 0.0, 1.0, 0.0, 4.0])
    stored = np.array([0.5, 0.25, 0.25, 1.0, 0.1, 0.3, 0.2, 0.2], np.float32)
    assert rank_check.ranks_in_query(stored, sizes).tolist() == \
        [0, 1, 2, 0, 3, 0, 1, 2]
    g, _ = rank_check.lambdas(stored, y, sizes, hyper)
    same = rank_check.own_walk_gap(stored.copy(), stored, g, y, sizes, hyper)
    assert same == {"own_rank_flips": 0, "own_grad_rows": 0,
                    "own_grad_gap": 0.0}
    # the last bit of one score, no rank moved: under the 1e-4 of a gradient
    bit = stored.copy()
    bit[0] = np.nextafter(bit[0], np.float32(9))
    near = rank_check.own_walk_gap(bit, stored, g, y, sizes, hyper)
    assert near["own_rank_flips"] == 0 and near["own_grad_rows"] == 0
    assert 0 < near["own_grad_gap"] < 1e-5
    # a tie broken the other way: rows 6 and 7 trade ranks, and the last
    # query's gradients move (row 7 holds the best label)
    flip = stored.copy()
    flip[7] = np.nextafter(flip[7], np.float32(9))
    far = rank_check.own_walk_gap(flip, stored, g, y, sizes, hyper)
    assert far["own_rank_flips"] == 2
    assert 2 <= far["own_grad_rows"] <= 4 and far["own_grad_gap"] > 1e-2


def test_reference_imports_nothing_of_the_program():
    import re

    for name in ("rank_check.py",):
        src = open(os.path.join(REPO, "benchmark", "reference", name)).read()
        assert not re.search(r"^\s*(from|import)\s+(lightgbm_tpu|jax)", src,
                             re.M)
    src = open(os.path.join(REPO, "benchmark", "datagen_rank.py")).read()
    assert not re.search(r"^\s*(from|import)\s+(lightgbm_tpu|jax)", src, re.M)


# -- the kind, end to end ----------------------------------------------------

def test_tiny_rank_cell_is_correct_and_reports_its_layout(bench_copy, capsys):
    from lightgbm_tpu.utils import profiling

    profiling.reset()
    cell = add_rank_cell(bench_copy)
    res, err = bench_copy.run(capsys, cell, seed=2**31 + 11, trace=1)
    assert res["correct"] is True and not over(res), res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    c = res["counters"]
    assert c["total_rounds"] == 3 + c["window_rounds"]
    assert c["rank_queries"] == 80 and c["rank_truncation"] == 30
    assert c["rank_doc_slots"] >= 6000
    assert c["rank_pair_slots"] >= c["rank_pairs_visited"] > 0
    assert len(c["reference_ndcg10"]) == 3
    assert c["reference_ndcg10"][-1] > c["reference_ndcg10"][0] > 0.3
    assert c["rank_grad_probe_ms"] > 0
    # the probe runs after the window: its seconds are its own counter
    assert c["rank_grad_probe_s"] * 1000.0 > 5 * c["rank_grad_probe_ms"]
    # what ranking by the program's stored scores hides: read, not compared
    for name in ("own_rank_flips", "own_grad_rows", "own_grad_gap"):
        assert len(c[name]) == 3 and c[name][0] == 0 and name not in \
            res["checks"]
    assert max(c["own_grad_gap"]) < 1e-2
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert got["rank_pad_ratio"] == c["rank_doc_slots"] / 6000
    assert got["rank_pair_slot_ratio"] == \
        c["rank_pair_slots"] / c["rank_pairs_visited"]
    assert got["rank_grad_ms"] == c["rank_grad_probe_ms"]
    assert 0 < got["rank_pack_s"] < got["binning_s"] + 60
    assert "init_abs" in res["checks"] and "[check] init_abs" in err


def test_two_seeds_of_one_table_read_the_same_ndcg(bench_copy, capsys):
    """``table_seed``: the seed orders the columns; the groups, the labels
    and with them the trees' statistics are the table's."""
    cell = add_rank_cell(bench_copy, "tiny-rank-table",
                         dict(copy.deepcopy(TINY_RANK), table_seed=77))

    def run(seed):
        res, _ = bench_copy.run(capsys, cell, seed=seed)
        assert res["correct"] is True, res["checks"]
        return res

    a, b = run(2**31 + 1), run(2**31 + 2)
    assert a["counters"]["reference_ndcg10"] == pytest.approx(
        b["counters"]["reference_ndcg10"], rel=1e-9)
    assert a["counters"]["rank_blocks"] == b["counters"]["rank_blocks"]
    assert set(a["metrics"]) == {"train_rows_rounds_per_s", "setup_s"}


def test_rank_control_comes_out_not_correct(bench_copy, capsys):
    from benchmark.readings import variant

    cfg, fault = variant(copy.deepcopy(TINY_RANK), "control")
    assert fault is None and cfg["params"]["hist_dtype"] == "bf16"
    cell = add_rank_cell(bench_copy, "tiny-rank-control", cfg)
    res, _ = bench_copy.run(capsys, cell, seed=3)
    assert res["correct"] is False
    assert "leaf_value_worst" in over(res)


@pytest.mark.parametrize("fault,number", [
    ("state_unchanged", "trees_missing"),
    ("half_batch", "leaf_count_off"),
    ("altered_answer", "leaf_value_worst"),
    ("restricted_features", "split_gain_short"),
    ("pointwise", "init_abs"),
    ("pointwise", "leaf_value_worst"),
    ("no_truncation", "leaf_value_worst"),
])
def test_planted_rank_fault_is_not_correct(bench_copy, capsys, fault,
                                           number):
    from benchmark.kinds import train_window, train_window_rank
    from benchmark.manifest import Manifest

    cell = add_rank_cell(bench_copy)
    res, _ = bench_copy.run(capsys, cell, seed=5, fault=fault)
    assert res["correct"] is False
    assert number in over(res), res["checks"]
    # the fault was that run's: the next Cell built in this process has
    # none, and no module of the kinds holds one
    man = Manifest(str(bench_copy.root))
    entry = man.cell(cell)
    nxt = train_window_rank.Cell(man.config(entry), man.traffic(entry), 5,
                                 None)
    assert nxt.fault is None
    assert not hasattr(train_window, "FAULT")
    assert not hasattr(train_window_rank, "FAULT")


def test_a_program_without_the_rank_facts_is_refused(bench_copy, capsys,
                                                     monkeypatch):
    """The parent commit's program packs its queries another way and notes
    no ``train.rank_*``: the run ends before a round is trained."""
    from lightgbm_tpu import ranking
    from lightgbm_tpu.utils import profiling

    profiling.reset()
    real = ranking.LambdaRank.set_group

    def no_facts(self, *args, **kwargs):
        real(self, *args, **kwargs)
        self.facts = {}

    monkeypatch.setattr(ranking.LambdaRank, "set_group", no_facts)
    cell = add_rank_cell(bench_copy)
    with pytest.raises(SystemExit, match="train.rank_queries"):
        bench_copy.run(capsys, cell)
    assert capsys.readouterr().out.strip() == ""


def test_a_failing_probe_fails_the_run(bench_copy, capsys, monkeypatch):
    """``rank_grad_ms`` is a probe of the program's lambda pass: a pass
    that breaks ends the run with its error, not with a metric left out."""
    from lightgbm_tpu.models import gbdt

    def broken(self):
        raise RuntimeError("the lambda pass broke")

    monkeypatch.setattr(gbdt.Booster, "_group_grad_call", broken)
    cell = add_rank_cell(bench_copy)
    with pytest.raises(RuntimeError, match="the lambda pass broke"):
        bench_copy.run(capsys, cell, seed=7)
    assert capsys.readouterr().out.strip() == ""


def test_real_cell_limits_lie_between_their_readings():
    """``order_excess``: PERF.md section 2's readings of this cell, the
    largest sound one and the control's smallest (my chip runs, PR 37)."""
    from benchmark.manifest import Manifest

    man = Manifest()
    limits = man.config(man.cell(CELL))["limits"]
    assert 3 * 0.0042 < limits["order_excess"] < 0.037 / 2
    assert 3 * 0.00061 < limits["leaf_value_worst"] < 0.0353 / 3


# -- the real cell's entries -------------------------------------------------

def test_real_cell_resolves_to_its_three_files(manifest):
    from benchmark.manifest import load_kind

    man = manifest
    cell = man.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "train-window-rank"
    config, traffic = man.config(cell), man.traffic(cell)
    assert (config["rows"], config["features"], config["queries"],
            config["query_docs"]) == (2_270_296, 136, 18_919, "1-1251")
    assert config["reduced"] == ["num_trees"]
    assert not {"hist_dtype", "wave_tail", "wave_width", "hist_impl"} \
        & set(config["params"])
    assert config["params"]["objective"] == "lambdarank"
    assert config["params"]["min_sum_hessian_in_leaf"] == 100.0
    assert isinstance(config["table_seed"], int)
    assert len(config["source"]) <= 200 and "MS LTR" in config["source"]
    assert traffic["kind"] == "train_window_rank"
    assert {k: traffic[k] for k in (
        "rounds_per_call", "checked_rounds", "sample_rows", "split_nodes",
        "order_leaves", "trace_seconds")} == {
            "rounds_per_call": 1, "checked_rounds": 3, "sample_rows": 100000,
            "split_nodes": 16, "order_leaves": 16, "trace_seconds": 10}
    kind = load_kind(traffic["kind"])
    assert {"pointwise", "no_truncation", "fewer_leaves",
            "restricted_features", "greedy_tail"} <= set(
                kind.Cell.PARAM_FAULTS)
    names = {m["name"] for m in man.metrics_of(CELL, "end_to_end")}
    assert names == {"train_rows_rounds_per_s", "setup_s"}


def test_rank_metrics_are_listed_for_the_rank_cell_alone(manifest):
    man = manifest
    listed = {m["name"]: m for m in man.doc["per_layer"]}
    for name in RANK_METRICS:
        assert CELL in listed[name]["workloads"]
        for cell in listed[name]["workloads"]:
            kind = man.traffic(man.cell(cell))["kind"]
            assert kind == "train_window_rank", (name, cell)
        assert listed[name]["layer"] == "objective"
        spec = man.metric_spec(name)
        assert spec["what"] and spec["unit"] == listed[name]["unit"]
        assert not os.path.exists(os.path.join(
            man.root, "benchmark", "metrics", name + ".py"))
    mine = {m["name"] for m in man.metrics_of(CELL, "per_layer")}
    assert set(RANK_METRICS) <= mine
    assert {"hist_wave_roofline", "hist_root_roofline",
            "hist_narrow_roofline", "grower_xla_pct", "train_floor_mfu_pct",
            "device_idle_pct.train", "wave_passes_per_round"} <= mine
    for cell in ("higgs-10m5.train", "epsilon-400k.train"):
        assert not set(RANK_METRICS) & {
            m["name"] for m in man.metrics_of(cell, "per_layer")}
