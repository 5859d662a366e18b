"""The plain reference of the training cells on hand-made trees."""

import numpy as np
import pytest

from bench_helpers import grow_plain
from benchmark import datagen
from benchmark.reference import gbdt_check as ref

# x0 <= 0.5 ? (x1 <= -1.0 ? A : B) : C
DUMP = {
    "split_index": 0, "split_feature": 0, "threshold": 0.5,
    "decision_type": "<=", "internal_count": 6,
    "left_child": {
        "split_index": 1, "split_feature": 1, "threshold": -1.0,
        "decision_type": "<=", "internal_count": 4,
        "left_child": {"leaf_index": 3, "leaf_value": 0.0, "leaf_count": 1},
        "right_child": {"leaf_index": 4, "leaf_value": 0.0, "leaf_count": 3}},
    "right_child": {"leaf_index": 2, "leaf_value": 0.0, "leaf_count": 2},
}
X = np.array([[0.5, -1.0], [0.4, -0.9], [0.0, 0.0], [0.5, 5.0],
              [0.6, -3.0], [9.0, 9.0]], np.float32)
Y = np.array([1, 0, 1, 1, 0, 1], np.float32)


def exact_tree():
    """DUMP with the leaf values the definition gives at round 1."""
    t = ref.flatten_tree(DUMP)
    s0 = ref.init_score(Y)
    g, h = ref.grad_hess(np.full(6, s0), Y.astype(np.float64))
    leaf = ref.route(X, t)
    for node in np.unique(leaf):
        rows = leaf == node
        t["value"][node] = -g[rows].sum() / h[rows].sum()
    return t, leaf


def test_flatten_and_route():
    t = ref.flatten_tree(DUMP)
    assert t["feature"].tolist() == [0, 1, -1, -1, -1]
    assert t["left"].tolist() == [1, 2, -1, -1, -1]
    assert t["right"].tolist() == [4, 3, -1, -1, -1]
    assert t["count"].tolist() == [6, 4, 1, 3, 2]
    # x <= threshold goes left, compared in float64 on the raw values
    assert ref.route(X, t).tolist() == [2, 3, 3, 3, 4, 4]
    with pytest.raises(ValueError):
        ref.flatten_tree(dict(DUMP, decision_type="=="))


def test_route_in_blocks_and_threads(monkeypatch):
    rng = np.random.default_rng(0)
    Xb = rng.standard_normal((5000, 2)).astype(np.float32)
    t = ref.flatten_tree(DUMP)
    whole = ref.route(Xb, t, threads=1)
    monkeypatch.setattr(ref, "ROUTE_BLOCK", 512)
    assert np.array_equal(ref.route(Xb, t, threads=4), whole)
    want = np.where(Xb[:, 0] > 0.5, 4, np.where(Xb[:, 1] <= -1.0, 2, 3))
    assert np.array_equal(whole, want)


def test_binary_logloss_pieces():
    assert ref.init_score(Y) == pytest.approx(np.log((4 / 6) / (2 / 6)))
    g, h = ref.grad_hess(np.zeros(2), np.array([1.0, 0.0]))
    assert g.tolist() == [-0.5, 0.5] and h.tolist() == [0.25, 0.25]
    assert ref.logloss(np.zeros(4), np.array([1.0, 0, 1, 0])) == \
        pytest.approx(np.log(2))


def test_exact_answer_reads_zero():
    t, leaf = exact_tree()
    lr = 0.1
    s1 = ref.init_score(Y) + lr * t["value"][leaf]
    r = ref.check_rounds(X, Y, [t], [s1.astype(np.float32)],
                         ref.init_score(Y),
                         {"learning_rate": lr, "lambda_l2": 0.0})
    rd = r["rounds"][0]
    assert r["init_abs"] == 0.0 and rd["leaves"] == 3
    assert rd["leaf_value_worst"] < 1e-12 and rd["leaf_value_rms"] < 1e-12
    assert rd["leaf_count_off"] == 0 and rd["root_count_off"] == 0
    assert rd["score_abs"] < 1e-6
    assert rd["loss"] < ref.logloss(np.full(6, ref.init_score(Y)),
                                    Y.astype(np.float64))
    assert ref.check_sample(X[[0, 5]], [t], ref.init_score(Y), lr,
                            s1[[0, 5]]) < 1e-12


def test_each_fault_shows_in_its_number():
    t, leaf = exact_tree()
    lr, hyper = 0.1, {"learning_rate": 0.1, "lambda_l2": 0.0}
    s0 = ref.init_score(Y)
    s1 = s0 + lr * t["value"][leaf]

    wrong = {k: v.copy() for k, v in t.items()}
    wrong["value"][4] *= 1.5                          # an altered leaf
    rd = ref.check_rounds(X, Y, [wrong], [s1], s0, hyper)["rounds"][0]
    assert rd["leaf_value_worst"] == pytest.approx(
        0.5 * abs(t["value"][4]) / max(abs(t["value"][4]),
                                       np.median(np.abs(t["value"][2:]))))
    assert rd["score_abs"] > 1e-3                     # scores kept the old leaf

    wrong = {k: v.copy() for k, v in t.items()}
    wrong["count"][3] = 2                             # a row left out
    rd = ref.check_rounds(X, Y, [wrong], [s1], s0, hyper)["rounds"][0]
    assert rd["leaf_count_off"] == 1

    # a state that did not move
    rd = ref.check_rounds(X, Y, [t], [np.full(6, s0)], s0, hyper)["rounds"][0]
    assert rd["score_abs"] == pytest.approx(np.abs(lr * t["value"][leaf]).max())
    assert ref.check_sample(X, [t], s0, lr, np.full(6, s0)) > 1e-3

    # lambda_l2 is part of the definition
    rd = ref.check_rounds(X, Y, [t], [s1], s0,
                          {"learning_rate": lr, "lambda_l2": 1.0})["rounds"][0]
    assert rd["leaf_value_worst"] > 0.1


# -- which splits were chosen, and in which order ---------------------------

def test_candidate_edges_by_hand():
    Xq = np.arange(1000, dtype=np.float32).reshape(500, 2)   # 0,2,.. / 1,3,..
    edges = ref.candidate_edges(Xq, 5, np.random.default_rng(0))
    at = (np.arange(1, 5) * 499) // 5                        # 99 199 299 399
    assert edges[0].tolist() == (2.0 * at).tolist()
    assert edges[1].tolist() == (2.0 * at + 1).tolist()
    flat = ref.candidate_edges(np.ones((50, 1), np.float32), 5,
                               np.random.default_rng(0))
    assert flat[0].tolist() == [1.0]                         # distinct values


def test_best_gain_by_hand():
    Xn = np.array([[0.0], [1.0], [2.0], [3.0]], np.float32)
    g = np.array([-1.0, -1.0, 1.0, 3.0])
    h = np.ones(4)
    edges = [np.array([0.0, 1.0, 2.0])]
    by_hand = [ref.gain_of(g[:k].sum(), float(k), g.sum(), 4.0, 0.0)
               for k in (1, 2, 3)]
    assert by_hand == pytest.approx([3.0, 9.0, 25 / 3])
    assert ref.best_gain(Xn, g, h, edges, 0.0, 0.0) == pytest.approx(9.0)
    # min_sum_hessian_in_leaf rules the 1|3 and 3|1 splits out, then all
    assert ref.best_gain(Xn, g, h, edges, 0.0, 2.0) == pytest.approx(9.0)
    assert ref.best_gain(Xn, g, h, edges, 0.0, 2.5) == -np.inf
    # lambda_l2 is part of the gain
    assert ref.best_gain(Xn, g, h, edges, 1.0, 0.0) == pytest.approx(
        4 / 3 + 16 / 3 - 4 / 5)


def test_node_sums_and_subtree_end():
    t = ref.flatten_tree(DUMP)                 # 0:(1:(2,3),4)
    assert ref.subtree_end(t).tolist() == [5, 4, 3, 4, 5]
    assert ref.node_sums(t, np.array([0, 0, 1.0, 10.0, 100.0])).tolist() == \
        [111.0, 11.0, 1.0, 10.0, 100.0]


def test_best_first_bounds_by_hand():
    # 0:(1:(2,3:(4,5)),6:(7,8)) with gains 0:10, 1:8, 3:2, 6:5
    leaf = {"leaf_value": 0.0, "leaf_count": 1}
    inner = lambda a, b: {"split_feature": 0, "threshold": 0.0,   # noqa: E731
                          "decision_type": "<=", "internal_count": 2,
                          "left_child": a, "right_child": b}
    t = ref.flatten_tree(inner(inner(leaf, inner(leaf, leaf)),
                               inner(leaf, leaf)))
    assert t["feature"].tolist() == [0, 0, -1, 0, -1, -1, 0, -1, -1]
    gain = np.array([10.0, 8, np.nan, 2, np.nan, np.nan, 5, np.nan, np.nan])
    bound = ref.best_first_bounds(t, gain)
    # replay: 0 (10), 1 (8), 6 (5), 3 (2).  Leaf 2 came with split 1: after
    # it 5 and 2 were taken, so it may hold at most 2; leaves 7, 8 came with
    # split 6: after it only 2; leaves 4, 5 came last: nothing bounds them
    assert bound[[2, 7, 8]].tolist() == [2.0, 2.0, 2.0]
    assert np.isinf(bound[[4, 5]]).all() and np.isnan(bound[[0, 1, 3, 6]]).all()


HYPER = {"learning_rate": 0.1, "lambda_l2": 0.0, "max_bin": 63,
         "min_sum_hessian_in_leaf": 5.0}


def _grown(seed=5, rows=20_000, leaves=31, **grow):
    """A plain leaf-wise tree on the reference's own candidates, and what
    the reference reads of it."""
    Xg, yg = datagen.higgs_like(rows, 8, seed)
    y64 = yg.astype(np.float64)
    s0 = ref.init_score(y64)
    g, h = ref.grad_hess(np.full(rows, s0), y64)
    edges = ref.candidate_edges(Xg, HYPER["max_bin"],
                                np.random.default_rng([seed, 25]))
    t = ref.flatten_tree(grow_plain(
        Xg, g, h, edges, leaves, min_hess=HYPER["min_sum_hessian_in_leaf"],
        **grow))
    s1 = s0 + 0.1 * t["value"][ref.route(Xg, t)]
    rd = ref.check_rounds(Xg, yg, [t], [s1], s0, HYPER, seed=seed,
                          split_nodes=12, order_leaves=8)["rounds"][0]
    return rd


def test_plain_leafwise_tree_reads_nought():
    rd = _grown()
    assert rd["leaves"] == 31 and rd["nodes_checked"] == 13
    assert rd["leaves_checked"] == 8
    assert abs(rd["split_gain_short"]) < 1e-9      # it chose the best splits
    assert rd["order_excess"] <= 1e-9              # and the best leaf first
    assert rd["leaf_value_worst"] < 1e-12 and rd["leaf_count_off"] == 0


@pytest.mark.parametrize("grow,number,least", [
    ({"features": [1, 3, 4, 5, 6, 7]}, "split_gain_short", 0.3),
    ({"order": "oldest"}, "order_excess", 0.3),
])
def test_a_worse_grower_shows(grow, number, least):
    """A scan that leaves features out, and growth that is not leaf-wise,
    each keep exact leaf statistics and show in a number of their own."""
    rd = _grown(**grow)
    assert rd[number] > least
    assert rd["leaf_value_worst"] < 1e-12 and rd["leaf_count_off"] == 0


@pytest.mark.filterwarnings("ignore:.*encountered in:RuntimeWarning")
def test_a_split_that_splits_nothing_reads_one():
    """A root whose threshold sends every row left has gained nothing."""
    rng = np.random.default_rng(0)
    Xs = rng.standard_normal((400, 2)).astype(np.float32)
    ys = (Xs[:, 0] > 0).astype(np.float32)
    t = ref.flatten_tree({
        "split_feature": 0, "threshold": 1e9, "decision_type": "<=",
        "internal_count": 400,
        "left_child": {"leaf_value": 0.0, "leaf_count": 400},
        "right_child": {"leaf_value": 0.0, "leaf_count": 0}})
    rd = ref.check_rounds(Xs, ys, [t], [np.zeros(400)], 0.0,
                          dict(HYPER, min_sum_hessian_in_leaf=0.0),
                          split_nodes=4, order_leaves=4)["rounds"][0]
    assert rd["split_gain_short"] == 1.0 and rd["leaves"] == 2
