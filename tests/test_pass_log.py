"""The wave grower's pass log (PR 39): per wave pass, its role, its splits
and the rows it streamed, split and histogrammed (``models.tree._PASS``),
carried by the round program to ``profiling.defer("train.passes")``.

(a) invariants on every logged pass, at every tail, partition-fused and
not; (b) an independent recount from the grown tree; (c) the log changes
no tree: digests of the parent commit's trees; (d) ``update_many`` keeps
the log unread until ``snapshot()``, in a bounded ring that ``reset()``
clears.
"""

import hashlib
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.models import tree as tree_mod
from lightgbm_tpu.models.spec import WaveSchedule, _exact_overgrow_target
from lightgbm_tpu.ops.split import SplitContext
from lightgbm_tpu.utils import profiling

B, WIDTH, NARROW, LEAVES = 32, 8, 4, 31
CASES = [(tail, fused) for tail in ("greedy", "half", "exact")
         for fused in (True, False)]


def _ctx():
    z = jnp.float32
    return SplitContext(lambda_l1=z(0.0), lambda_l2=z(1.0),
                        min_data_in_leaf=z(2.0), min_sum_hessian=z(1e-3),
                        min_gain_to_split=z(0.0))


def _table(n=3000, f=6, seed=3, bagged=False):
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, B, (n, f)).astype(np.int32)
    y = (bins[:, 0] + 0.5 * bins[:, 1] + rng.normal(0, 6, n) > 24)
    g = (0.5 - y).astype(np.float32)
    h = np.full(n, 0.25, np.float32)
    bag = (rng.random(n) < 0.7 if bagged else np.ones(n, bool))
    bag = bag.astype(np.float32)
    return jnp.asarray(bins), jnp.asarray(np.stack([g * bag, h * bag, bag],
                                                   -1))


def _wave(tail, fused):
    cap = (_exact_overgrow_target(LEAVES, WIDTH, 2.0) if tail == "exact"
           else None)
    return WaveSchedule(WIDTH, tail, cap, narrow_width=NARROW if fused else 0)


def _grow(tail, fused, grow=None, bagged=False):
    """One tree of the case, through ``grow`` (``grow_tree_logged``: the
    triple; ``grow_tree``, which the parent commit has too: the pair)."""
    bins, stats = _table(bagged=bagged)
    grow = grow or tree_mod.grow_tree_logged
    return jax.jit(lambda: grow(
        bins, stats, jnp.ones(bins.shape[1], jnp.float32), _ctx(), LEAVES,
        B, -1, wave=_wave(tail, fused), hist_impl="pallas",
        hist_dtype="f32", fuse_partition=fused))(), stats


def _ran(passes):
    """The columns of the passes that ran, as ``{column: values}``."""
    P = np.asarray(passes)
    ran = P[tree_mod._PASS.SPLITS] > 0
    n = int(ran.sum())
    assert ran[:n].all() and not P[:, n:].any(), "ran passes form a prefix"
    return {k: P[getattr(tree_mod._PASS, k), :n] for k in
            ("ROLE", "SPLITS", "STREAMED", "PARENTS", "DIRECT")}


@pytest.mark.parametrize("tail, fused", CASES)
def test_every_logged_pass_holds_its_invariants(tail, fused):
    (tree, _, passes), stats = _grow(tail, fused, bagged=True)
    assert passes.shape == (tree_mod._PASS.NC,
                            tree_mod.wave_extent(_wave(tail, fused),
                                                 LEAVES)[0] - 1)
    log = _ran(passes)
    assert len(log["SPLITS"]) >= 2
    assert (log["SPLITS"] >= 1).all()
    assert (log["DIRECT"] <= log["PARENTS"] / 2).all()
    assert (log["PARENTS"] <= log["STREAMED"]).all()
    assert log["STREAMED"][0] >= stats.shape[0]
    # the root's split: every in-bag row
    assert log["PARENTS"][0] == float(np.asarray(stats)[:, 2].sum())
    leaves_before = 1 + np.concatenate([[0], np.cumsum(log["SPLITS"])[:-1]])
    if fused:
        assert (log["ROLE"][leaves_before <= NARROW] == 0).all()
        assert (log["ROLE"][leaves_before > NARROW] == 1).any()
    else:
        assert (log["ROLE"] == 1).all()


@pytest.mark.parametrize("tail, fused", [c for c in CASES if c[0] != "exact"])
def test_the_log_recounts_from_the_tree(tail, fused):
    """Unpruned tails, no bagging: the log's sums are the tree's own."""
    (tree, _, passes), _ = _grow(tail, fused)
    log = _ran(passes)
    internal = ~np.asarray(tree.is_leaf) & (np.asarray(tree.left) >= 0)
    count = np.asarray(tree.count)
    left, right = np.asarray(tree.left)[internal], \
        np.asarray(tree.right)[internal]
    assert log["SPLITS"].sum() == int(tree.num_leaves) - 1
    assert log["PARENTS"].sum() == count[internal].sum()
    assert log["DIRECT"].sum() == np.minimum(count[left],
                                             count[right]).sum()


def test_the_log_recounts_from_the_dump():
    """The same through a booster's round program and ``dump_model()``."""
    X, y = _data()
    profiling.reset()
    b = lgb.Booster(dict(_PARAMS, wave_tail="greedy"),
                    lgb.Dataset(X, label=y))
    b.update_many(2)
    rounds = np.concatenate(profiling.snapshot()["arrays"]["train.passes"])
    assert rounds.shape[0] == 2

    def walk(node, acc):
        if "leaf_index" in node:
            return node["leaf_count"]
        kids = [walk(node[k], acc) for k in ("left_child", "right_child")]
        acc.append((node["internal_count"], min(kids)))
        return node["internal_count"]

    for info, passes in zip(b.dump_model()["tree_info"], rounds):
        log, acc = _ran(passes), []
        walk(info["tree_structure"], acc)
        assert log["SPLITS"].sum() == info["num_leaves"] - 1
        assert log["PARENTS"].sum() == sum(c for c, _ in acc)
        assert log["DIRECT"].sum() == sum(m for _, m in acc)


def test_the_exact_tail_logs_the_overgrown_table(monkeypatch):
    grown = []
    real = tree_mod._exact_prune

    def spy(P, *args, **kwargs):
        grown.append(jnp.sum(P[:, tree_mod._PK.IS_LEAF] > 0.5))
        return real(P, *args, **kwargs)

    monkeypatch.setattr(tree_mod, "_exact_prune", spy)
    for fused in (True, False):
        grown.clear()
        bins, stats = _table()
        tree, _, passes = tree_mod.grow_tree_logged(
            bins, stats, jnp.ones(bins.shape[1], jnp.float32), _ctx(),
            LEAVES, B, -1, wave=_wave("exact", fused), hist_impl="pallas",
            hist_dtype="f32", fuse_partition=fused)
        log = _ran(passes)
        assert log["SPLITS"].sum() == int(grown[0]) - 1 > LEAVES - 1
        assert int(tree.num_leaves) == LEAVES


# ---- (c) the log changes no tree: digests the parent commit gives ---------

def tree_digest(tail, fused):
    """sha256 of the tree's arrays and row_leaf, grown by ``grow_tree``."""
    (tree, row_leaf), _ = _grow(tail, fused, grow=tree_mod.grow_tree)
    h = hashlib.sha256()
    for a in (*tree[:9], row_leaf):
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()[:16]


_PARAMS = {"objective": "binary", "num_leaves": 15, "learning_rate": 0.1,
           "min_data_in_leaf": 5, "verbosity": -1, "wave_width": 8,
           "grow_policy": "frontier"}


def _data(rows=4000, features=6, seed=11):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, features)).astype(np.float32)
    return X, (X[:, 0] + 0.5 * X[:, 1] + rng.normal(0, 0.5, rows)
               > 0).astype(np.float32)


def as_the_parent_dumped(node):
    """A dump as the commits before the missing-value fields wrote it:
    every split ``default_left: true`` and no ``missing_type``.  The
    trees and values are compared; the two fields are
    ``tests/test_efb_members.py``'s."""
    if isinstance(node, dict):
        out = {k: as_the_parent_dumped(v) for k, v in node.items()
               if not ("split_index" in node and k == "missing_type")}
        if "split_index" in node:
            out["default_left"] = True
        return out
    if isinstance(node, list):
        return [as_the_parent_dumped(v) for v in node]
    return node


def model_digest(tail):
    """sha256 of ``dump_model()`` and the training scores after two rounds
    of ``update_many`` (the round program the log rides in)."""
    X, y = _data()
    b = lgb.Booster(dict(_PARAMS, wave_tail=tail), lgb.Dataset(X, label=y))
    b.update_many(2)
    h = hashlib.sha256(json.dumps(as_the_parent_dumped(b.dump_model()),
                                  sort_keys=True).encode())
    h.update(np.asarray(b._pred_train).tobytes())
    return h.hexdigest()[:16]


# computed on the parent commit (749c9b0), which has no pass log
PARENT_TREES = {
    ("greedy", True): "b561b5349a7992aa",
    ("greedy", False): "b561b5349a7992aa",
    ("half", True): "e3e504f35bb9b38b", ("half", False): "e3e504f35bb9b38b",
    ("exact", True): "706891ee8b750de5", ("exact", False): "2d0bcae0de967f1f",
}
PARENT_MODELS = {"greedy": "2291bd5940d62367", "half": "cbe71631108622a9",
                 "exact": "c353ee32bb8a2410"}


@pytest.mark.parametrize("tail, fused", CASES)
def test_trees_are_the_parents(tail, fused):
    assert tree_digest(tail, fused) == PARENT_TREES[tail, fused]


@pytest.mark.parametrize("tail", sorted(PARENT_MODELS))
def test_models_are_the_parents(tail):
    assert model_digest(tail) == PARENT_MODELS[tail]


# ---- (d) the recorder keeps it unread -------------------------------------

def test_update_many_defers_the_log_unread():
    X, y = _data()
    profiling.reset()
    b = lgb.Booster(dict(_PARAMS), lgb.Dataset(X, label=y))
    b.update_many(3)
    b.update_many(2)
    kept = [a for _, a in profiling._process._deferred["train.passes"]]
    assert [a.shape[0] for a in kept] == [3, 2]
    assert all(isinstance(a, jax.Array) for a in kept)
    arrays = profiling.snapshot()["arrays"]["train.passes"]
    assert all(isinstance(a, np.ndarray) for a in arrays)
    facts = profiling.snapshot()["facts"]
    assert facts["train.wave_tail"] == "exact"
    assert arrays[0].shape[1:] == (tree_mod._PASS.NC,
                                   facts["train.overgrow_leaves"] - 1)
    profiling.reset()
    assert profiling.snapshot()["arrays"] == {}


def test_the_ring_is_bounded_by_rounds():
    rec = profiling.Recorder()
    for i in range(10):
        rec.defer("x", jnp.full((50, 2), i))
    held = rec.snapshot()["arrays"]["x"]
    # the newest arrays that hold DEFER_ROWS rounds, and no older one
    assert [int(a[0, 0]) for a in held] == [7, 8, 9]
    assert sum(len(a) for a in held) - len(held[0]) < profiling.DEFER_ROWS
    rec.defer("x", jnp.zeros((500, 2)))   # one segment longer than that
    assert [len(a) for a in rec.snapshot()["arrays"]["x"]] == [500]
    rec.defer("y", jnp.float32(1.0))      # a scalar is one row
    assert rec.snapshot()["arrays"]["y"] == [1.0]
    rec.reset()
    assert rec.snapshot()["arrays"] == {}
