"""The plain reference of the sparse wide cell: ``gbdt_check`` on a table
that is mostly missing values.

numpy and float64 only; imports nothing of the program but
``gbdt_check``'s tree utilities, and knows nothing of bundles: it reads the
raw rows and the program's answer (``Booster.dump_model()``), whose every
split is one original feature's ``x <= threshold``.  Its departures from
``gbdt_check``, each for the missing values:

* a row whose value is NaN goes RIGHT at every split, as the dump's
  ``missing_type: "NaN"`` with ``default_left: false`` says (``x <=
  threshold`` is false for NaN, so ``gbdt_check.route`` already does it;
  ``dump_missing`` counts the splits whose dump says otherwise, which a
  learned missing direction would make: none may);
* the candidate thresholds (:func:`candidate_edges`) are the ``max_bin -
  1`` inner quantiles of each column's NON-NaN values in a seeded sample,
  plus the column's largest value: the split "a value, or NaN", which on a
  column that is mostly missing is the first split there is;
* short trees: at LightGBM's ``min_sum_hessian_in_leaf`` 100 and 0.58 %
  positives a tree stops long before ``num_leaves`` where no leaf can
  split, so ``leaves_off`` reads nothing here.  :func:`unsplit_leaves`
  takes its place: for every checked tree with fewer leaves than the
  budget, the ``order_leaves`` leaves of the largest hessian are searched
  for a split that leaves ``UNSPLIT_MARGIN`` more than the minimum on both
  sides and gains; a tree that stopped with one is short of leaves.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .gbdt_check import (EDGE_SAMPLE, ROUTE_THREADS, best_gain, check_splits,
                         grad_hess, init_score, logloss, route)

UNSPLIT_MARGIN = 0.05


def candidate_edges(X, max_bin: int, rng) -> list:
    """Per column the distinct values at the ``max_bin - 1`` inner quantiles
    of its non-NaN values in a seeded sample, and its largest value (the
    split that sends every value left and NaN right)."""
    n = X.shape[0]
    take = np.sort(rng.choice(n, size=min(EDGE_SAMPLE, n), replace=False))
    S = np.sort(X[take], axis=0)                      # NaN last
    edges = []
    for f in range(X.shape[1]):
        vals = S[:, f][~np.isnan(S[:, f])]
        if not len(vals):
            edges.append(np.zeros(0, X.dtype))
            continue
        at = (np.arange(1, max_bin) * (len(vals) - 1)) // max_bin
        edges.append(np.unique(np.append(vals[at], vals[-1])))
    return edges


def dump_missing(structure: dict) -> int:
    """Splits of one dumped tree that do not send NaN right."""
    if "left_child" not in structure:
        return 0
    wrong = int(structure.get("missing_type") == "NaN"
                and structure.get("default_left", True))
    return (wrong + dump_missing(structure["left_child"])
            + dump_missing(structure["right_child"]))


def unsplit_leaves(X, tree, leaf, g, h, sums, edges, hyper, leaves: int,
                   pool=None) -> int:
    """How many of the ``leaves`` leaves of the largest hessian of a tree
    short of ``num_leaves`` hold a split that leaves ``(1 +
    UNSPLIT_MARGIN) x min_sum_hessian_in_leaf`` on both sides and gains."""
    is_leaf = tree["feature"] < 0
    if is_leaf.sum() >= int(hyper["num_leaves"]):
        return 0
    lam = float(hyper["lambda_l2"])
    need = float(hyper["min_sum_hessian_in_leaf"]) * (1 + UNSPLIT_MARGIN)
    H = sums[1]
    held = np.flatnonzero(is_leaf)
    held = held[np.argsort(-H[held], kind="stable")][:leaves]
    found = 0
    for i in held:
        if H[i] < 2 * need:
            continue
        rows = np.flatnonzero(leaf == i)
        best = best_gain(X[rows], g[rows], h[rows], edges, lam, need, pool)
        found += int(np.isfinite(best) and best > 0)
    return found


def check_rounds(X, y, trees, scores_after, program_init, hyper,
                 seed: int = 0, split_nodes: int = 0,
                 order_leaves: int = 0) -> dict:
    """``gbdt_check.check_rounds`` with the candidate thresholds and the
    short-tree check above; the same numbers, one group per round, and
    ``unsplit_leaves``."""
    lr, lam = float(hyper["learning_rate"]), float(hyper["lambda_l2"])
    y = np.asarray(y, np.float64)
    n = len(y)
    s0 = init_score(y)
    out = {"init_abs": abs(float(program_init) - s0), "rounds": []}
    score = np.full(n, s0, np.float64)
    search = split_nodes > 0 or order_leaves > 0
    rng = np.random.default_rng([int(seed), 42])
    edges = candidate_edges(X, int(hyper["max_bin"]), rng) if search else None
    pool = ThreadPoolExecutor(ROUTE_THREADS) if search else None
    for tree, prog_score in zip(trees, scores_after):
        g, h = grad_hess(score, y)
        leaf = route(X, tree)
        m = len(tree["feature"])
        G = np.bincount(leaf, weights=g, minlength=m)
        H = np.bincount(leaf, weights=h, minlength=m)
        C = np.bincount(leaf, minlength=m)
        is_leaf = tree["feature"] < 0
        v_ref = -G[is_leaf] / (H[is_leaf] + lam)
        v_prog = tree["value"][is_leaf]
        scale = np.maximum(np.abs(v_ref), np.median(np.abs(v_ref)))
        rel = np.abs(v_prog - v_ref) / scale
        rd = {}
        if search:
            rd = check_splits(X, tree, leaf, g, h, (G, H, C), edges, hyper,
                              rng, split_nodes, order_leaves, pool)
            rd["unsplit_leaves"] = unsplit_leaves(
                X, tree, leaf, g, h, (G, H, C), edges, hyper, order_leaves,
                pool)
        score = score + lr * tree["value"][leaf]
        rd.update({
            "leaves": int(is_leaf.sum()),
            "hessian_sum": float(H[is_leaf].sum()),
            "loss": logloss(score, y),
            "leaf_value_worst": float(rel.max()),
            "leaf_value_rms": float(np.sqrt(np.mean(rel ** 2))),
            "leaf_count_off": int(np.abs(
                tree["count"][is_leaf] - C[is_leaf]).max()),
            "root_count_off": int(abs(int(tree["count"][0]) - n)),
            "score_abs": float(np.abs(
                np.asarray(prog_score, np.float64) - score).max()),
        })
        out["rounds"].append(rd)
    if pool:
        pool.shutdown()
    return out
