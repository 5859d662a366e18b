"""The plain reference of the ranking cells: LightGBM's lambdarank
gradients query by query, and the exact statistics of the trees grown on
them.

numpy and float64 only; imports nothing of ``lightgbm_tpu`` (the tree
utilities are ``gbdt_check``'s: the walk over raw values, the reference's
own candidate thresholds and split search, the replay of leaf-wise order).
It is handed what ``gbdt_check`` is handed, and the query sizes: rows are
group-contiguous, query ``q`` is rows ``start_q .. start_q + n_q - 1``.

**The gradients** are ``LambdarankNDCG::GetGradientsForOneQuery``
(LightGBM ``src/objective/rank_objective.hpp``), as recalled with no
network, one query at a time (``lambdas_one_query``):

    gain(l)    = 2^l - 1                          (label_gain's default)
    disc(r)    = 1 / log2(2 + r)                  r = 0-based rank by score
    ranks:       descending score, ties in row order (std::stable_sort)
    1/maxDCG   = 1 / sum_{r < T} gain(sorted labels)[r] * disc(r),
                 T = lambdarank_truncation_level; 0 where no document of
                 the query is relevant (its gradients are then all 0)
    pairs:       rank i over 0 .. min(T, n - 1) - 1, rank j over i+1 .. n-1,
                 labels different; hi = the one with the larger label
    dNDCG      = (gain_hi - gain_lo) * |disc_hi - disc_lo| / maxDCG
                 / (0.01 + |s_hi - s_lo|)   under lambdarank_norm, where
                                            the query's best and worst
                                            scores differ
    p          = 1 / (1 + exp(sigmoid * (s_hi - s_lo)))
    lambda     = sigmoid * p * dNDCG:      grad_hi -= lambda, grad_lo += lambda
    hessian    = sigmoid^2 * p * (1 - p) * dNDCG     onto both
    norm:        under lambdarank_norm, with L = 2 * (sum of lambda over the
                 query's pairs) > 0, every gradient and hessian of the query
                 times log2(1 + L) / L

Departures from ``rank_objective.hpp``, each carried by program and
reference alike: ``p`` is the exact logistic, where LightGBM reads a
table of 1,048,576 entries over [-50/sigmoid/2, 50/sigmoid/2]; no
document carries the sentinel score ``kMinScore``; no position-bias
terms (LightGBM 4's ``lambdarank_position_bias_regularization``); the
initial score is 0 (LightGBM boosts ranking from zero).  The gradient of
the loss is returned (``grad``), so a leaf's value is ``-G / (H +
lambda_l2)`` as in ``gbdt_check``.

**Near-ties.**  A rank is a discontinuous function of the scores, and
after round 1 the documents of one leaf tie exactly.  The gradients of
round ``k`` are therefore taken from the program's STORED float32 scores
after round ``k - 1`` (``scores_after[k - 2]``; zeros before round 1),
which ``score_abs`` has just held to the reference's own float64 walk
within its limit: the reference's ranks are then the ranks of the very
numbers the program sorted, where its own walk rounded to float32 could
differ from the program's chain of float32 additions in the last bit and
flip a pair at the top of a query.  The walk itself, every leaf's
statistics and the split search stay the reference's own.  What the
shortcut hides is read, not compared, round by round (``own_walk_gap``):
``own_rank_flips``, the rows whose rank in their query differs when the
reference ranks by its OWN walk rounded to float32; ``own_grad_rows``, the
rows whose gradient then differs by more than 1e-4 of the round's largest
(the scores' last bits alone move a gradient by less: a pair's dNDCG
divides by ``0.01 + |ds|``); ``own_grad_gap``, the largest such difference
as a share of the round's largest gradient.

``ndcg10`` (NDCG@10 of the training scores after each checked round, the
mean over queries, a query without a relevant document counting 1 as in
LightGBM's metric) is read, not compared: the real folds are not here.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .gbdt_check import ROUTE_THREADS, candidate_edges, check_splits, route


def gain_of_label(label: np.ndarray) -> np.ndarray:
    return 2.0 ** np.asarray(label, np.float64) - 1.0


def lambdas_one_query(score: np.ndarray, label: np.ndarray, hyper: dict):
    """``(grad, hess)`` of one query's documents (float64, row order)."""
    score = np.asarray(score, np.float64)
    n = len(score)
    grad, hess = np.zeros(n), np.zeros(n)
    if n < 2:
        return grad, hess
    sigma = float(hyper.get("sigmoid", 1.0))
    trunc = int(hyper.get("lambdarank_truncation_level", 30))
    norm = bool(hyper.get("lambdarank_norm", True))
    gain = gain_of_label(label)
    disc = 1.0 / np.log2(2.0 + np.arange(n))
    top = np.sort(gain)[::-1][:trunc]
    max_dcg = float(np.sum(top * disc[:len(top)]))
    if max_dcg <= 0.0:
        return grad, hess
    order = np.argsort(-score, kind="stable")       # ties in row order
    s, gn = score[order], gain[order]
    differ = s[0] != s[-1]
    m = min(trunc, n - 1)
    i = np.arange(m)[:, None]
    j = np.arange(n)[None, :]
    pair = (j > i) & (gn[:m, None] != gn[None, :])
    i_hi = gn[:m, None] > gn[None, :]       # rank i holds the larger label
    # s_hi - s_lo and gain_hi - gain_lo, pair by pair
    d_score = np.where(i_hi, 1.0, -1.0) * (s[:m, None] - s[None, :])
    delta = (np.abs(gn[:m, None] - gn[None, :])
             * np.abs(disc[:m, None] - disc[None, :]) / max_dcg)
    if norm and differ:
        delta = delta / (0.01 + np.abs(d_score))
    with np.errstate(over="ignore"):
        p = 1.0 / (1.0 + np.exp(sigma * d_score))
    lam = np.where(pair, sigma * p * delta, 0.0)
    hes = np.where(pair, sigma * sigma * p * (1.0 - p) * delta, 0.0)
    push = np.where(i_hi, lam, -lam)        # onto rank j, off rank i
    g_rank = push.sum(axis=0)
    g_rank[:m] -= push.sum(axis=1)
    h_rank = hes.sum(axis=0)
    h_rank[:m] += hes.sum(axis=1)
    total = 2.0 * lam.sum()
    if norm and total > 0.0:
        scale = np.log2(1.0 + total) / total
        g_rank, h_rank = g_rank * scale, h_rank * scale
    grad[order], hess[order] = g_rank, h_rank
    return grad, hess


def lambdas(score: np.ndarray, label: np.ndarray, sizes: np.ndarray,
            hyper: dict):
    """``(grad, hess)`` over every row, a plain loop over the queries."""
    n = int(np.sum(sizes))
    grad, hess = np.zeros(n), np.zeros(n)
    at = 0
    for size in np.asarray(sizes, np.int64):
        rows = slice(at, at + int(size))
        grad[rows], hess[rows] = lambdas_one_query(
            score[rows], label[rows], hyper)
        at += int(size)
    return grad, hess


def ranks_in_query(score: np.ndarray, sizes: np.ndarray) -> np.ndarray:
    """Every row's 0-based rank in its query: descending score, ties in
    row order."""
    sizes = np.asarray(sizes, np.int64)
    n = int(sizes.sum())
    query = np.repeat(np.arange(len(sizes)), sizes)
    start = np.repeat(np.cumsum(sizes) - sizes, sizes)
    by = np.lexsort((np.arange(n), -np.asarray(score[:n], np.float64), query))
    rank = np.empty(n, np.int64)
    rank[by] = np.arange(n) - start[by]
    return rank


def ndcg_at(score: np.ndarray, label: np.ndarray, sizes: np.ndarray,
            k: int = 10) -> float:
    """Mean NDCG@k over the queries (ties in row order; a query with no
    relevant document counts 1)."""
    sizes = np.asarray(sizes, np.int64)
    n = int(sizes.sum())
    query = np.repeat(np.arange(len(sizes)), sizes)
    gain = gain_of_label(label[:n])

    def dcg(key):
        rank = ranks_in_query(key, sizes)
        top = rank < k
        return np.bincount(query[top],
                           weights=gain[top] / np.log2(2.0 + rank[top]),
                           minlength=len(sizes))

    got, ideal = dcg(score), dcg(gain)
    return float(np.mean(np.where(ideal > 0,
                                  got / np.maximum(ideal, 1e-300), 1.0)))


def own_walk_gap(own, ranked, grad, label, sizes, hyper) -> dict:
    """What ranking by the program's stored scores (``ranked``) hides:
    the same round's gradients from the reference's own walk rounded to
    float32 (``own``), set against ``grad``.  Read, not compared."""
    if np.array_equal(own, ranked):
        return {"own_rank_flips": 0, "own_grad_rows": 0, "own_grad_gap": 0.0}
    flips = int(np.sum(ranks_in_query(own, sizes)
                       != ranks_in_query(ranked, sizes)))
    g_own, _ = lambdas(own, label, sizes, hyper)
    gap = np.abs(g_own - grad) / max(float(np.abs(grad).max()), 1e-300)
    return {"own_rank_flips": flips,
            "own_grad_rows": int(np.sum(gap > 1e-4)),
            "own_grad_gap": float(gap.max())}


def check_rounds(X, y, sizes, trees, scores_after, program_init, hyper,
                 seed: int = 0, split_nodes: int = 0,
                 order_leaves: int = 0) -> dict:
    """Follow the program's first ``len(trees)`` rounds, as
    ``gbdt_check.check_rounds`` does, on lambdarank's gradients.

    ``sizes``: the query sizes, in row order; ``hyper``: the constants the
    configuration states (``learning_rate``, ``lambda_l2``, ``sigmoid``,
    ``lambdarank_truncation_level``, ``lambdarank_norm``; with
    ``split_nodes`` or ``order_leaves`` also ``max_bin`` and
    ``min_sum_hessian_in_leaf``).  Returns plain
    numbers, one group per round."""
    lr, lam = float(hyper["learning_rate"]), float(hyper["lambda_l2"])
    y = np.asarray(y, np.float64)
    n = len(y)
    out = {"init_abs": abs(float(program_init)), "rounds": []}
    score = np.zeros(n, np.float64)             # the reference's own walk
    ranked = np.zeros(n, np.float32)            # what the program sorted
    search = split_nodes > 0 or order_leaves > 0
    rng = np.random.default_rng([int(seed), 37])
    edges = candidate_edges(X, int(hyper["max_bin"]), rng) if search else None
    pool = ThreadPoolExecutor(ROUTE_THREADS) if search else None
    for tree, prog_score in zip(trees, scores_after):
        g, h = lambdas(ranked, y, sizes, hyper)
        own = own_walk_gap(score.astype(np.float32), ranked, g, y, sizes,
                           hyper)
        leaf = route(X, tree)
        m = len(tree["feature"])
        G = np.bincount(leaf, weights=g, minlength=m)
        H = np.bincount(leaf, weights=h, minlength=m)
        C = np.bincount(leaf, minlength=m)
        is_leaf = tree["feature"] < 0
        with np.errstate(divide="ignore", invalid="ignore"):
            v_ref = -G[is_leaf] / (H[is_leaf] + lam)
        v_prog = tree["value"][is_leaf]
        scale = np.maximum(np.abs(v_ref), np.median(np.abs(v_ref)))
        rel = np.abs(v_prog - v_ref) / scale
        rd = {}
        if search:
            rd = check_splits(X, tree, leaf, g, h, (G, H, C), edges, hyper,
                              rng, split_nodes, order_leaves, pool)
        score = score + lr * tree["value"][leaf]
        ranked = np.asarray(prog_score, np.float32)
        rd.update(own)
        rd.update({
            "leaves": int(is_leaf.sum()),
            "ndcg10": ndcg_at(score, y, sizes, 10),
            "hessian_sum": float(H[is_leaf].sum()),
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
