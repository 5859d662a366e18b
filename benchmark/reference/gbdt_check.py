"""The plain reference of the training cells: exact statistics of a tree.

numpy and float64 only; imports nothing of ``lightgbm_tpu``.  It is handed
the raw rows and labels the benchmark made from the seed, the
configuration's stated hyper-parameters, and the program's ANSWER as a
user receives it (``Booster.dump_model()``: trees with raw-value
thresholds, leaf values and leaf counts) with the training scores the
program kept after each checked round.  It judges the answer the way a
served token is judged against the reference's logits: rows are routed by
``x <= threshold`` on the raw values (no bin codes, no bin edges), and
every leaf's value, count and every row's score are recomputed from the
rows that really reach it.

    leaf value  v = -G / (H + lambda_l2),  G = sum g, H = sum h over the leaf
    g = sigmoid(s) - y,  h = sigmoid(s) * (1 - sigmoid(s))   (binary logloss)
    score       s_k = s_{k-1} + learning_rate * v_program[leaf_k(x)]

so a histogram summed in a lower precision, rows left out of a batch, a
partition that sends rows to another leaf than the thresholds say, or a
state that did not move, each shows in a number of its own.

WHICH splits were chosen is held against the reference's own search, not
against the program's tables.  The reference makes its own candidate
thresholds (per feature the ``max_bin - 1`` inner quantiles of a seeded
sample of the raw rows) and, on the rows of a node, scans every feature
and candidate in float64 for the best

    gain = GL^2/(HL+lambda) + GR^2/(HR+lambda) - G^2/(H+lambda),
           HL, HR >= min_sum_hessian_in_leaf

``split_gain_short``: for the root and a seeded sample of inner nodes, how
far the gain of the split the program CHOSE (its feature and threshold
applied to the same rows) falls short of that best, as a share of it.  A
near-tie that bfloat16 flips costs nothing here; a scan over half the
features does.  ``order_excess``: leaf-wise growth splits the leaf with
the largest gain first, so replaying the tree's own splits best-first
gives every final leaf the least gain that was taken after it existed,
and the leaf's own best gain may not exceed that; read on the leaves
likeliest to.  ``leaves_off``: every tree has exactly ``num_leaves`` leaves.
"""

from __future__ import annotations

import heapq
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROUTE_BLOCK = 1 << 18
ROUTE_THREADS = 8
EDGE_SAMPLE = 200_000     # rows the reference's candidate thresholds use
NODE_ROWS = 1 << 19       # rows of one node the split search reads at most


def flatten_tree(structure: dict) -> dict:
    """Arrays from one ``tree_structure`` of a LightGBM-style model dump."""
    feat, thr, left, right, value, count = [], [], [], [], [], []

    def add(node: dict) -> int:
        i = len(feat)
        for a in (feat, thr, left, right, value, count):
            a.append(0)
        if "left_child" not in node:
            feat[i], left[i], right[i] = -1, -1, -1
            value[i] = float(node["leaf_value"])
            count[i] = int(node.get("leaf_count", -1))
            return i
        if node.get("decision_type", "<=") != "<=":
            raise ValueError("the reference walks numeric '<=' splits only")
        feat[i] = int(node["split_feature"])
        thr[i] = float(node["threshold"])
        count[i] = int(node.get("internal_count", -1))
        left[i] = add(node["left_child"])
        right[i] = add(node["right_child"])
        return i

    import sys
    old = sys.getrecursionlimit()
    sys.setrecursionlimit(max(old, 10_000))
    try:
        add(structure)
    finally:
        sys.setrecursionlimit(old)
    return dict(feature=np.asarray(feat, np.int32),
                threshold=np.asarray(thr, np.float64),
                left=np.asarray(left, np.int32),
                right=np.asarray(right, np.int32),
                value=np.asarray(value, np.float64),
                count=np.asarray(count, np.int64))


def _route_block(X: np.ndarray, tree: dict) -> np.ndarray:
    n = X.shape[0]
    node = np.zeros(n, np.int32)
    feature, threshold = tree["feature"], tree["threshold"]
    left, right = tree["left"], tree["right"]
    active = np.arange(n)
    while active.size:
        cur = node[active]
        f = feature[cur]
        inner = f >= 0
        if not inner.all():
            active, cur, f = active[inner], cur[inner], f[inner]
            if not active.size:
                break
        x = X[active, f].astype(np.float64)
        node[active] = np.where(x <= threshold[cur], left[cur], right[cur])
    return node


def route(X: np.ndarray, tree: dict, threads: int = ROUTE_THREADS
          ) -> np.ndarray:
    """Node index of the leaf each raw row reaches (``x <= threshold`` goes
    left, compared in float64), in row blocks over a few threads."""
    n = X.shape[0]
    out = np.empty(n, np.int32)
    starts = range(0, n, ROUTE_BLOCK)

    def one(s):
        out[s:s + ROUTE_BLOCK] = _route_block(X[s:s + ROUTE_BLOCK], tree)

    if threads <= 1 or n <= ROUTE_BLOCK:
        for s in starts:
            one(s)
    else:
        with ThreadPoolExecutor(threads) as pool:
            list(pool.map(one, starts))
    return out


def init_score(y: np.ndarray) -> float:
    p = float(np.mean(y, dtype=np.float64))
    return float(np.log(p / (1.0 - p)))


def grad_hess(score: np.ndarray, y: np.ndarray):
    p = 1.0 / (1.0 + np.exp(-score))
    return p - y, p * (1.0 - p)


def logloss(score: np.ndarray, y: np.ndarray) -> float:
    return float(np.mean(np.logaddexp(0.0, score) - y * score))


def candidate_edges(X, max_bin: int, rng) -> list:
    """The reference's own candidate thresholds: per feature the distinct
    values at the ``max_bin - 1`` inner quantiles of a seeded sample of
    raw rows (``x <= edge`` goes left)."""
    n = X.shape[0]
    take = np.sort(rng.choice(n, size=min(EDGE_SAMPLE, n), replace=False))
    S = np.sort(X[take], axis=0)
    at = (np.arange(1, max_bin) * (len(take) - 1)) // max_bin
    return [np.unique(S[at, f]) for f in range(X.shape[1])]


def gain_of(GL, HL, G, H, lam):
    return (GL * GL / (HL + lam) + (G - GL) ** 2 / (H - HL + lam)
            - G * G / (H + lam))


def best_gain(Xn, g, h, edges, lam, min_hess, pool=None) -> float:
    """Largest gain over every feature and candidate threshold on the rows
    ``Xn`` (raw values) with gradients ``g`` and hessians ``h``; ``-inf``
    where no candidate leaves ``min_hess`` on both sides."""
    G, H = float(g.sum()), float(h.sum())

    def one(f):
        e = edges[f]          # values of X itself: compared in X's own type
        code = np.searchsorted(e, Xn[:, f], side="left")
        GL = np.cumsum(np.bincount(code, weights=g, minlength=len(e) + 1))
        HL = np.cumsum(np.bincount(code, weights=h, minlength=len(e) + 1))
        GL, HL = GL[:-1], HL[:-1]
        ok = (HL >= min_hess) & (H - HL >= min_hess)
        if not ok.any():
            return -np.inf
        return float(gain_of(GL[ok], HL[ok], G, H, lam).max())

    feats = range(Xn.shape[1])
    return max(pool.map(one, feats) if pool else map(one, feats))


def node_sums(tree: dict, per_leaf: np.ndarray) -> np.ndarray:
    """Sums over each node's subtree (children follow their parent)."""
    out = np.array(per_leaf, np.float64)
    for i in range(len(out) - 1, -1, -1):
        if tree["feature"][i] >= 0:
            out[i] = out[tree["left"][i]] + out[tree["right"][i]]
    return out


def subtree_end(tree: dict) -> np.ndarray:
    """``end[i]``: nodes ``i .. end[i]-1`` are node ``i``'s subtree (the
    flattening is in preorder)."""
    m = len(tree["feature"])
    end = np.arange(1, m + 1)
    for i in range(m - 1, -1, -1):
        if tree["feature"][i] >= 0:
            end[i] = end[tree["right"][i]]
    return end


def best_first_bounds(tree: dict, gain: np.ndarray) -> np.ndarray:
    """Replay the tree's own splits largest gain first; for every leaf the
    least gain among the splits taken after the leaf came to be (``inf``
    where none was)."""
    feature, left, right = tree["feature"], tree["left"], tree["right"]
    born = np.zeros(len(feature), np.int64)
    taken, heap = [], ([(-gain[0], 0)] if feature[0] >= 0 else [])
    while heap:
        _, i = heapq.heappop(heap)
        taken.append(gain[i])
        for c in (left[i], right[i]):
            born[c] = len(taken)
            if feature[c] >= 0:
                heapq.heappush(heap, (-gain[c], c))
    least_after = np.append(
        np.minimum.accumulate(np.asarray(taken, np.float64)[::-1])[::-1],
        np.inf) if taken else np.array([np.inf])
    return np.where(feature < 0, least_after[born], np.nan)


def check_splits(X, tree, leaf, g, h, sums, edges, hyper, rng,
                 nodes: int, leaves: int, pool=None) -> dict:
    """``split_gain_short`` over the root and ``nodes`` seeded inner nodes
    and ``order_excess`` over the ``leaves`` likeliest leaves of one tree
    (``leaf``: the leaf node each row reaches; ``g, h``: the reference's
    gradients before this tree; ``sums``: their sums and the row count
    per leaf node)."""
    lam = float(hyper["lambda_l2"])
    min_hess = float(hyper["min_sum_hessian_in_leaf"])
    feature, threshold = tree["feature"], tree["threshold"]
    m = len(feature)
    G, H, C = (node_sums(tree, a) for a in sums)
    inner = np.flatnonzero(feature >= 0)
    out = {"split_gain_short": 0.0, "order_excess": 0.0,
           "nodes_checked": 0, "leaves_checked": 0}
    if not inner.size:
        return out
    gain = np.full(m, np.nan)
    li, ri = tree["left"][inner], tree["right"][inner]
    gain[inner] = gain_of(G[li], H[li], G[inner], H[inner], lam)
    typical = float(np.median(gain[inner]))
    end = subtree_end(tree)
    # a node's subtree is a run of node numbers, so its rows are a run of
    # the rows sorted by the leaf they reach
    by_leaf = np.argsort(leaf.astype(np.int16 if m < 2 ** 15 else np.int32),
                         kind="stable")
    first = np.concatenate([[0], np.cumsum(np.bincount(leaf, minlength=m))])

    def rows_of(i):
        rows = by_leaf[first[i]:first[end[i]]]
        step = -(-len(rows) // NODE_ROWS)
        if step > 1:
            rows = rows[int(rng.integers(step))::step]
        return rows, len(rows) / max(float(C[i]), 1.0)

    def best_on(i):
        rows, share = rows_of(i)
        Xn, gn, hn = X[rows], g[rows], h[rows]
        return (Xn, gn, hn, share,
                best_gain(Xn, gn, hn, edges, lam, min_hess * share, pool))

    pick = inner[inner > 0]
    pick = rng.choice(pick, size=min(nodes, len(pick)), replace=False)
    short = []
    for i in [0] + sorted(int(p) for p in pick):
        Xn, gn, hn, share, best = best_on(i)
        goes_left = Xn[:, feature[i]].astype(np.float64) <= threshold[i]
        with np.errstate(divide="ignore", invalid="ignore"):
            chosen = float(gain_of(gn[goes_left].sum(), hn[goes_left].sum(),
                                   gn.sum(), hn.sum(), lam))
        # a split that sends every row one way has gained nothing
        short.append((best - chosen) / max(best, typical * share)
                     if np.isfinite(chosen) else 1.0)
    out["split_gain_short"] = float(max(short))
    out["nodes_checked"] = len(short)

    bound = best_first_bounds(tree, gain)
    # the leaves likeliest to hold too much: by the gain their parent's
    # split would leave them, hessian for hessian, against their bound
    parent = np.zeros(m, np.int64)
    parent[li], parent[ri] = inner, inner
    held = np.flatnonzero((feature < 0) & np.isfinite(bound))
    owed = (gain[parent[held]] * H[held] / H[parent[held]]
            / np.maximum(bound[held], typical))
    held = held[np.argsort(-owed, kind="stable")][:leaves]
    excess = []
    for i in held:
        _, _, _, share, best = best_on(int(i))
        if np.isfinite(best):
            excess.append((best / share - bound[i])
                          / max(bound[i], typical))
    if excess:
        out["order_excess"] = float(max(excess))
    out["leaves_checked"] = len(excess)
    return out


def check_rounds(X, y, trees, scores_after, program_init, hyper,
                 seed: int = 0, split_nodes: int = 0,
                 order_leaves: int = 0) -> dict:
    """Follow the program's first ``len(trees)`` rounds.

    ``trees``: flattened dumps in round order; ``scores_after[k]``: the
    program's training scores after round ``k+1`` (float32, one per row);
    ``hyper``: the constants the configuration states (``learning_rate``,
    ``lambda_l2``; with ``split_nodes`` or ``order_leaves`` also
    ``max_bin`` and ``min_sum_hessian_in_leaf``).  Returns plain numbers,
    one group per round."""
    lr, lam = float(hyper["learning_rate"]), float(hyper["lambda_l2"])
    y = np.asarray(y, np.float64)
    n = len(y)
    s0 = init_score(y)
    out = {"init_abs": abs(float(program_init) - s0), "rounds": []}
    score = np.full(n, s0, np.float64)
    search = split_nodes > 0 or order_leaves > 0
    rng = np.random.default_rng([int(seed), 25])
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
        score = score + lr * tree["value"][leaf]
        rd.update({
            "leaves": int(is_leaf.sum()),
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


def check_sample(X_rows, trees, init, learning_rate, program_scores) -> float:
    """Widest gap between the program's final training scores on a sample
    of rows and ``init + learning_rate * sum_k v_k[leaf_k(x)]`` walked over
    EVERY tree the run produced."""
    score = np.full(X_rows.shape[0], float(init), np.float64)
    for tree in trees:
        score += learning_rate * tree["value"][route(X_rows, tree, threads=1)]
    return float(np.abs(np.asarray(program_scores, np.float64) - score).max())
