"""The plain reference of the categorical cell: ``gbdt_check`` on a table
whose categorical columns were given to the program as
``categorical_feature``.

numpy and float64 only; imports nothing of the program but
``gbdt_check``'s tree utilities, and knows only raw values: it reads the
raw rows and the program's answer (``Booster.dump_model()``), where a
categorical split is ``decision_type '=='`` with the LEFT set as LightGBM
writes it, the raw category values joined by ``||``.  Its departures from
``gbdt_check``:

* routing (:func:`route`): a row goes left at a categorical split iff its
  raw value is one of the set's, so NaN, a value never seen and a rare
  category go right; at a numeric split iff ``x <= threshold`` (NaN right);
* its own category bins, by LightGBM's rule (``BinMapper::FindBin`` for a
  categorical feature) on the rows LightGBM bins from (:func:`bin_sample`:
  200,000 of them, drawn with ``data_random_seed``): the distinct values
  ranked by their count there, at most ``max_bin - 1`` kept, none past the
  second seen fewer than ``min_data_in_bin`` times; the rest and NaN are
  the overflow, in no set.  The cap is the program's, not LightGBM's:
  LightGBM keeps categories past ``max_bin`` until they cover 99 % of the
  sampled rows, and on the categorical cell's table the 254 most common
  submodels cover about two thirds of them, so LightGBM would give many
  more submodels bins of their own; the reference holds the program to its
  own cap (one-byte codes, one-hots at most 256 bins tall), and cannot see
  that departure.  On the whole table's counts the kept
  categories part from the sample's near the 254th (on the categorical
  cell's table 8 of 254 submodels and 6 of 254 models, 0.5 % of the
  rows): a set no binning of the sample can build would be searched here,
  and a deep node held by one such category reads as a split the program
  missed;
* the split search (:func:`best_cat_gain`) of a categorical column is
  LightGBM's ``FindBestThresholdCategorical``: one category against the
  rest where the column has at most ``max_cat_to_onehot`` bins (its kept
  categories and the overflow), else the categories holding ``cat_smooth``
  rows sorted by ``G / (H + cat_smooth)`` and their prefixes scanned from
  both ends, at most ``min(max_cat_threshold, (used + 1) / 2)``
  categories, ``min_data_per_group`` rows on the right and between two
  candidates, at ``lambda_l2 + cat_l2``; a split's gain is its children's
  objective less the node's at ``lambda_l2`` (LightGBM's ``gain_shift``),
  so it ranks with the numeric candidates.  Counts are the rows', not
  LightGBM's estimate from the hessian.  A node's category sets are
  searched on ALL its rows (its numeric thresholds, as ``gbdt_check``'s,
  on a sample of at most ``gbdt_check.NODE_ROWS``): a sorted scan over a
  few hundred categories of a few rows each picks noise, and read 0.43 to
  0.67 against sound trees on the categorical cell's table; the gains of
  both are taken in the sample's units;
* ``split_gain_short`` and ``order_excess`` use this gain for the tree's
  own categorical splits too, and its own candidate thresholds for the
  numeric columns (``gbdt_check.candidate_edges``' rule).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .gbdt_check import (EDGE_SAMPLE, NODE_ROWS, ROUTE_BLOCK, ROUTE_THREADS,
                         best_first_bounds, best_gain, grad_hess, init_score,
                         logloss, node_sums, subtree_end)


def parse_set(text) -> np.ndarray:
    """The raw values of a dumped category set (``"3||7||12"``)."""
    if isinstance(text, (list, tuple)):
        return np.asarray(text, np.float64)
    parts = [p for p in str(text).split("||") if p != ""]
    return np.asarray([float(p) for p in parts], np.float64)


class Table:
    """The raw rows' categorical columns as codes of their DISTINCT values
    (every distinct value its own code, NaN the last), made once: a set
    of raw values is then a set of codes, and routing by codes is routing
    by raw values."""

    def __init__(self, X: np.ndarray, categorical):
        self.X = X
        self.categorical = [int(c) for c in categorical]
        self.slot = {c: i for i, c in enumerate(self.categorical)}
        self.values, self.codes = [], None
        n = X.shape[0]
        if self.categorical:
            self.codes = np.empty((n, len(self.categorical)), np.int32)
        for i, c in enumerate(self.categorical):
            col = X[:, c]
            vals = np.unique(col[~np.isnan(col)]).astype(np.float64)
            code = np.searchsorted(vals, col.astype(np.float64))
            code[np.isnan(col)] = len(vals)
            self.values.append(vals)
            self.codes[:, i] = code

    def set_codes(self, c: int, raw: np.ndarray) -> np.ndarray:
        """Codes of the raw values ``raw`` of column ``c`` (values the
        table does not hold are left out: no row has them)."""
        vals = self.values[self.slot[c]]
        at = np.clip(np.searchsorted(vals, raw), 0, max(len(vals) - 1, 0))
        return at[(len(vals) > 0) & (vals[at] == raw)] if len(vals) else \
            np.zeros(0, np.int64)


def flatten_tree(structure: dict, table: Table) -> dict:
    """Arrays of one ``tree_structure``: ``gbdt_check.flatten_tree``'s and,
    for a categorical split, its set as a boolean row over the column's
    codes (``member[i]``; ``is_cat[i]``)."""
    feat, thr, left, right, value, count, cat, sets = ([] for _ in range(8))

    def add(node: dict) -> int:
        i = len(feat)
        for a in (feat, thr, left, right, value, count, cat):
            a.append(0)
        sets.append(None)
        if "left_child" not in node:
            feat[i], left[i], right[i] = -1, -1, -1
            value[i] = float(node["leaf_value"])
            count[i] = int(node.get("leaf_count", -1))
            return i
        feat[i] = int(node["split_feature"])
        count[i] = int(node.get("internal_count", -1))
        if node.get("decision_type", "<=") == "==":
            if feat[i] not in table.slot:
                raise ValueError(
                    f"a category set on column {feat[i]}, not categorical")
            cat[i] = 1
            sets[i] = table.set_codes(feat[i], parse_set(node["threshold"]))
            thr[i] = np.nan
        else:
            thr[i] = float(node["threshold"])
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
    m = len(feat)
    width = 1 + max([len(v) for v in table.values] + [0])
    member = np.zeros((m, width), bool)
    for i, s in enumerate(sets):
        if s is not None:
            member[i, s] = True
    return dict(feature=np.asarray(feat, np.int32),
                threshold=np.asarray(thr, np.float64),
                left=np.asarray(left, np.int32),
                right=np.asarray(right, np.int32),
                value=np.asarray(value, np.float64),
                count=np.asarray(count, np.int64),
                is_cat=np.asarray(cat, bool), member=member)


def _route_block(table: Table, rows: slice, tree: dict) -> np.ndarray:
    X = table.X[rows]
    codes = None if table.codes is None else table.codes[rows]
    n = X.shape[0]
    slot = np.full(max(table.X.shape[1], 1), -1, np.int64)
    for c, i in table.slot.items():
        slot[c] = i
    node = np.zeros(n, np.int32)
    feature, threshold = tree["feature"], tree["threshold"]
    left, right, is_cat = tree["left"], tree["right"], tree["is_cat"]
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
        go = x <= threshold[cur]
        c = is_cat[cur]
        if c.any():
            at = np.flatnonzero(c)
            go[at] = tree["member"][cur[at],
                                    codes[active[at], slot[f[at]]]]
        node[active] = np.where(go, left[cur], right[cur])
    return node


def route(table: Table, tree: dict, rows=None,
          threads: int = ROUTE_THREADS) -> np.ndarray:
    """Node index of the leaf each row reaches (all rows, or the rows
    ``rows``: an index array), in row blocks over a few threads."""
    if rows is not None:
        sub = Table.__new__(Table)
        sub.X, sub.categorical, sub.slot = (table.X[rows], table.categorical,
                                            table.slot)
        sub.values = table.values
        sub.codes = None if table.codes is None else table.codes[rows]
        return route(sub, tree, threads=1)
    n = table.X.shape[0]
    out = np.empty(n, np.int32)
    starts = range(0, n, ROUTE_BLOCK)

    def one(s):
        out[s:s + ROUTE_BLOCK] = _route_block(
            table, slice(s, s + ROUTE_BLOCK), tree)

    if threads <= 1 or n <= ROUTE_BLOCK:
        for s in starts:
            one(s)
    else:
        with ThreadPoolExecutor(threads) as pool:
            list(pool.map(one, starts))
    return out


def kept_categories(table: Table, hyper: dict) -> list:
    """Per categorical column (in ``table.categorical``'s order) the codes
    of the categories it keeps, by LightGBM's rule on the counts of the
    rows LightGBM bins from, capped at ``max_bin - 1`` as the program caps
    it (module docstring)."""
    max_bin = int(hyper["max_bin"])
    least = int(hyper.get("min_data_in_bin", 3))
    rows = bin_sample(table.X.shape[0], hyper)
    out = []
    for i in range(len(table.categorical)):
        d = len(table.values[i])
        cnt = np.bincount(table.codes[rows, i], minlength=d + 1)[:d]
        rank = np.argsort(-cnt, kind="stable")[:max_bin - 1]
        rare = np.flatnonzero(cnt[rank] < least)
        rare = rare[rare > 1]
        if len(rare):
            rank = rank[:rare[0]]
        out.append(np.sort(rank[cnt[rank] > 0]))
    return out


def bin_sample(n: int, hyper: dict) -> np.ndarray:
    """The rows LightGBM bins a table from: ``bin_construct_sample_cnt``
    (200,000) of them, drawn with ``data_random_seed`` (1), here numpy's
    ``default_rng(seed).choice`` without replacement (all rows of a smaller
    table)."""
    if n <= EDGE_SAMPLE:
        return np.arange(n)
    rng = np.random.default_rng(int(hyper.get("data_random_seed", 1)))
    return np.sort(rng.choice(n, size=EDGE_SAMPLE, replace=False))


def cat_gain_of(GL, HL, G, H, lam, lam_cat):
    """A category set's gain: children at ``lam_cat`` (``lambda_l2`` +
    ``cat_l2`` for a sorted scan's set, ``lambda_l2`` for one category
    against the rest), the node at ``lam``."""
    return (GL * GL / (HL + lam_cat) + (G - GL) ** 2 / (H - HL + lam_cat)
            - G * G / (H + lam))


def best_cat_gain(code, kept, g, h, hyper, share: float) -> float:
    """LightGBM's best category-set gain on one node's rows of one column
    (``code``: the rows' codes; ``kept``: the column's kept categories),
    ``-inf`` where no set qualifies; limits and lambdas scaled by
    ``share``."""
    lam = float(hyper["lambda_l2"]) * share
    lam_cat = lam + float(hyper.get("cat_l2", 10.0)) * share
    smooth = float(hyper.get("cat_smooth", 10.0)) * share
    min_hess = float(hyper["min_sum_hessian_in_leaf"]) * share
    min_data = float(hyper.get("min_data_in_leaf", 1)) * share
    per_group = float(hyper.get("min_data_per_group", 100)) * share
    max_cat = int(hyper.get("max_cat_threshold", 32))
    G, H, N = float(g.sum()), float(h.sum()), float(len(g))
    d = int(code.max()) + 1 if len(code) else 1
    size = max(d, int(kept.max()) + 1 if len(kept) else 0)
    gb = np.bincount(code, weights=g, minlength=size)[kept]
    hb = np.bincount(code, weights=h, minlength=size)[kept]
    cb = np.bincount(code, minlength=size)[kept].astype(np.float64)
    best = -np.inf
    if len(kept) + 1 <= int(hyper.get("max_cat_to_onehot", 4)):
        ok = ((cb >= min_data) & (hb >= min_hess) & (N - cb >= min_data)
              & (H - hb >= min_hess) & (cb > 0))
        if ok.any():
            best = float(cat_gain_of(gb[ok], hb[ok], G, H, lam, lam).max())
        return best
    elig = np.flatnonzero(cb >= smooth)
    used = len(elig)
    if not used:
        return best
    ratio = gb[elig] / (hb[elig] + smooth)
    asc = elig[np.argsort(ratio, kind="stable")]
    span = min(used, max_cat, (used + 1) // 2)
    for order in (asc, asc[::-1]):
        GL = HL = CL = group = 0.0
        for t in order[:span]:
            GL += gb[t]
            HL += hb[t]
            CL += cb[t]
            group += cb[t]
            if CL < min_data or HL < min_hess:
                continue
            if (N - CL < min_data or N - CL < per_group
                    or H - HL < min_hess):
                break
            if group < per_group:
                continue
            group = 0.0
            best = max(best, float(cat_gain_of(GL, HL, G, H, lam, lam_cat)))
    return best


def leaf_lambdas(table: Table, kept: list, tree: dict, hyper: dict):
    """Per node the lambda of its value: ``lambda_l2``, and ``lambda_l2 +
    cat_l2`` for a child of a sorted category set's split (LightGBM's
    ``FindBestThresholdCategorical`` computes those children's outputs at
    the lambda it scanned with; one category against the rest at
    ``lambda_l2``)."""
    lam = float(hyper["lambda_l2"])
    out = np.full(len(tree["feature"]), lam)
    to_onehot = int(hyper.get("max_cat_to_onehot", 4))
    for i in np.flatnonzero(tree["is_cat"]):
        k = kept[table.slot[int(tree["feature"][i])]]
        if len(k) + 1 > to_onehot:
            out[[tree["left"][i], tree["right"][i]]] = (
                lam + float(hyper.get("cat_l2", 10.0)))
    return out


def check_splits(table, kept, tree, leaf, g, h, sums, edges, hyper, rng,
                 nodes: int, leaves: int, pool=None) -> dict:
    """``gbdt_check.check_splits`` over numeric and categorical columns:
    ``split_gain_short`` over the root and ``nodes`` seeded inner nodes,
    ``order_excess`` over the ``leaves`` likeliest leaves of one tree;
    ``split_worst`` (read, not compared): the node that falls shortest, its
    rows, the column the tree split it on, and the categorical column of
    the reference's best split there (-1: a numeric one)."""
    lam = float(hyper["lambda_l2"])
    lam_cat = lam + float(hyper.get("cat_l2", 10.0))
    to_onehot = int(hyper.get("max_cat_to_onehot", 4))
    min_hess = float(hyper["min_sum_hessian_in_leaf"])
    feature, threshold = tree["feature"], tree["threshold"]
    m = len(feature)
    G, H, C = (node_sums(tree, a) for a in sums)
    inner = np.flatnonzero(feature >= 0)
    out = {"split_gain_short": 0.0, "order_excess": 0.0,
           "nodes_checked": 0, "leaves_checked": 0}
    if not inner.size:
        return out

    def lam_of(i):
        """The children's lambda of node ``i``'s split."""
        if not tree["is_cat"][i]:
            return lam
        k = kept[table.slot[int(feature[i])]]
        return lam if len(k) + 1 <= to_onehot else lam_cat

    gain = np.full(m, np.nan)
    li, ri = tree["left"][inner], tree["right"][inner]
    gain[inner] = [cat_gain_of(G[l], H[l], G[i], H[i], lam, lam_of(i))
                   for i, l in zip(inner, li)]
    typical = float(np.median(gain[inner]))
    end = subtree_end(tree)
    by_leaf = np.argsort(leaf.astype(np.int16 if m < 2 ** 15 else np.int32),
                         kind="stable")
    first = np.concatenate([[0], np.cumsum(np.bincount(leaf, minlength=m))])
    numeric = [f for f in range(table.X.shape[1]) if f not in table.slot]
    num_edges = [edges[f] for f in numeric]

    def rows_of(i):
        rows = by_leaf[first[i]:first[end[i]]]
        step = -(-len(rows) // NODE_ROWS)
        if step > 1:
            rows = rows[int(rng.integers(step))::step]
        return rows, len(rows) / max(float(C[i]), 1.0)

    def best_on(i):
        # numeric candidates on the node's sample; category sets on ALL its
        # rows (a sorted scan of many categories on a few rows each would
        # pick noise), in the sample's units
        rows, share = rows_of(i)
        gn, hn = g[rows], h[rows]
        best = best_gain(table.X[rows][:, numeric], gn, hn, num_edges,
                         lam * share, min_hess * share, pool)
        full = by_leaf[first[i]:first[end[i]]]
        gf, hf = g[full], h[full]
        best_col = -1
        for s, c in enumerate(table.categorical):
            got = share * best_cat_gain(
                table.codes[full, s], kept[s], gf, hf, hyper, 1.0)
            if got > best:
                best, best_col = got, c
        return rows, gn, hn, share, best, best_col

    pick = inner[inner > 0]
    pick = rng.choice(pick, size=min(nodes, len(pick)), replace=False)
    short, where = [], []
    for i in [0] + sorted(int(p) for p in pick):
        rows, gn, hn, share, best, best_col = best_on(i)
        f = int(feature[i])
        with np.errstate(divide="ignore", invalid="ignore"):
            if tree["is_cat"][i]:
                full = by_leaf[first[i]:first[end[i]]]
                gf, hf = g[full], h[full]
                left = tree["member"][i, table.codes[full, table.slot[f]]]
                chosen = share * float(cat_gain_of(
                    gf[left].sum(), hf[left].sum(), gf.sum(), hf.sum(), lam,
                    lam_of(i)))
            else:
                left = table.X[rows, f].astype(np.float64) <= threshold[i]
                chosen = float(cat_gain_of(gn[left].sum(), hn[left].sum(),
                                           gn.sum(), hn.sum(), lam * share,
                                           lam * share))
        short.append((best - chosen) / max(best, typical * share)
                     if np.isfinite(chosen) else 1.0)
        where.append([i, int(C[i]), f, best_col])
    worst = int(np.argmax(short))
    out["split_gain_short"] = float(short[worst])
    out["split_worst"] = where[worst]
    out["nodes_checked"] = len(short)

    bound = best_first_bounds(tree, gain)
    parent = np.zeros(m, np.int64)
    parent[li], parent[ri] = inner, inner
    held = np.flatnonzero((feature < 0) & np.isfinite(bound))
    owed = (gain[parent[held]] * H[held] / H[parent[held]]
            / np.maximum(bound[held], typical))
    held = held[np.argsort(-owed, kind="stable")][:leaves]
    excess = []
    for i in held:
        _, _, _, share, best, _ = best_on(int(i))
        if np.isfinite(best):
            excess.append((best / share - bound[i])
                          / max(bound[i], typical))
    if excess:
        out["order_excess"] = float(max(excess))
    out["leaves_checked"] = len(excess)
    return out


def candidate_edges(table: Table, max_bin: int, rng) -> list:
    """``gbdt_check.candidate_edges`` of the numeric columns (none for a
    categorical one): the distinct values at the ``max_bin - 1`` inner
    quantiles of each column's non-NaN values in a seeded sample."""
    X = table.X
    n = X.shape[0]
    take = np.sort(rng.choice(n, size=min(EDGE_SAMPLE, n), replace=False))
    S = np.sort(X[take], axis=0)
    edges = []
    for f in range(X.shape[1]):
        vals = S[:, f][~np.isnan(S[:, f])]
        if f in table.slot or not len(vals):
            edges.append(np.zeros(0, X.dtype))
            continue
        at = (np.arange(1, max_bin) * (len(vals) - 1)) // max_bin
        edges.append(np.unique(vals[at]))
    return edges


def dump_faults(structure: dict, categorical) -> int:
    """Splits of one dumped tree on a categorical column that are not a
    category set sending NaN right (a threshold on category numbers, or a
    set with ``default_left``)."""
    if "left_child" not in structure:
        return 0
    wrong = int(int(structure["split_feature"]) in categorical and (
        structure.get("decision_type") != "=="
        or structure.get("default_left", False)))
    return (wrong + dump_faults(structure["left_child"], categorical)
            + dump_faults(structure["right_child"], categorical))


def check_rounds(table: Table, y, trees, scores_after, program_init, hyper,
                 seed: int = 0, split_nodes: int = 0,
                 order_leaves: int = 0) -> dict:
    """``gbdt_check.check_rounds`` with the routing, bins and search above;
    the same numbers, one group per round, and ``cat_splits`` (the
    tree's category-set splits)."""
    lr, lam = float(hyper["learning_rate"]), float(hyper["lambda_l2"])
    y = np.asarray(y, np.float64)
    n = len(y)
    s0 = init_score(y)
    out = {"init_abs": abs(float(program_init) - s0), "rounds": []}
    score = np.full(n, s0, np.float64)
    search = split_nodes > 0 or order_leaves > 0
    rng = np.random.default_rng([int(seed), 44])
    edges = (candidate_edges(table, int(hyper["max_bin"]), rng) if search
             else None)
    kept = kept_categories(table, hyper)
    pool = ThreadPoolExecutor(ROUTE_THREADS) if search else None
    for tree, prog_score in zip(trees, scores_after):
        g, h = grad_hess(score, y)
        leaf = route(table, tree)
        m = len(tree["feature"])
        G = np.bincount(leaf, weights=g, minlength=m)
        H = np.bincount(leaf, weights=h, minlength=m)
        C = np.bincount(leaf, minlength=m)
        is_leaf = tree["feature"] < 0
        lam_leaf = leaf_lambdas(table, kept, tree, hyper)[is_leaf]
        v_ref = -G[is_leaf] / (H[is_leaf] + lam_leaf)
        v_prog = tree["value"][is_leaf]
        scale = np.maximum(np.abs(v_ref), np.median(np.abs(v_ref)))
        rel = np.abs(v_prog - v_ref) / scale
        rd = {}
        if search:
            rd = check_splits(table, kept, tree, leaf, g, h, (G, H, C),
                              edges, hyper, rng, split_nodes, order_leaves,
                              pool)
        score = score + lr * tree["value"][leaf]
        rd.update({
            "leaves": int(is_leaf.sum()),
            "cat_splits": int(tree["is_cat"].sum()),
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


def check_sample(table: Table, rows, trees, init, learning_rate,
                 program_scores) -> float:
    """``gbdt_check.check_sample`` routed as above: the widest gap between
    the program's final training scores on the rows ``rows`` and a walk
    over every tree of the run."""
    score = np.full(len(rows), float(init), np.float64)
    for tree in trees:
        score += learning_rate * tree["value"][route(table, tree, rows)]
    return float(np.abs(np.asarray(program_scores, np.float64) - score).max())
