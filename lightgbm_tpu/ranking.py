"""LambdaRank objective + NDCG/MAP metrics (MSLR-WEB30K north-star config).

TPU-native replacement for LightGBM's ``src/objective/rank_objective.hpp``
(LambdarankNDCG) and ``src/metric/rank_metric.hpp``.  Upstream iterates
queries serially and documents pairwise; here a round's lambdas are one
dense tensor program over queries PACKED BY LENGTH:

  * ``set_group`` (host, once per training, no Python loop over queries)
    sorts the queries into a few blocks ``[Q_b, G_b]``, ``G_b`` from a
    fixed ladder of widths (8, 16, 24, 32, 48, 64, 96, ...: each query
    goes to the narrowest that holds it, so padding stays near 1.2x where
    one ``[Q, G_max]`` layout pays ``G_max / mean``); equal-length queries
    are ONE block that maps to the row axis by reshape alone.  The
    layout's STATIC part (``layout``: the blocks' shapes) keys the compiled
    round; its tensors (``groups``: each block's first rows, lengths,
    label gains and inverse max-DCG, and each row's slot) are OPERANDS of
    the round program,
    so two trainings on equal shapes share one program;
  * per round and block: scores come in by one windowed gather (a query's
    documents are contiguous rows), ONE sort no wider than the block puts
    them in rank order (ties in row order, as ``std::stable_sort``), and
    the pair block is ``[Q_b, min(T, G_b), G_b]``: rank ``i`` inside
    ``lambdarank_truncation_level`` against every rank ``j > i``, which
    is exactly what LightGBM's double loop visits.  In rank order the
    position discounts are constants;
  * the mathematics is ``LambdarankNDCG::GetGradientsForOneQuery``: for a
    pair with gains ``g_hi > g_lo``, ``dNDCG = (g_hi - g_lo) * |disc_hi -
    disc_lo| / maxDCG@T``, divided by ``0.01 + |s_hi - s_lo|`` under
    ``lambdarank_norm`` when the query's best and worst scores differ;
    ``p = 1 / (1 + exp(sigmoid * (s_hi - s_lo)))``; ``lambda = sigmoid *
    p * dNDCG`` (off the better document's gradient, onto the other's),
    ``hessian = sigmoid^2 * p * (1 - p) * dNDCG`` onto both; under
    ``lambdarank_norm`` both scaled per query by ``log2(1 + L) / L``,
    ``L = 2 * sum of lambda`` (each pair counts for its two documents).
    No floor under the hessians.  Which of a pair is the better document is
    read from the label GAINS (LightGBM: the labels; the same wherever
    ``label_gain`` increases with the label, as the default does);
  * a second sort (by slot) takes the sums back to slot order, and every
    row reads its own slot of the blocks laid end to end (``row_slot``,
    one gather a round): each row is written exactly once, and padding
    slots, which hold exact zeros, are read by nobody.

Label gains default to LightGBM's ``2^label - 1`` table.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .config import Params
from .metrics import Metric
from .objectives import Objective
from .utils import profiling

_LANE = 8  # block widths are multiples of the sublane
# pair slots one chunk of a block's queries evaluates at a time: a float32
# temporary of 16 MB, so a round's pair blocks never add up in memory
_PAIR_CHUNK = 4 << 20


def _group_slots(starts: np.ndarray, sizes: np.ndarray, width: int,
                 n_rows: int) -> Tuple[np.ndarray, np.ndarray]:
    """``(row [Q, width] int64, valid [Q, width] bool)`` of group-contiguous
    queries: slot ``j`` of a query is row ``start + j``; padding slots are
    clipped into the table and masked by ``valid``."""
    col = np.arange(width, dtype=np.int64)
    valid = col[None, :] < sizes[:, None]
    row = np.minimum(starts[:, None] + col[None, :], max(n_rows - 1, 0))
    return row, valid


def _pack_groups(group_sizes: np.ndarray,
                 max_docs: Optional[int] = None) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side: group sizes -> (doc_idx [Q, G] int32, valid [Q, G] bool),
    ONE block as wide as the longest query (the ranking metrics' layout).

    Rows are assumed group-contiguous (the lightgbm Dataset contract: group
    sizes partition the row axis in order — SURVEY.md §2B group field).
    Padding slots point at row 0 and are masked by ``valid``.
    """
    sizes = np.asarray(group_sizes, np.int64)
    g = int(sizes.max()) if max_docs is None else int(max_docs)
    g = max(_LANE, -(-g // _LANE) * _LANE)
    row, valid = _group_slots(np.cumsum(sizes) - sizes, sizes, g,
                              int(sizes.sum()))
    return np.where(valid, row, 0).astype(np.int32), valid


def _width_ladder(longest: int) -> np.ndarray:
    """8, 16, 24, 32, 48, 64, 96, ...: the powers of two and the widths
    halfway between them, up to the first that holds ``longest``."""
    out = [_LANE]
    while out[-1] < longest:
        w = out[-1]
        if w & (w - 1):                 # 24, 48, 96, ...: on to the power
            out.append(w * 4 // 3)
        else:                           # (12 is no multiple of 8)
            out.append(w * 3 // 2 if w >= 16 else w * 2)
    return np.asarray(out, np.int64)


def _label_gain_table(label_gain: Optional[List[float]],
                      max_label: int) -> np.ndarray:
    if label_gain is not None:
        t = np.asarray(label_gain, np.float64)
        if len(t) <= max_label:
            raise ValueError(
                f"label_gain has {len(t)} entries but labels reach {max_label}")
        return t
    return (2.0 ** np.arange(max_label + 1)) - 1.0  # LightGBM default


def _inverse_max_dcg(gains: np.ndarray, valid: np.ndarray,
                     truncation: int) -> np.ndarray:
    """Host-side per-query 1/maxDCG@truncation (0 when maxDCG == 0)."""
    q, g = gains.shape
    neg = np.where(valid, gains, -np.inf)
    top = -np.sort(-neg, axis=1)[:, :truncation]           # desc
    disc = 1.0 / np.log2(2.0 + np.arange(top.shape[1]))
    dcg = np.sum(np.where(np.isfinite(top), top, 0.0) * disc, axis=1)
    inv = np.zeros(q)
    nz = dcg > 0
    inv[nz] = 1.0 / dcg[nz]
    return inv


def pairs_visited(sizes: np.ndarray, truncation: int) -> int:
    """Pairs LightGBM's double loop visits on these groups: ``i`` over the
    first ``min(T, n - 1)`` ranks, ``j`` over every rank after ``i``."""
    n = np.asarray(sizes, np.int64)
    m = np.minimum(int(truncation), np.maximum(n - 1, 0))
    return int(np.sum(m * (n - 1) - m * (m - 1) // 2))


class LambdaRank(Objective):
    """Pairwise LambdaRank with ΔNDCG weighting (lambdarank objective)."""

    name = "lambdarank"
    needs_group = True

    def __init__(self, params: Params):
        super().__init__(params)
        self.sigma = float(params.sigmoid)
        self.truncation = int(params.lambdarank_truncation_level)
        self.norm = bool(params.lambdarank_norm)
        # the packed layout: ``layout`` is its static part (hashable: with
        # the three constants above it keys the compiled round), ``groups``
        # the tensors a round takes as operands, ``facts`` its own counts
        self.layout = None
        self.groups = None
        self.facts = {}

    # -- group setup (called by Booster._setup_training) -----------------
    @profiling.span("lgbtpu.rank.pack")
    def set_group(self, group_sizes: np.ndarray, y_host: np.ndarray,
                  n_padded: int) -> None:
        """Pack the queries by length (``n_padded``: the length of the row
        axis a round's scores have, the table's padding rows included)."""
        sizes = np.asarray(group_sizes, np.int64).reshape(-1)
        y_host = np.asarray(y_host).reshape(-1)
        n = int(sizes.sum())
        if n > int(n_padded) or n > len(y_host):
            raise ValueError(
                f"group sizes sum to {n}, over the {len(y_host)} labels")
        starts = np.cumsum(sizes) - sizes
        uniform = bool(len(sizes)) and bool((sizes == sizes[0]).all())
        if uniform:
            # equal lengths: ONE block, and its slots map to the row axis
            # by reshape + pad alone, no gather and no scatter
            width = np.full(len(sizes), max(
                _LANE, -(-int(sizes[0]) // _LANE) * _LANE), np.int64)
        else:
            ladder = _width_ladder(int(sizes.max()) if len(sizes) else 1)
            width = ladder[np.searchsorted(ladder, sizes)]
        max_label = int(y_host[:n].max()) if n else 0
        table = _label_gain_table(self.params.label_gain, max_label)
        blocks, shapes = [], []
        # the way back: the slot each row reads its sums from, in the
        # blocks laid end to end; the table's padding rows read the zero
        # that follows the last block
        base = 0
        row_slot = (None if uniform else
                    np.full(int(n_padded), int(width.sum()), np.int64))
        for g in np.unique(width):          # a dozen widths, not queries
            members = np.flatnonzero(width == g)
            row, valid = _group_slots(starts[members], sizes[members],
                                      int(g), n)
            gains = np.where(valid, table[y_host[row].astype(np.int64)], 0.0)
            # cast on the host: a device-side cast is a program to build
            # for every new shape, a dozen blocks of them
            blocks.append(dict(
                start=jnp.asarray(starts[members].astype(np.int32)),
                size=jnp.asarray(sizes[members].astype(np.int32)),
                gain=jnp.asarray(gains.astype(np.float32)),
                inv_max=jnp.asarray(_inverse_max_dcg(
                    gains, valid, self.truncation).astype(np.float32))))
            shapes.append((len(members), int(g)))
            if row_slot is not None:
                slot = base + np.arange(valid.size).reshape(valid.shape)
                row_slot[row[valid]] = slot[valid]
                base += valid.size
        self.layout = (int(sizes[0]) if uniform else 0, tuple(shapes))
        self.groups = dict(
            blocks=tuple(blocks),
            row_slot=(None if row_slot is None
                      else jnp.asarray(row_slot.astype(np.int32))))
        t = self.truncation
        self.facts = {
            "rank_queries": int(len(sizes)),
            "rank_blocks": [list(s) for s in shapes],
            "rank_doc_slots": int(sum(q * g for q, g in shapes)),
            "rank_pair_slots": int(sum(q * min(t, g) * g
                                       for q, g in shapes)),
            "rank_pairs_visited": pairs_visited(sizes, t),
            "rank_truncation": t,
        }

    # -- device pairwise lambdas ----------------------------------------
    def grad_hess(self, pred, y, w, groups=None):
        """``groups``: the layout's tensors (``self.groups`` of the
        instance ``set_group`` prepared).  The round programs hand them in
        as operands; a caller that holds the prepared instance may leave
        them out."""
        groups = self.groups if groups is None else groups
        if groups is None or self.layout is None:
            raise ValueError(
                "lambdarank requires group information: pass group= to the "
                "training Dataset (lgb.Dataset(X, label=y, group=sizes))")
        uniform, shapes = self.layout
        blocks = groups["blocks"]
        n_pad = pred.shape[0]
        if uniform:
            (q, g), blk = shapes[0], blocks[0]
            scores = jnp.pad(pred[:q * uniform].reshape(q, uniform),
                             ((0, 0), (0, g - uniform)))
            g_q, h_q = self._block_lambdas(scores, blk)
            grad, hess = (jnp.pad(a[:, :uniform].reshape(-1),
                                  (0, n_pad - q * uniform))
                          for a in (g_q, h_q))
            return grad * w, hess * w
        # a window that starts at a query's first row never leaves the
        # table: the widest block's padding behind the last row
        padded = jnp.pad(pred, (0, max(g for _, g in shapes)))
        sums = []
        for (q, g), blk in zip(shapes, blocks):
            scores = jax.vmap(
                lambda st: lax.dynamic_slice(padded, (st,), (g,)))(
                    blk["start"])
            sums.append(self._block_lambdas(scores, blk))
        # back to the row axis: every row reads its own slot of the blocks
        # laid end to end (a gather: the chip runs it in half the time of
        # the scatter-add that writes the same numbers)
        zero = jnp.zeros(1, jnp.float32)
        grad, hess = (
            jnp.concatenate([a[k].reshape(-1) for a in sums] + [zero])[
                groups["row_slot"]] for k in (0, 1))
        return grad * w, hess * w

    def _block_lambdas(self, scores, blk):
        """Gradients and hessians ``[Q_b, G_b]`` in slot order of one block
        of queries from their scores in slot order; padding slots (whatever
        score they hold) get exact zeros."""
        q, g = scores.shape
        t = min(self.truncation, g)
        size, gain, inv_max = blk["size"], blk["gain"], blk["inv_max"]
        col = lax.broadcasted_iota(jnp.int32, (q, g), 1)
        valid = col < size[:, None]
        # descending by score, ties in slot (= row) order, padding last;
        # 0 - s so that a score of -0.0 ties with +0.0
        key = jnp.where(valid, 0.0 - scores, jnp.inf)
        key_s, order, gain_s = lax.sort((key, col, gain), dimension=1,
                                        is_stable=True, num_keys=1)
        s_s = jnp.where(valid, 0.0 - key_s, 0.0)   # `valid` holds by rank too
        worst = jnp.min(jnp.where(valid, scores, jnp.inf), axis=1)
        differ = s_s[:, 0] != worst

        qc = max(1, min(q, _PAIR_CHUNK // (t * g)))
        n_chunks = -(-q // qc)

        def chunked(a):
            a = jnp.pad(
                a, ((0, n_chunks * qc - q),) + ((0, 0),) * (a.ndim - 1))
            return a.reshape((n_chunks, qc) + a.shape[1:])

        args = tuple(chunked(a) for a in (s_s, gain_s, size, inv_max, differ))
        if n_chunks == 1:
            g_s, h_s = self._pair_block(tuple(a[0] for a in args))
        else:
            g_s, h_s = lax.map(self._pair_block, args)
            g_s = g_s.reshape(-1, g)[:q]
            h_s = h_s.reshape(-1, g)[:q]
        # back from rank order to slot order
        _, g_q, h_q = lax.sort((order, g_s, h_s), dimension=1, num_keys=1)
        return g_q, h_q

    def _pair_block(self, args):
        """One chunk of queries in RANK order: ``[qc, G]`` scores and gains
        -> gradient and hessian sums ``[qc, G]`` by rank."""
        s, gn, size, inv_max, differ = args
        qc, g = s.shape
        t = min(self.truncation, g)
        sigma = jnp.float32(self.sigma)
        rank_i = lax.broadcasted_iota(jnp.int32, (1, t, g), 1)
        rank_j = lax.broadcasted_iota(jnp.int32, (1, t, g), 2)
        # LightGBM's loops: i inside the truncation level, j after i
        pair = (rank_j > rank_i) & (rank_j < size[:, None, None])
        disc = 1.0 / jnp.log2(2.0 + lax.iota(jnp.float32, g))
        d_gain = gn[:, :t, None] - gn[:, None, :]
        d_score = s[:, :t, None] - s[:, None, :]        # >= 0 where j > i
        delta = (jnp.abs(d_gain)
                 * jnp.abs(disc[None, :t, None] - disc[None, None, :])
                 * inv_max[:, None, None])              # dNDCG [qc, t, G]
        if self.norm:
            delta = jnp.where(differ[:, None, None],
                              delta / (0.01 + jnp.abs(d_score)), delta)
        # +1: rank i holds the better document, -1: rank j does
        hi = jnp.sign(d_gain)
        p = 1.0 / (1.0 + jnp.exp(sigma * hi * d_score))
        lam = jnp.where(pair, sigma * p * delta, 0.0)
        hes = jnp.where(pair, sigma * sigma * p * (1.0 - p) * delta, 0.0)
        push = hi * lam     # off the better one's gradient, onto the other
        tail = ((0, 0), (0, g - t))
        g_s = jnp.sum(push, axis=1) - jnp.pad(jnp.sum(push, axis=2), tail)
        h_s = jnp.sum(hes, axis=1) + jnp.pad(jnp.sum(hes, axis=2), tail)
        if self.norm:
            total = 2.0 * jnp.sum(lam, axis=(1, 2))
            scale = jnp.where(
                total > 0.0,
                jnp.log2(1.0 + total) / jnp.maximum(total, 1e-30), 1.0)
            g_s, h_s = g_s * scale[:, None], h_s * scale[:, None]
        return g_s, h_s


# ---------------------------------------------------------------------------
# NDCG@k / MAP@k evaluation
# ---------------------------------------------------------------------------

def ndcg_at_k(scores: jnp.ndarray, gains: jnp.ndarray, valid: jnp.ndarray,
              k: int) -> jnp.ndarray:
    """Mean NDCG@k over queries (queries with maxDCG@k == 0 count as 1,
    matching LightGBM's NDCGMetric convention). [Q, G] dense layout."""
    masked = jnp.where(valid, scores, -jnp.inf)
    order = jnp.argsort(-masked, axis=-1, stable=True)
    top = jnp.take_along_axis(gains, order[:, :k], axis=-1)
    topv = jnp.take_along_axis(valid, order[:, :k], axis=-1)
    disc = 1.0 / jnp.log2(2.0 + lax.iota(jnp.float32, min(
        k, gains.shape[-1])))
    dcg = jnp.sum(top * topv * disc[None, :], axis=-1)
    ideal = jnp.take_along_axis(
        gains, jnp.argsort(-jnp.where(valid, gains, -jnp.inf), axis=-1,
                           stable=True)[:, :k], axis=-1)
    idcg = jnp.sum(ideal * disc[None, :], axis=-1)
    return jnp.where(idcg > 0, dcg / jnp.maximum(idcg, 1e-20), 1.0)


@functools.lru_cache(maxsize=None)
def _ndcg_eval_fn(k: int):
    @jax.jit
    def fn(scores, gains, valid, qweight):
        per_q = ndcg_at_k(scores, gains, valid, k)
        return jnp.sum(per_q * qweight) / jnp.maximum(jnp.sum(qweight), 1e-12)

    return fn


def map_at_k(scores: jnp.ndarray, rel: jnp.ndarray, valid: jnp.ndarray,
             k: int) -> jnp.ndarray:
    """Per-query MAP@k (upstream ``rank_metric.hpp`` MapMetric semantics):
    binary relevance (label > 0), AP@k = sum over relevant hits in the top-k
    of hits_so_far/position, normalized by min(num_relevant, k); queries with
    no relevant docs count as 1 (same degenerate-query convention the NDCG
    metric uses). [Q, G] dense layout."""
    masked = jnp.where(valid, scores, -jnp.inf)
    order = jnp.argsort(-masked, axis=-1, stable=True)
    rel_sorted = jnp.take_along_axis(rel & valid, order, axis=-1)
    kk = min(k, rel.shape[-1])
    hits = jnp.cumsum(rel_sorted.astype(jnp.float32), axis=-1)[:, :kk]
    pos = 1.0 + lax.iota(jnp.float32, kk)
    acc = jnp.sum(jnp.where(rel_sorted[:, :kk], hits / pos, 0.0), axis=-1)
    npos = jnp.sum((rel & valid).astype(jnp.float32), axis=-1)
    denom = jnp.minimum(npos, float(kk))
    return jnp.where(npos > 0, acc / jnp.maximum(denom, 1.0), 1.0)


@functools.lru_cache(maxsize=None)
def _map_eval_fn(k: int):
    @jax.jit
    def fn(scores, rel, valid, qweight):
        per_q = map_at_k(scores, rel, valid, k)
        return jnp.sum(per_q * qweight) / jnp.maximum(jnp.sum(qweight), 1e-12)

    return fn


class RankEvalContext:
    """Per-dataset packed layout for ranking metrics, built once."""

    def __init__(self, group_sizes: np.ndarray, y_host: np.ndarray,
                 label_gain: Optional[List[float]]):
        doc_idx, valid = _pack_groups(group_sizes)
        labels = np.zeros(doc_idx.shape)
        labels[valid] = y_host[doc_idx[valid]]
        table = _label_gain_table(label_gain, int(labels.max()))
        self.doc_idx = jnp.asarray(doc_idx)
        self.valid = jnp.asarray(valid)
        self.gains = jnp.asarray(np.where(valid, table[labels.astype(np.int64)],
                                          0.0), jnp.float32)
        # binary relevance for MAP: label > 0 (upstream MapMetric threshold)
        self.rel = jnp.asarray(np.where(valid, labels > 0, False))
        self.qweight = jnp.ones(doc_idx.shape[0], jnp.float32)

    def ndcg(self, pred_raw: jnp.ndarray, k: int) -> float:
        scores = pred_raw[self.doc_idx]
        return float(_ndcg_eval_fn(int(k))(scores, self.gains, self.valid,
                                           self.qweight))

    def map(self, pred_raw: jnp.ndarray, k: int) -> float:
        scores = pred_raw[self.doc_idx]
        return float(_map_eval_fn(int(k))(scores, self.rel, self.valid,
                                          self.qweight))


def eval_ranking(pred_raw, ds, eval_at: List[int],
                 label_gain: Optional[List[float]] = None,
                 metrics: Tuple[str, ...] = ("ndcg",)):
    """[(name, value, higher_better)] for ndcg@k / map@k over a grouped
    Dataset (upstream ``rank_metric.hpp`` NDCGMetric / MapMetric)."""
    ctx = getattr(ds, "_rank_eval_ctx", None)
    if ctx is None:
        gs = ds.get_group()
        if gs is None:
            raise ValueError(
                "ranking metrics require the Dataset to have group")
        ctx = RankEvalContext(gs, ds.get_label(), label_gain)
        ds._rank_eval_ctx = ctx
    out = []
    for m in metrics:
        if m == "ndcg":
            out.extend((f"ndcg@{k}", ctx.ndcg(pred_raw, k), True)
                       for k in eval_at)
        elif m == "map":
            out.extend((f"map@{k}", ctx.map(pred_raw, k), True)
                       for k in eval_at)
    return out


def get_ranking_metric(name: str, params=None) -> Metric:
    """Metric registry entry for ndcg — evaluated via the grouped path.

    The plain (pred, y, w) metric signature cannot express grouping, so
    Booster/_eval_on special-cases ranking metrics through
    :func:`eval_ranking`; this stub keeps the registry lookup coherent
    (name + higher_better) for callers that only inspect metadata.
    """
    if name not in ("ndcg", "map"):
        raise ValueError(f"Unknown ranking metric: {name}")

    def _needs_group(*_a, **_k):
        raise ValueError(
            f"{name} must be evaluated with group information "
            "(use Booster.eval_valid / lgb.cv with a grouped Dataset)")

    return Metric(name, True, _needs_group)
