"""MSLR-shaped inputs of the ranking cells, from a table seed: the same
seed gives the same queries, rows and labels.

``mslr_like`` is ``lightgbm_tpu.utils.datasets.make_mslr_like`` copied, as
``datagen.higgs_like`` is ``make_higgs_like`` (the benchmark imports
nothing of the program to make its inputs).  The real MSLR-WEB30K cannot
be fetched, so every shape below is ASSUMED from what is recalled of it
(the configuration's ``assumed`` says so): what the chip sees of the real
table is its size, its query lengths, how many distinct values a column
has, and labels that a tree can learn.

* **Queries**: ``queries`` lengths from a lognormal law (sigma 0.75, so
  the median is about three quarters of the mean and the longest about
  ten times it), clipped to ``docs_lo .. docs_hi`` and adjusted so that
  they sum to exactly ``rows`` with both ends of the range present;
  rows are group-contiguous, in the queries' order.
* **Columns**, in MSLR's mix: the first ninth integer counts with fewer
  than 16 distinct values (covered query terms), the next quarter integer
  counts with fewer than 255 (term frequencies, stream lengths), a few
  constant within nine queries of ten (IDF-like: a property of the
  query), the rest heavy-tailed continuous (lognormal: TF-IDF, BM25,
  language-model scores).  ``datagen.reorder_columns`` then puts them in
  the order of ``--seed``, as in the other cells.
* **Labels** 0-4 at 52 / 32 / 13 / 2 / 1 %: cuts of a latent relevance
  that depends on the columns' underlying normals nonlinearly, plus an
  offset of the query and noise, so that lambdarank has something to
  learn and NDCG@10 rises over the first rounds.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

_SIGNAL_STREAM = 987654321
LABEL_SHARES = (0.52, 0.32, 0.13, 0.02, 0.01)
BLOCK = 1 << 16           # rows a block: a stream and a thread's task each
THREADS = 8


def query_sizes(rows: int, queries: int, rng, docs_lo: int = 1,
                docs_hi: int = 1251) -> np.ndarray:
    """``queries`` lengths in ``docs_lo .. docs_hi`` that sum to ``rows``,
    both ends present (where ``queries`` >= 2 and the sum allows it)."""
    if not queries * docs_lo <= rows <= queries * docs_hi:
        raise ValueError(f"{queries} queries of {docs_lo}..{docs_hi} "
                         f"documents cannot hold {rows} rows")
    mean = rows / queries
    sizes = np.exp(rng.normal(np.log(mean) - 0.75 ** 2 / 2, 0.75, queries))
    sizes = np.clip(np.rint(sizes), docs_lo, docs_hi).astype(np.int64)
    ends = 2 if queries >= 2 and (
        docs_hi + docs_lo * (queries - 1) <= rows
        <= docs_lo + docs_hi * (queries - 1)) else 0
    if ends:
        sizes[:2] = docs_hi, docs_lo            # moved to seeded places below
    for _ in range(64):
        off = rows - int(sizes.sum())
        if off == 0:
            break
        step = 1 if off > 0 else -1
        free = np.flatnonzero((sizes + step >= docs_lo)
                              & (sizes + step <= docs_hi))
        free = free[free >= ends]
        take = rng.choice(free, size=min(abs(off), len(free)), replace=False)
        sizes[take] += step
    if int(sizes.sum()) != rows:
        raise ValueError("query sizes did not reach the row total")
    return sizes[rng.permutation(queries)]


def mslr_like(rows: int = 2_270_296, features: int = 136,
              queries: int = 18_919, seed: int = 0, docs_lo: int = 1,
              docs_hi: int = 1251):
    """``(X float32 [rows, features], y float32 [rows] in 0..4, sizes int64
    [queries])``: an MSLR-WEB30K-shaped learning-to-rank table."""
    rng = np.random.default_rng(int(seed))
    sizes = query_sizes(rows, queries, rng, docs_lo, docs_hi)
    query = np.repeat(np.arange(queries, dtype=np.int32), sizes)
    sig = np.random.default_rng(_SIGNAL_STREAM)
    w = (sig.normal(0, 1, features) / np.sqrt(features)).astype(np.float32)
    spread = sig.uniform(0.5, 1.5, features).astype(np.float32)
    tiny = max(1, features // 9)                 # fewer than 16 values
    count = tiny + features // 4                 # fewer than 255 values
    const = count + max(1, features // 23)       # the query's own
    offset = rng.normal(0, 0.7, queries).astype(np.float32)
    per_query = rng.standard_normal((queries, const - count),
                                    dtype=np.float32)
    loose = (rng.random(queries) < 0.1).astype(np.float32)   # 1 in 10 varies
    X = np.empty((rows, features), np.float32)
    latent = np.empty(rows, np.float32)

    def fill(i):
        """One block of rows from a stream of its own: the columns'
        normals, the latent relevance they give, then each kind of column
        from its normals, in place."""
        own = np.random.default_rng([int(seed), i])
        part, q = X[i:i + BLOCK], query[i:i + BLOCK]
        own.standard_normal(out=part, dtype=np.float32)
        latent[i:i + BLOCK] = (
            part @ w
            + np.float32(0.8) * np.sin(part[:, 0] * 2) * part[:, 1 % features]
            + np.float32(0.5) * (part[:, 2 % features] ** 2 - 1) + offset[q]
            + np.float32(0.5) * own.standard_normal(len(q), dtype=np.float32))
        a = part[:, :tiny]
        np.clip(np.floor(np.exp(np.float32(0.8) * a, out=a), out=a),
                0, 11, out=a)
        a = part[:, tiny:count]
        np.clip(np.floor(np.exp(np.float32(1.2) * a + np.float32(2.5),
                                out=a), out=a), 0, 250, out=a)
        a = part[:, count:const]
        a *= np.float32(0.1) * loose[q][:, None]
        a += per_query[q]
        a = part[:, const:]
        np.exp(a * spread[const:], out=a)

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(fill, range(0, rows, BLOCK)))
    cuts = np.quantile(latent, np.cumsum(LABEL_SHARES)[:-1])
    y = np.searchsorted(cuts, latent).astype(np.float32)
    return X, y, sizes
