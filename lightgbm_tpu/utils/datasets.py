"""Deterministic synthetic datasets for tests, examples and benchmarks.

No network egress is available and the reference's real data (ggplot2
`diamonds`, Higgs-11M) cannot be fetched, so we synthesize structurally
similar datasets (SURVEY.md §4: tolerance bands, not bit-parity):

* ``make_synthetic_diamonds`` — mimics the reference workload's shape
  (r/gridsearchCV.R:5-23): ~53,940 rows, target ``log_price`` driven mostly
  by ``log_carat`` plus ordered-factor quality codes, mild noise.  Same
  feature names, same 85/15 Bernoulli split convention.
* ``make_higgs_like`` — binary classification with the Higgs shape
  (N rows × 28 continuous features) for throughput benchmarking
  (BASELINE.json north-star config).
* ``make_mslr_like`` — learning to rank with the MSLR-WEB30K shape
  (queries of 1 to 1,251 group-contiguous documents, 136 columns of
  counts and heavy-tailed scores, labels 0-4) for the ranking path.
* ``make_boosting_curve`` — the 1-D ``y = |x| + cos(x)`` synthetic from
  bagging_boosting.ipynb:67-74 (faithful port: n=1000, U(-4,4) grid,
  U(-.05,.05) noise).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Tuple

import numpy as np


def make_synthetic_diamonds(n: int = 53940, seed: int = 3928272):
    """Return (X df-like dict, y, feature_names) mirroring diamonds log-price.

    Columns: log_carat (continuous), cut/color/clarity (ordinal codes),
    depth, table (continuous).  log_price is a smooth nonlinear function of
    them plus Gaussian noise, calibrated so a linear fit leaves clearly more
    residual than a GBDT (the reference's glmnet-vs-lgb quality ladder).
    """
    rng = np.random.default_rng(seed)
    carat = np.exp(rng.normal(-0.4, 0.6, n)).clip(0.2, 5.1)
    log_carat = np.log(carat)
    cut = rng.integers(1, 6, n).astype(np.float64)       # 1..5 ordered
    color = rng.integers(1, 8, n).astype(np.float64)     # 1..7
    clarity = rng.integers(1, 9, n).astype(np.float64)   # 1..8
    depth = rng.normal(61.75, 1.4, n).clip(43, 79)
    table = rng.normal(57.5, 2.2, n).clip(43, 95)

    # price model: dominated by carat (elasticity ~1.7), modulated by quality
    # codes with strong nonlinearities and interactions a linear model cannot
    # catch — calibrated so linear RMSE ~0.15 vs GBDT ~0.095, the reference's
    # quality-ladder gap (glmnet 0.1456 vs lgb 0.0957).
    log_price = (
        6.8
        + 1.7 * log_carat
        + 0.06 * cut
        + 0.08 * color
        + 0.10 * clarity
        + 0.07 * clarity * log_carat                        # interaction
        + 0.18 * np.sin(2.6 * log_carat)                    # curvature
        + 0.12 * np.cos(1.9 * log_carat + 0.6 * clarity)    # mixed wiggle
        - 0.05 * np.abs(depth - 61.75) * (log_carat > 0)
        - 0.01 * np.abs(table - 57.0)
        + rng.normal(0.0, 0.085, n)
    )
    X = np.column_stack([log_carat, cut, color, clarity, depth, table])
    names = ["log_carat", "cut", "color", "clarity", "depth", "table"]
    return X, log_price, names


def train_test_split_bernoulli(n: int, p_train: float = 0.85,
                               seed: int = 3928272):
    """The reference's split: Bernoulli membership, not exact counts
    (r/gridsearchCV.R:21 ``sample(c(FALSE, TRUE), n, replace=TRUE,
    p=c(0.15, 0.85))``)."""
    rng = np.random.default_rng(seed)
    is_train = rng.random(n) < p_train
    return np.where(is_train)[0], np.where(~is_train)[0]


def make_higgs_like(n: int = 1_000_000, num_features: int = 28,
                    seed: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """Binary task with Higgs-like shape and ~0.5 class balance."""
    rng = np.random.default_rng(seed)
    X = rng.normal(0, 1, (n, num_features)).astype(np.float32)
    # the signal vector comes from its OWN fixed stream: with w drawn from
    # the (seed, n)-dependent stream, a validation set generated with a
    # different seed/size got a DIFFERENT labeling function and the AUC
    # ceiling collapsed to ~0.52 (round-2 bench measured exactly that)
    w = np.random.default_rng(987654321).normal(0, 1, num_features)
    logits = (X @ w) * 0.6 + 0.8 * np.sin(X[:, 0] * 2) * X[:, 1] \
        + 0.5 * (X[:, 2] ** 2 - 1)
    p = 1 / (1 + np.exp(-logits))
    y = (rng.random(n) < p).astype(np.float32)
    return X, y


def iter_higgs_like_blocks(n: int = 1_000_000, num_features: int = 28,
                           seed: int = 0, block_rows: int = 131_072):
    """Yield ``(X_block, y_block)`` pairs of the Higgs-like task without
    ever materializing the full matrix — the host-memory companion to
    ``Dataset.from_blocks``.

    Each block draws from its own ``default_rng((seed, b))`` stream, so
    block ``b`` is reproducible in isolation (a re-iterated generator
    yields identical blocks — ``from_blocks`` needs two passes).  The
    signal vector ``w`` comes from the same fixed stream as
    ``make_higgs_like``, so streamed and in-memory variants share the
    labeling FUNCTION, though not the row values: the per-block RNG
    streams necessarily differ from the single-stream draw.
    """
    w = np.random.default_rng(987654321).normal(0, 1, num_features)
    n_blocks = (n + block_rows - 1) // block_rows
    for b in range(n_blocks):
        nb = min(block_rows, n - b * block_rows)
        rng = np.random.default_rng((seed, b))
        X = rng.normal(0, 1, (nb, num_features)).astype(np.float32)
        logits = (X @ w) * 0.6 + 0.8 * np.sin(X[:, 0] * 2) * X[:, 1] \
            + 0.5 * (X[:, 2] ** 2 - 1)
        p = 1 / (1 + np.exp(-logits))
        y = (rng.random(nb) < p).astype(np.float32)
        yield X, y


_MSLR_LABEL_SHARES = (0.52, 0.32, 0.13, 0.02, 0.01)
_MSLR_BLOCK = 1 << 16    # rows a block: a stream and a thread's task each


def mslr_query_sizes(rows: int, queries: int, rng, docs_lo: int = 1,
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


def make_mslr_like(rows: int = 2_270_296, features: int = 136,
              queries: int = 18_919, seed: int = 0, docs_lo: int = 1,
              docs_hi: int = 1251):
    """``(X float32 [rows, features], y float32 [rows] in 0..4, sizes int64
    [queries])``: an MSLR-WEB30K-shaped learning-to-rank table (the real
    one cannot be fetched: the shapes are assumed from what is recalled of
    it).  Query lengths from a lognormal law clipped to ``docs_lo ..
    docs_hi`` and adjusted to sum to ``rows`` with both ends present, rows
    group-contiguous; a ninth of the columns integer counts with fewer
    than 16 distinct values, a quarter with fewer than 255, a few constant
    within nine queries of ten, the rest lognormal; labels at 52 / 32 / 13
    / 2 / 1 % from a latent relevance that depends on the columns'
    underlying normals nonlinearly, plus an offset of the query and noise.
    ``benchmark/datagen_rank.py`` holds the benchmark's own copy."""
    rng = np.random.default_rng(int(seed))
    sizes = mslr_query_sizes(rows, queries, rng, docs_lo, docs_hi)
    query = np.repeat(np.arange(queries, dtype=np.int32), sizes)
    sig = np.random.default_rng(987654321)
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
        part, q = X[i:i + _MSLR_BLOCK], query[i:i + _MSLR_BLOCK]
        own.standard_normal(out=part, dtype=np.float32)
        latent[i:i + _MSLR_BLOCK] = (
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

    with ThreadPoolExecutor(8) as pool:
        list(pool.map(fill, range(0, rows, _MSLR_BLOCK)))
    cuts = np.quantile(latent, np.cumsum(_MSLR_LABEL_SHARES)[:-1])
    y = np.searchsorted(cuts, latent).astype(np.float32)
    return X, y, sizes


def make_boosting_curve(n: int = 1000, seed: int = 8657):
    """bagging_boosting.ipynb:67-74 faithful port (numpy legacy RandomState
    to honor np.random.seed(8657) semantics)."""
    rs = np.random.RandomState(seed)
    x = rs.uniform(-4, 4, n)
    noise = rs.uniform(-0.05, 0.05, n)
    y = np.abs(x) + np.cos(x) + noise
    return x.reshape(-1, 1), y
