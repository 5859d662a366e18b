"""Inputs of the sparse wide cell from ``--seed``: a table of the shape of
Bosch Production Line Performance's ``train_numeric`` (Kaggle, 2016).

A row is a part; a column is one measurement at one station of a
production line: 968 columns on 52 stations of 4 lines.  The stations of a
line fall into groups of ALTERNATIVES (``LAYOUT``): a part that enters a
group passes exactly one of its stations, and one that skips the group
passes none, so the columns of two stations of one group are never both
measured on a row.  A column is NaN where its station was not passed, and
now and then where it was (its own light missingness).  About half the
columns take few distinct values (a count, a flag, a coarse reading),
the rest are fine-grained readings.  1 part in 172 fails (the published
table: 6,879 of 1,183,747), by a fixed function of the route it took and
a few stations' readings.

What is recalled of the source and what is chosen here is listed under
``assumed`` in the configuration; nothing here imports the program.  The
layout is fixed (its own stream, ``LAYOUT_STREAM``): the table seed draws
the parts and their readings in blocks of ``BLOCK_ROWS`` rows, each from a
stream of its own (made on a few threads; the table is the same), and
the labels from one more; a table of fewer columns is the first columns
of the wider one.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

LAYOUT_STREAM = 968052
LABEL_STREAM = 57_6000_172
POSITIVE_SHARE = 6879 / 1183747          # 0.58 %
BLOCK_ROWS = 1 << 16

# (line, stations in the group, share of parts that enter the group):
# 24, 2, 3 and 23 stations on the four lines, as in the published table
GROUPS = (
    (0, 6, 1.00), (0, 6, 0.99), (0, 6, 0.98), (0, 6, 0.97),
    (1, 2, 0.50),
    (2, 3, 0.55),
    (3, 6, 1.00), (3, 6, 0.99), (3, 6, 0.98), (3, 5, 0.97),
)
# Dirichlet concentration of a group's shares: most parts pass one or two
# of its stations, the others see few
STATION_SKEW = 0.3
# the least share of a group's parts a station takes: two stations of two
# groups then meet on dozens of the 50,000 rows the bundling reads, so
# the bundles it forms (of stations of one group) meet nowhere else
SHARE_FLOOR = 0.04
THREADS = 8               # blocks made at once (none changes the table)


def _layout():
    """Stations, columns and how each column reads, from a fixed stream."""
    rng = np.random.default_rng(LAYOUT_STREAM)
    stations = []                     # (group, share of the group's parts)
    for g, (_, k, _) in enumerate(GROUPS):
        shares = rng.dirichlet(np.full(k, STATION_SKEW))
        shares = SHARE_FLOOR + (1 - k * SHARE_FLOOR) * shares
        stations += [(g, float(s)) for s in shares]
    n_st = len(stations)
    # 968 columns over the stations, 5 at least a station
    weights = rng.dirichlet(np.full(n_st, 3.0))
    per = 5 + np.floor(weights * (968 - 5 * n_st)).astype(int)
    per[np.argsort(-weights)[:968 - per.sum()]] += 1
    col_station = np.repeat(np.arange(n_st), per)
    n_cols = len(col_station)
    coarse = rng.random(n_cols) < 0.6
    cardinality = np.where(coarse, rng.integers(1, 33, n_cols), 0)
    step = np.round(rng.uniform(0.002, 0.05, n_cols), 3)
    center = np.round(rng.normal(0.0, 0.2, n_cols), 3)
    spread = rng.uniform(0.05, 0.3, n_cols)
    missing = rng.uniform(0.0, 0.03, n_cols)
    return dict(stations=stations, col_station=col_station,
                cardinality=cardinality, step=step, center=center,
                spread=spread, missing=missing)


LAYOUT = _layout()
STATIONS = len(LAYOUT["stations"])
FEATURES = len(LAYOUT["col_station"])


def _route(rng, rows: int) -> np.ndarray:
    """``int16[rows, groups]``: the station each part passed in each group
    (-1: it skipped the group)."""
    out = np.full((rows, len(GROUPS)), -1, np.int16)
    first = 0
    for g, (_, k, enter) in enumerate(GROUPS):
        shares = np.array([s for gg, s in LAYOUT["stations"] if gg == g])
        cum = np.cumsum(shares / shares.sum())
        pick = np.minimum(np.searchsorted(cum, rng.random(rows)), k - 1)
        entered = rng.random(rows) < enter
        out[:, g] = np.where(entered, first + pick, -1)
        first += k
    return out


def _block(seed: int, b: int, rows: int, features: int):
    """One block's readings ``X[rows, features]`` and its route."""
    rng = np.random.default_rng([int(seed), b])
    route = _route(rng, rows)
    visited = np.zeros((rows, STATIONS), bool)
    for g in range(len(GROUPS)):
        on = route[:, g] >= 0
        visited[np.flatnonzero(on), route[on, g]] = True
    lay = LAYOUT
    XT = np.full((features, rows), np.nan, np.float32)
    passed = [np.flatnonzero(visited[:, s]) for s in range(STATIONS)]
    for c in range(features):
        f = c % FEATURES
        at = passed[lay["col_station"][f]]
        at = at[rng.random(len(at)) >= lay["missing"][f]]
        k = lay["cardinality"][f]
        if k:
            v = lay["center"][f] + lay["step"][f] * rng.integers(0, k,
                                                                 len(at))
        else:
            v = np.round(rng.normal(lay["center"][f], lay["spread"][f],
                                    len(at)), 3)
        XT[c, at] = v
    return np.ascontiguousarray(XT.T), route


def _risk(X: np.ndarray, route: np.ndarray) -> np.ndarray:
    """The failure logit before its intercept: the route (a few stations
    fail more parts than their alternatives) and a few readings."""
    st = np.array([0, 7, 25, 31, 44, 50]) % STATIONS
    passed = np.zeros(len(X))
    for g in range(len(GROUPS)):
        passed += np.isin(route[:, g], st) * (1.0 + 0.3 * g)
    cols = [3, 140, 512, 801]
    read = sum(np.nan_to_num(np.abs(X[:, c] - LAYOUT["center"][c])
                             / LAYOUT["spread"][c], nan=0.0) > 1.5
               for c in cols if c < X.shape[1])
    return 0.45 * passed + 1.2 * read


def bosch_like(rows: int, features: int, table_seed: int):
    """float32 ``X[rows, features]`` (NaN where a station was not passed)
    and float32 labels in {0, 1}, ``POSITIVE_SHARE`` of them 1."""
    if features > FEATURES:
        raise ValueError(f"the layout has {FEATURES} columns")
    X = np.empty((rows, features), np.float32)
    risk = np.empty(rows)

    def fill(b):
        at = b * BLOCK_ROWS
        part, route = _block(table_seed, b, min(BLOCK_ROWS, rows - at),
                             features)
        X[at:at + len(part)] = part
        risk[at:at + len(part)] = _risk(part, route)

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(fill, range(-(-rows // BLOCK_ROWS))))
    # the intercept that fails POSITIVE_SHARE of the parts in expectation
    lo, hi = -30.0, 10.0
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        if np.mean(1.0 / (1.0 + np.exp(-(risk + mid)))) > POSITIVE_SHARE:
            hi = mid
        else:
            lo = mid
    p = 1.0 / (1.0 + np.exp(-(risk + lo)))
    u = np.random.default_rng([int(table_seed), LABEL_STREAM]).random(rows)
    return X, (u < p).astype(np.float32)
