"""Inputs from ``--seed``: the same seed gives the same inputs.

``higgs_like`` is ``lightgbm_tpu.utils.datasets.make_higgs_like`` copied
(the labelling function with its own fixed stream), drawing float32
directly so that 10.5M x 28 values take seconds, not tens of them.

``table`` gives every seed the SAME work in another order: the rows and
labels of the configuration's own ``table_seed``, with the columns in an
order drawn from ``--seed``.  A tree's histograms are taken column by
column, so the trees, and with them the passes a round runs, are the
table's and not the seed's (PR 36: across tables the rate spread by 1–5 %,
on one table it repeats to 0.0–0.4 %)."""

from __future__ import annotations

import numpy as np

_SIGNAL_STREAM = 987654321


def higgs_like(rows: int, features: int, seed: int):
    """Binary task of Higgs shape, class balance about 0.5: float32
    ``X[rows, features]`` and float32 labels in {0, 1}."""
    rng = np.random.default_rng(int(seed))
    X = rng.standard_normal((rows, features), dtype=np.float32)
    w = np.random.default_rng(_SIGNAL_STREAM).normal(
        0, 1, features).astype(np.float32)
    logits = (X @ w) * np.float32(0.6) \
        + np.float32(0.8) * np.sin(X[:, 0] * 2) * X[:, 1] \
        + np.float32(0.5) * (X[:, 2] ** 2 - 1)
    p = 1 / (1 + np.exp(-logits))
    y = (rng.random(rows, dtype=np.float32) < p).astype(np.float32)
    return X, y


def reorder_columns(X, seed: int) -> None:
    """The columns of ``X`` in an order drawn from ``seed``.  In place, a
    block of rows at a time: a second table would cost its page faults,
    seconds of set-up at 400,000 x 2,000."""
    rows, features = X.shape
    order = np.random.default_rng(int(seed)).permutation(features)
    block = max(1, (1 << 20) // features)
    buf = np.empty((block, features), X.dtype)
    for i in range(0, rows, block):
        part = X[i:i + block]
        part[...] = np.take(part, order, axis=1, out=buf[:len(part)],
                            mode="clip")


def table(rows: int, features: int, table_seed: int, seed: int):
    """``higgs_like(rows, features, table_seed)`` with its columns in the
    order of ``seed`` (the labels are made before the columns move)."""
    X, y = higgs_like(rows, features, table_seed)
    reorder_columns(X, seed)
    return X, y
