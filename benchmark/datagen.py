"""Inputs from ``--seed``: the same seed gives the same inputs.

``higgs_like`` is ``lightgbm_tpu.utils.datasets.make_higgs_like`` copied
(the labelling function with its own fixed stream), drawing float32
directly so that 10.5M x 28 values take seconds, not tens of them."""

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
