"""Inputs of the categorical cell from ``--seed``: a table of the shape of
the Allstate Claim Prediction Challenge's ``train_set`` (Kaggle, 2012),
given to LightGBM as LightGBM advises, its categorical columns as
``categorical_feature``.

A row is one vehicle of a household in one calendar year; its 32 feature
columns are the Kaggle table's, in its order (``COLUMNS``).  Sixteen are
numeric or ordinal and sixteen categorical (``CATEGORICAL``): the blinded
vehicle hierarchy (make -> model -> submodel, about 75 / 1,300 / 2,700
levels), twelve household categories of 2 to 10 levels, some of them
often missing, and one non-vehicle category of 15 levels.  A category is
coded as its level's number (float32), a missing one as NaN.  Popularity
in the hierarchy is Zipf-like at each level, so the 254 most common
submodels cover about two thirds of the rows and the rest share the
overflow bin.  0.73 % of the rows have a claim, by a fixed logistic
function in which the categorical effects weigh more than the numeric ones.

What is recalled of the source and what is chosen here is listed under
``assumed`` in the configuration; nothing here imports the program.  The
layout (the hierarchy, the levels and every effect) is fixed (its own
stream, ``LAYOUT_STREAM``): the table seed draws the rows in blocks of
``BLOCK_ROWS`` rows, each from a stream of its own (made on a few threads;
the table is the same), and the labels from one more.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

LAYOUT_STREAM = 2012_0713
LABEL_STREAM = 73_0000_13
POSITIVE_SHARE = 0.0073
BLOCK_ROWS = 1 << 17
THREADS = 8               # blocks made at once (none changes the table)

MAKES, MODELS, SUBMODELS = 75, 1300, 2700
# household categories: levels, and the share of rows where it is missing
CAT_LEVELS = {"Cat1": (10, 0.002), "Cat2": (3, 0.37), "Cat3": (6, 0.0003),
              "Cat4": (3, 0.43), "Cat5": (3, 0.43), "Cat6": (6, 0.002),
              "Cat7": (4, 0.55), "Cat8": (3, 0.0003), "Cat9": (2, 0.0),
              "Cat10": (3, 0.0003), "Cat11": (6, 0.002),
              "Cat12": (6, 0.002)}
NVCAT_LEVELS = 15
COLUMNS = (["Vehicle", "Calendar_Year", "Model_Year", "Blind_Make",
            "Blind_Model", "Blind_Submodel"] + list(CAT_LEVELS)
           + ["OrdCat"] + [f"Var{i}" for i in range(1, 9)] + ["NVCat"]
           + [f"NVVar{i}" for i in range(1, 5)])
CATEGORICAL = tuple(i for i, c in enumerate(COLUMNS)
                    if c.startswith(("Blind_", "Cat")) or c == "NVCat")
FEATURES = len(COLUMNS)


def _zipf(rng, k: int, s: float) -> np.ndarray:
    """Popularity of ``k`` levels, Zipf-like with exponent ``s``, in a
    random order."""
    w = 1.0 / np.arange(1, k + 1) ** s
    return rng.permutation(w / w.sum())


def _split(rng, total: int, parts: int, weight: np.ndarray) -> np.ndarray:
    """``total`` children over ``parts`` parents, one at least each, the
    rest by ``weight``."""
    extra = rng.multinomial(total - parts, weight)
    return 1 + extra


def _layout():
    rng = np.random.default_rng(LAYOUT_STREAM)
    make_p = _zipf(rng, MAKES, 1.4)
    models_of = _split(rng, MODELS, MAKES, make_p)
    model_make = np.repeat(np.arange(MAKES), models_of)
    model_p = np.concatenate([make_p[m] * _zipf(rng, k, 1.6)
                              for m, k in enumerate(models_of)])
    subs_of = _split(rng, SUBMODELS, MODELS, model_p / model_p.sum())
    sub_model = np.repeat(np.arange(MODELS), subs_of)
    sub_p = np.concatenate([model_p[m] * _zipf(rng, k, 1.6)
                            for m, k in enumerate(subs_of)])
    sub_p /= sub_p.sum()
    # a vehicle's own numbers: model year and Var1-Var8 follow the submodel
    sub_year = 2005 - np.minimum(rng.geometric(0.13, SUBMODELS), 24)
    sub_vars = rng.normal(0.0, 1.0, (SUBMODELS, 8))
    cats = {c: (_zipf(rng, k, 0.8), miss) for c, (k, miss) in
            CAT_LEVELS.items()}
    nvcat_p = _zipf(rng, NVCAT_LEVELS, 1.2)
    # the claim's logit: categorical effects (the hierarchy, each household
    # category, NVCat) and numeric ones (Var1-Var8, NVVar1-4, the vehicle's
    # age and number)
    effects = dict(
        make=rng.normal(0.0, 0.35, MAKES),
        model=rng.normal(0.0, 0.30, MODELS),
        sub=rng.normal(0.0, 0.45, SUBMODELS),
        cats={c: rng.normal(0.0, 0.30, k + 1) for c, (k, _) in
              CAT_LEVELS.items()},
        nvcat=rng.normal(0.0, 0.40, NVCAT_LEVELS),
        vars=rng.normal(0.0, 0.12, 8),
        nvvars=rng.normal(0.0, 0.20, 4))
    return dict(model_make=model_make, sub_model=sub_model,
                sub_cdf=np.cumsum(sub_p), sub_year=sub_year,
                sub_vars=sub_vars, cats=cats, nvcat_cdf=np.cumsum(nvcat_p),
                effects=effects)


LAYOUT = _layout()


def _draw(rng, cdf: np.ndarray, rows: int) -> np.ndarray:
    return np.minimum(np.searchsorted(cdf, rng.random(rows)), len(cdf) - 1)


def _block(seed: int, b: int, rows: int):
    """One block's rows ``X[rows, 32]`` and its claim logit before the
    intercept."""
    rng = np.random.default_rng([int(seed), b])
    lay, eff = LAYOUT, LAYOUT["effects"]
    X = np.empty((rows, FEATURES), np.float32)
    sub = _draw(rng, lay["sub_cdf"], rows)
    model = lay["sub_model"][sub]
    make = lay["model_make"][model]
    vehicle = np.minimum(rng.geometric(0.45, rows), 24)
    year = 2005 + _draw(rng, np.array([0.30, 0.65, 1.0]), rows)
    model_year = np.minimum(lay["sub_year"][sub]
                            + rng.integers(-1, 2, rows), year)
    X[:, 0], X[:, 1], X[:, 2] = vehicle, year, model_year
    X[:, 3], X[:, 4], X[:, 5] = make, model, sub
    logit = (eff["make"][make] + eff["model"][model] + eff["sub"][sub])
    for j, (c, (p, miss)) in enumerate(lay["cats"].items()):
        level = _draw(rng, np.cumsum(p), rows)
        gone = rng.random(rows) < miss
        X[:, 6 + j] = np.where(gone, np.nan, level)
        logit += eff["cats"][c][np.where(gone, len(p), level)]
    ordcat = 1 + _draw(rng, np.cumsum([.05, .2, .25, .2, .15, .1, .05]),
                       rows)
    X[:, 18] = np.where(rng.random(rows) < 0.001, np.nan, ordcat)
    vars_ = lay["sub_vars"][sub] + rng.normal(0.0, 0.25, (rows, 8))
    X[:, 19:27] = vars_
    nvcat = _draw(rng, lay["nvcat_cdf"], rows)
    X[:, 27] = nvcat
    nvvars = np.where(rng.random((rows, 4)) < 0.7, -0.23,
                      -0.23 + rng.exponential(1.0, (rows, 4)))
    X[:, 28:32] = nvvars
    logit += (eff["nvcat"][nvcat] + vars_ @ eff["vars"]
              + (nvvars + 0.23) @ eff["nvvars"]
              + 0.04 * (2007 - model_year.astype(np.float64))
              + 0.15 * (vehicle > 2))
    return X, logit


def allstate_like(rows: int, table_seed: int):
    """float32 ``X[rows, 32]`` (the categorical columns ``CATEGORICAL``:
    level numbers, NaN where missing) and float32 labels in {0, 1},
    ``POSITIVE_SHARE`` of them 1."""
    X = np.empty((rows, FEATURES), np.float32)
    logit = np.empty(rows)

    def fill(b):
        at = b * BLOCK_ROWS
        part, lg = _block(table_seed, b, min(BLOCK_ROWS, rows - at))
        X[at:at + len(part)] = part
        logit[at:at + len(part)] = lg

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(fill, range(-(-rows // BLOCK_ROWS))))
    # the intercept that gives POSITIVE_SHARE of the rows a claim in
    # expectation, set on every eighth row
    some = logit[::8]
    lo, hi = -30.0, 10.0
    for _ in range(50):
        mid = 0.5 * (lo + hi)
        if np.mean(1.0 / (1.0 + np.exp(-(some + mid)))) > POSITIVE_SHARE:
            hi = mid
        else:
            lo = mid
    p = 1.0 / (1.0 + np.exp(-(logit + lo)))
    u = np.random.default_rng([int(table_seed), LABEL_STREAM]).random(rows)
    return X, (u < p).astype(np.float32)


def categorical_after(order: np.ndarray) -> list:
    """The categorical columns of a table whose columns were put in
    ``order`` (new column ``j`` is old column ``order[j]``)."""
    return [j for j, c in enumerate(order) if c in CATEGORICAL]
