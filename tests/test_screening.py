"""Gain-informed feature screening tests (ISSUE 20): EMA-FS.

Three contracts:

* EXACTNESS OFF — ``feature_screen="off"`` (and the degenerate
  ``keep_ratio=1.0`` screener) routes through the unified mask layer
  with a ``None``/all-ones base, so whole trained models are
  BIT-IDENTICAL (``np.array_equal``) to the pre-screening paths —
  strict and wave growers, in-memory and streamed.
* COMPACTION PARITY — with screening ON, the in-memory and streamed
  paths plan the same active sets and grow the same trees (histogram
  ``row_chunk`` pinned to the block size, the r7 accumulation-order
  rule), and winner ids are always GLOBAL feature ids.
* FRESHNESS — refresh rounds run the full feature set and observe
  gains, so a feature whose gain only emerges late re-enters the
  active set; without refreshes it provably never does.
"""

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.dataset import Dataset
from lightgbm_tpu.faults import ScreenScopeError


def _problem(n, f, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(0, 1, (n, f)).astype(np.float32)
    w = rng.normal(0, 1, f)
    logits = (X @ w) * 0.7 + 0.6 * np.sin(X[:, 0] * 2)
    y = (rng.random(n) < 1 / (1 + np.exp(-logits))).astype(np.float32)
    return X, y


def _trees_equal(a, b):
    for ta, tb in zip(a.trees, b.trees):
        for field in ("split_feature", "split_bin", "left", "right",
                      "leaf_value", "is_leaf"):
            if not np.array_equal(np.asarray(getattr(ta, field)),
                                  np.asarray(getattr(tb, field))):
                return False
    return len(a.trees) == len(b.trees)


def _train(X, y, extra, rounds=4):
    p = dict(objective="binary", num_leaves=15, learning_rate=0.1,
             max_bin=63, min_data_in_leaf=5, verbose=-1, seed=7)
    p.update(extra)
    bst = lgb.Booster(p, Dataset(X, label=y, params=dict(p)))
    for _ in range(rounds):
        bst.update()
    return bst


def _split_feature_set(bst):
    out = set()
    for t in bst.trees:
        sf = np.asarray(t.split_feature)
        out |= set(sf[sf >= 0].tolist())
    return out


# ---------------------------------------------------------------------------
# exactness off: the unified mask layer is bit-identical when not screening
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("grower", [{"wave_width": 1}, {"wave_width": 4}],
                         ids=["strict", "wave"])
@pytest.mark.parametrize("n,f", [(900, 5), (700, 13), (640, 136)])
def test_screen_off_bit_identical_strict_and_wave(grower, n, f):
    X, y = _problem(n, f)
    base = _train(X, y, grower)
    off = _train(X, y, dict(grower, feature_screen="off"))
    # keep_ratio=1.0 keeps every feature: the screener exists but can
    # never compact, so the full pipeline (plan/observe included) must
    # still be bit-identical to the unscreened program
    keep_all = _train(X, y, dict(grower, feature_screen="ema",
                                 screen_keep_ratio=1.0))
    for other in (off, keep_all):
        assert _trees_equal(base, other)
        assert np.array_equal(np.asarray(base._pred_train),
                              np.asarray(other._pred_train))


def test_screen_off_bit_identical_streamed():
    n, f, block_rows = 1800, 13, 512
    X, y = _problem(n, f)
    blocks = [(X[lo:lo + block_rows], y[lo:lo + block_rows])
              for lo in range(0, n, block_rows)]
    trained = []
    for extra in ({}, {"feature_screen": "off"}):
        p = dict(objective="binary", num_leaves=15, learning_rate=0.1,
                 max_bin=63, min_data_in_leaf=5, verbose=-1, seed=7,
                 stream_block_rows=block_rows, **extra)
        bst = lgb.Booster(p, Dataset.from_blocks(blocks,
                                                 params=dict(p)))
        for _ in range(4):
            bst.update()
        trained.append(bst)
    assert trained[0]._streamed and trained[1]._streamed
    assert _trees_equal(trained[0], trained[1])


# ---------------------------------------------------------------------------
# compaction parity: screened in-memory == screened streamed, global ids
# ---------------------------------------------------------------------------

SCREEN = dict(feature_screen="ema", screen_keep_ratio=0.3,
              screen_refresh_rounds=4, screen_ema_decay=0.9)


@pytest.mark.parametrize("grower", [{"wave_width": 1}, {"wave_width": 4}],
                         ids=["strict", "wave"])
def test_screened_in_memory_matches_streamed(grower):
    n, f, block_rows, rounds = 1800, 13, 512, 6
    X, y = _problem(n, f)
    base = dict(objective="binary", num_leaves=15, learning_rate=0.1,
                max_bin=63, min_data_in_leaf=5, verbose=-1, seed=7,
                **SCREEN, **grower)
    # accumulation-order rule (r7): pin the in-memory histogram chunking
    # to the streamed block size so partial sums add in the same order
    p_mem = dict(base, row_chunk=block_rows)
    p_st = dict(base, stream_block_rows=block_rows)
    mem = lgb.Booster(p_mem, Dataset(X, label=y, params=dict(p_mem)))
    blocks = [(X[lo:lo + block_rows], y[lo:lo + block_rows])
              for lo in range(0, n, block_rows)]
    st = lgb.Booster(p_st, Dataset.from_blocks(blocks, params=dict(p_st)))
    for _ in range(rounds):
        mem.update()
        st.update()
    assert st._streamed and mem._screener is not None
    assert _trees_equal(mem, st)
    assert np.array_equal(np.asarray(mem._pred_train),
                          np.asarray(st._pred_train))
    # compaction actually happened (keep=4 of 13) AND winners are global
    assert mem._screener.keep == 4
    for bst in (mem, st):
        feats = _split_feature_set(bst)
        assert feats and all(0 <= fid < f for fid in feats)


def test_screened_stream_moves_fewer_bytes():
    n, f, block_rows = 2048, 20, 512
    X, y = _problem(n, f, seed=3)
    blocks = [(X[lo:lo + block_rows], y[lo:lo + block_rows])
              for lo in range(0, n, block_rows)]
    streamed_bytes = []
    for extra in ({}, dict(SCREEN, screen_keep_ratio=0.25,
                           screen_refresh_rounds=3)):
        p = dict(objective="binary", num_leaves=15, learning_rate=0.1,
                 max_bin=63, min_data_in_leaf=5, verbose=-1, seed=7,
                 stream_block_rows=block_rows, **extra)
        bst = lgb.Booster(p, Dataset.from_blocks(blocks,
                                                 params=dict(p)))
        for _ in range(6):
            bst.update()
        streamed_bytes.append(bst.train_set.block_store.bytes_streamed)
    full, screened = streamed_bytes
    # ColumnViewStore slices host-side BEFORE device_put: 4 of 6 rounds
    # stream 5/20 columns, so PCIe bytes must drop well below full width
    assert screened < 0.6 * full, (screened, full)


# ---------------------------------------------------------------------------
# composition: screening x feature_fraction x bynode x EFB, one mask path
# ---------------------------------------------------------------------------

def _onehot_problem(n=2000, k=40, seed=5):
    rng = np.random.default_rng(seed)
    cat = rng.integers(0, k, n)
    onehot = np.zeros((n, k), np.float32)
    onehot[np.arange(n), cat] = 1.0
    dense = rng.normal(size=(n, 3)).astype(np.float32)
    X = np.concatenate([dense, onehot], axis=1)
    effect = rng.normal(0, 1.0, k)
    y = (dense[:, 0] + effect[cat]
         + rng.normal(0, 0.1, n)).astype(np.float32)
    return X, y


def test_screening_composes_with_ff_bynode_and_efb():
    X, y = _onehot_problem()
    ff = dict(feature_fraction=0.8, feature_fraction_bynode=0.7,
              objective="regression")
    on = _train(X, y, dict(ff, **dict(SCREEN, screen_refresh_rounds=3)),
                rounds=6)
    ds = on.train_set
    fb = int(ds.num_feature_)              # post-EFB training width
    assert fb < X.shape[1]                 # bundling really engaged
    # the screener's features, like every split's, are the original ones
    assert on._screener is not None and on._screener.keep < X.shape[1]
    feats = _split_feature_set(on)
    assert feats and all(0 <= fid < X.shape[1] for fid in feats)
    # no double-masking: the degenerate keeper composes with BOTH
    # fraction draws bit-identically to the unscreened program (the
    # base-mask routing must not perturb either RNG stream)
    plain = _train(X, y, ff, rounds=6)
    keep_all = _train(X, y,
                      dict(ff, **dict(SCREEN, screen_keep_ratio=1.0)),
                      rounds=6)
    assert _trees_equal(plain, keep_all)


# ---------------------------------------------------------------------------
# freshness: refresh rounds rediscover late-gain features
# ---------------------------------------------------------------------------

def _late_gain_problem(n=2000, f=6, seed=11):
    """Feature 0 carries a big step, feature 5 a smaller one: stumps fit
    feature 0 first, and only once its residual has shrunk below the
    feature-5 step does feature 5's gain emerge — strictly later than
    round 0's EWMA snapshot."""
    rng = np.random.default_rng(seed)
    X = rng.normal(0, 1, (n, f)).astype(np.float32)
    y = (2.0 * (X[:, 0] > 0) + 0.6 * (X[:, 5] > 0)
         + rng.normal(0, 0.01, n)).astype(np.float32)
    return X, y


def test_refresh_rediscovers_late_gain_feature():
    X, y = _late_gain_problem()
    base = dict(objective="regression", num_leaves=2, learning_rate=0.5,
                max_bin=63, min_data_in_leaf=5, verbose=-1, seed=7,
                feature_screen="ema", screen_keep_ratio=0.15,  # keep=1
                screen_ema_decay=0.9)
    fresh = _train(X, y, dict(base, screen_refresh_rounds=3), rounds=12)
    assert fresh._screener.keep == 1
    # refreshes at rounds 3/6/9 rerun the full set; by then feature 0's
    # residual step (2.0 * 0.5^k) is below feature 5's 0.6 -> rediscovered
    assert 5 in _split_feature_set(fresh)
    # guard: with refreshes effectively disabled, the screened rounds
    # only ever see the round-0 winner — feature 5 can never re-enter
    stale = _train(X, y, dict(base, screen_refresh_rounds=1000),
                   rounds=12)
    assert 5 not in _split_feature_set(stale)
    assert 0 in _split_feature_set(stale)


# ---------------------------------------------------------------------------
# unit: the global-id remap and the scope fences
# ---------------------------------------------------------------------------

def test_remap_split_features_passes_sentinels_through():
    import collections

    import jax.numpy as jnp

    from lightgbm_tpu.models.feature_mask import remap_split_features

    T = collections.namedtuple("T", ["split_feature"])
    tree = T(split_feature=jnp.asarray([2, -1, 0, 1, -1], jnp.int32))
    out = remap_split_features(tree, np.asarray([4, 9, 130], np.int32))
    assert np.array_equal(np.asarray(out.split_feature),
                          [130, -1, 4, 9, -1])


@pytest.mark.parametrize("extra,key", [
    (dict(objective="multiclass", num_class=3), "num_class"),
    (dict(linear_tree=True), "linear_tree"),
    (dict(boosting="dart"), "boosting"),
    (dict(extra_trees=True), "extra_trees"),
    (dict(monotone_constraints=[1, 0, 0, 0, 0]), "monotone_constraints"),
    (dict(interaction_constraints=[[0, 1], [2, 3, 4]]),
     "interaction_constraints"),
    (dict(tree_learner="feature"), "tree_learner"),
])
def test_screen_scope_fences(extra, key):
    X, y = _problem(300, 5, seed=2)
    if extra.get("objective") == "multiclass":
        y = (np.abs(X[:, 0]) * 2).astype(np.int32) % 3
    p = dict(objective="binary", num_leaves=7, verbose=-1,
             feature_screen="ema")
    p.update(extra)
    with pytest.raises(ScreenScopeError) as ei:
        lgb.Booster(p, Dataset(X, label=y, params=dict(p)))
    assert ei.value.key == key


def test_screen_budget_lines_all_green():
    from lightgbm_tpu.analysis.budgets import (check_screen_budgets,
                                               feature_screen_time_model)

    res = check_screen_budgets()
    assert res and all(r["ok"] for r in res), res
    t = feature_screen_time_model()
    assert t["speedup_x"] >= 1.5 and t["f_active"] == 34.0
    # the exactness guards: both degenerate operating points collapse
    # to a 1x factor — the model never charges an unearned discount
    assert feature_screen_time_model(keep_ratio=1.0)["speedup_x"] == 1.0
    assert feature_screen_time_model(
        refresh_rounds=1)["avg_round_factor"] == 1.0
