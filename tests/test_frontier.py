"""Frontier (wave) grower: parity with the strict grower + semantics.

The frontier grower (models/tree.py grow_tree_frontier) is the large-data
fast path: up to wave_width splits per histogram pass, sibling histograms
derived by subtraction (LightGBM's ConstructHistogram trick — SURVEY.md
§3.1).  With wave_width=1 its split order equals strict best-first, so we
check exact structural parity there; for wider waves we check predictive
parity and invariants.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.models.spec import WaveSchedule
from lightgbm_tpu.models.tree import grow_tree, grow_tree_frontier
from lightgbm_tpu.ops.predict import predict_tree_binned
from lightgbm_tpu.ops.split import SplitContext


def make_ctx(min_data=1.0):
    z = jnp.float32
    return SplitContext(lambda_l1=z(0.0), lambda_l2=z(0.0),
                        min_data_in_leaf=z(min_data),
                        min_sum_hessian=z(0.0), min_gain_to_split=z(0.0))


def _stats(y):
    n = len(y)
    return jnp.stack([jnp.asarray(-y, jnp.float32),
                      jnp.ones(n, jnp.float32),
                      jnp.ones(n, jnp.float32)], axis=-1)


def _problem(n=3000, f=5, bins_per=32, seed=0):
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, bins_per, (n, f)).astype(np.uint8)
    y = (1.5 * bins[:, 0] - 0.3 * (bins[:, 1] > 12) * bins[:, 2]
         + 0.05 * rng.normal(0, 1, n)).astype(np.float32)
    y = (y - y.mean()) / y.std()
    return bins, y


def test_wave1_matches_strict_structure():
    bins, y = _problem()
    fmask = jnp.ones(bins.shape[1], jnp.float32)
    t_strict, rl_strict = grow_tree(
        jnp.asarray(bins), _stats(y), fmask, make_ctx(), 15, 32, -1)
    t_wave, rl_wave, _ = grow_tree_frontier(
        jnp.asarray(bins), _stats(y), fmask, make_ctx(), 15, 32, -1,
        wave=WaveSchedule(1, "half"))
    assert int(t_wave.num_leaves) == int(t_strict.num_leaves)
    np.testing.assert_array_equal(np.asarray(t_wave.split_feature),
                                  np.asarray(t_strict.split_feature))
    np.testing.assert_array_equal(np.asarray(t_wave.split_bin),
                                  np.asarray(t_strict.split_bin))
    np.testing.assert_array_equal(np.asarray(rl_wave), np.asarray(rl_strict))
    np.testing.assert_allclose(np.asarray(t_wave.leaf_value),
                               np.asarray(t_strict.leaf_value), atol=1e-4)


@pytest.mark.parametrize("width", [4, 42])
def test_wide_wave_predictive_parity(width):
    bins, y = _problem(seed=1)
    fmask = jnp.ones(bins.shape[1], jnp.float32)
    t_strict, rl_s = grow_tree(
        jnp.asarray(bins), _stats(y), fmask, make_ctx(min_data=20.0),
        31, 32, -1)
    t_wave, rl_w, _ = grow_tree_frontier(
        jnp.asarray(bins), _stats(y), fmask, make_ctx(min_data=20.0),
        31, 32, -1, wave=WaveSchedule(width, "half"))
    assert int(t_wave.num_leaves) <= 31
    mse_s = float(np.mean((np.asarray(t_strict.leaf_value)[rl_s] - y) ** 2))
    mse_w = float(np.mean((np.asarray(t_wave.leaf_value)[rl_w] - y) ** 2))
    # one tree's fit quality must match strict within a whisker
    assert mse_w <= mse_s * 1.1 + 1e-6


def test_wave_traversal_matches_row_leaf():
    bins, y = _problem(seed=2)
    fmask = jnp.ones(bins.shape[1], jnp.float32)
    tree, row_leaf, _ = grow_tree_frontier(
        jnp.asarray(bins), _stats(y), fmask, make_ctx(), 31, 32, -1,
        wave=WaveSchedule(8, "half"))
    vals_train = np.asarray(tree.leaf_value)[np.asarray(row_leaf)]
    vals_traverse = np.asarray(
        predict_tree_binned(tree, jnp.asarray(bins), max_depth_cap=31))
    np.testing.assert_allclose(vals_train, vals_traverse, atol=1e-6)


def test_wave_min_data_and_budget():
    bins, y = _problem(seed=3)
    fmask = jnp.ones(bins.shape[1], jnp.float32)
    tree, row_leaf, _ = grow_tree_frontier(
        jnp.asarray(bins), _stats(y), fmask, make_ctx(min_data=100.0),
        16, 32, -1, wave=WaveSchedule(8, "half"))
    leaves = np.asarray(row_leaf)
    is_leaf = np.asarray(tree.is_leaf)
    assert int(tree.num_leaves) <= 16
    for node in np.unique(leaves):
        assert is_leaf[node]
        assert (leaves == node).sum() >= 100


def test_wave_max_depth():
    bins, y = _problem(seed=4)
    fmask = jnp.ones(bins.shape[1], jnp.float32)
    tree, _, _ = grow_tree_frontier(
        jnp.asarray(bins), _stats(y), fmask, make_ctx(), 31, 32,
        max_depth=2, wave=WaveSchedule(8, "half"))
    assert int(tree.num_leaves) <= 4


def test_frontier_policy_end_to_end_quality(small_regression):
    """Full train() with grow_policy=frontier lands near the strict model."""
    X, y = small_regression
    params = dict(objective="regression", learning_rate=0.1, num_leaves=31,
                  min_data_in_leaf=20, verbosity=-1)
    ds = lgb.Dataset(X, label=y)
    b_strict = lgb.train({**params, "grow_policy": "leafwise"}, ds,
                         num_boost_round=50)
    b_wave = lgb.train({**params, "grow_policy": "frontier"},
                       lgb.Dataset(X, label=y), num_boost_round=50)
    rmse_s = float(np.sqrt(np.mean((b_strict.predict(X) - y) ** 2)))
    rmse_w = float(np.sqrt(np.mean((b_wave.predict(X) - y) ** 2)))
    assert rmse_w <= rmse_s * 1.05 + 1e-6


def test_frontier_deterministic(small_regression):
    X, y = small_regression
    params = dict(objective="regression", num_leaves=31, seed=7,
                  grow_policy="frontier", bagging_fraction=0.8,
                  bagging_freq=1, feature_fraction=0.8, verbosity=-1)
    p1 = lgb.train(params, lgb.Dataset(X, label=y), 20).predict(X)
    p2 = lgb.train(params, lgb.Dataset(X, label=y), 20).predict(X)
    np.testing.assert_array_equal(p1, p2)


def test_fused_goss_matches_host_loop():
    """update_many's scanned GOSS path == per-round host GOSS updates."""
    import numpy as np
    import lightgbm_tpu as lgb

    rng = np.random.default_rng(0)
    n = 4000
    X = rng.normal(size=(n, 5)).astype(np.float32)
    y = (X[:, 0] * 2 + np.sin(X[:, 1] * 3)
         + rng.normal(0, 0.1, n)).astype(np.float32)
    params = {"boosting": "goss", "objective": "regression",
              "num_leaves": 15, "learning_rate": 0.2, "verbosity": -1,
              "top_rate": 0.3, "other_rate": 0.2}
    host = lgb.train(dict(params), lgb.Dataset(X, label=y),
                     num_boost_round=10, callbacks=[lambda env: None])
    fused = lgb.train(dict(params), lgb.Dataset(X, label=y),
                      num_boost_round=10)
    for th, tf in zip(host.trees, fused.trees):
        np.testing.assert_array_equal(np.asarray(th.split_feature),
                                      np.asarray(tf.split_feature))
    np.testing.assert_allclose(host.predict(X), fused.predict(X),
                               rtol=1e-5, atol=1e-6)
