"""Exclusive Feature Bundling (VERDICT r1 item 7; SURVEY.md §2C EFB row)."""

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.dataset import FeatureBundler


@pytest.fixture(scope="module")
def onehot_data():
    """200 one-hot columns from a 200-category variable + 3 dense features:
    the one-hots are perfectly mutually exclusive -> EFB's home turf."""
    rng = np.random.default_rng(5)
    n, k = 6000, 200
    cat = rng.integers(0, k, n)
    onehot = np.zeros((n, k), np.float32)
    onehot[np.arange(n), cat] = 1.0
    dense = rng.normal(size=(n, 3)).astype(np.float32)
    X = np.concatenate([dense, onehot], axis=1)
    effect = rng.normal(0, 1.0, k)
    y = (dense[:, 0] + effect[cat] + rng.normal(0, 0.1, n)).astype(np.float32)
    return X, y


def test_bundles_collapse_onehot_columns(onehot_data):
    X, y = onehot_data
    ds = lgb.Dataset(X, label=y)
    ds.construct()
    bundler = ds.bin_mapper.bundler
    assert bundler is not None, "mutually exclusive one-hots must bundle"
    # 200 one-hot features collapse into very few bundle columns
    assert ds.num_feature_ < 20, ds.num_feature_
    assert ds.num_feature() == X.shape[1]  # user-facing count unchanged
    # every original feature appears in exactly one group
    members = sorted(f for g in bundler.groups for f in g)
    assert members == list(range(X.shape[1]))


def test_bundled_training_matches_unbundled_quality(onehot_data):
    X, y = onehot_data
    params = {"objective": "regression", "num_leaves": 63,
              "learning_rate": 0.1, "verbosity": -1, "min_data_in_leaf": 5}
    b_on = lgb.train(dict(params), lgb.Dataset(X, label=y),
                     num_boost_round=60)
    b_off = lgb.train(dict(params, enable_bundle=False),
                      lgb.Dataset(X, label=y), num_boost_round=60)
    r_on = float(np.sqrt(np.mean((b_on.predict(X) - y) ** 2)))
    r_off = float(np.sqrt(np.mean((b_off.predict(X) - y) ** 2)))
    assert r_on <= r_off * 1.1, (r_on, r_off)
    # quality must be real: beat the label standard deviation comfortably
    assert r_on < float(np.std(y)) * 0.6


def test_bundled_predict_consistency_and_importance(onehot_data):
    X, y = onehot_data
    params = {"objective": "regression", "num_leaves": 31, "verbosity": -1,
              "min_data_in_leaf": 5}
    b = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=20)
    # predict on fresh rows codes them unbundled: the trees' own space
    pred_a = b.predict(X[:100])
    pred_b = b.predict(X[:100])
    np.testing.assert_array_equal(pred_a, pred_b)
    imp = b.feature_importance()
    assert imp.shape == (X.shape[1],)  # original feature space
    assert imp.sum() > 0
    # dense informative feature 0 must receive importance
    assert imp[0] > 0


def test_bundler_save_load_roundtrip(onehot_data, tmp_path):
    X, y = onehot_data
    params = {"objective": "regression", "num_leaves": 31, "verbosity": -1}
    b = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=10)
    path = str(tmp_path / "m.json")
    b.save_model(path)
    b2 = lgb.Booster(model_file=path)
    np.testing.assert_allclose(b.predict(X[:200]), b2.predict(X[:200]),
                               rtol=1e-6, atol=1e-7)


def test_conflict_rate_zero_keeps_conflicting_features_apart():
    rng = np.random.default_rng(3)
    n = 4000
    # two sparse features that are non-default TOGETHER on 5% of rows
    a = np.where(rng.random(n) < 0.1, rng.normal(2, 1, n), 0.0)
    both = rng.random(n) < 0.05
    b = np.where(both, rng.normal(-2, 1, n), 0.0)
    a = np.where(both, rng.normal(2, 1, n), a)
    dense = rng.normal(size=(n, 2))
    X = np.column_stack([dense, a, b]).astype(np.float32)
    codes = None
    ds = lgb.Dataset(X, label=rng.normal(size=n).astype(np.float32))
    ds.construct()
    bundler = ds.bin_mapper.bundler
    if bundler is not None:
        for g in bundler.groups:
            assert not ({2, 3} <= set(g)), \
                "conflicting features must not share a bundle at rate 0"


def test_every_dumped_split_is_one_feature_at_a_raw_threshold(onehot_data):
    """Bundles are a training-time layout: a split names one original
    feature and a raw-value threshold, and the saved model predicts with
    no bundler."""
    X, y = onehot_data
    b = lgb.train({"objective": "regression", "num_leaves": 15,
                   "verbosity": -1, "min_data_in_leaf": 5},
                  lgb.Dataset(X, label=y), num_boost_round=5)
    assert b.train_set.bin_mapper.bundler is not None

    def splits(node):
        if "leaf_value" in node:
            return []
        return ([node] + splits(node["left_child"])
                + splits(node["right_child"]))

    nodes = [s for t in b.dump_model()["tree_info"]
             for s in splits(t["tree_structure"])]
    assert nodes and not any("bundled_bin_threshold" in s for s in nodes)
    assert all(isinstance(s["threshold"], float) for s in nodes)
    assert {s["split_feature"] for s in nodes} - {0, 1, 2}  # one-hots split
    b2 = lgb.Booster(model_str=b.model_to_string())
    b2._bin_mapper.bundler = None
    np.testing.assert_allclose(b2.predict(X[:300]), b.predict(X[:300]),
                               rtol=1e-6, atol=1e-7)
