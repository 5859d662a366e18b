"""Round-4 regression tests: ADVICE r3 fixes + lazy tree store."""

import numpy as np
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb


def test_approx_top_mask_outlier_robust():
    """ADVICE r3 (medium): a single huge |gradient| must not collapse the
    bucketed threshold to first-k-by-index — iterative refinement keeps
    the selection a true top-k up to final-bucket tie-breaking."""
    from lightgbm_tpu.ops.sampling import approx_top_mask

    rng = np.random.default_rng(0)
    n, k = 100_000, 20_000
    x = np.abs(rng.normal(0, 0.01, n)).astype(np.float32)
    x[12345] = 50.0                       # the outlier
    sel = np.asarray(approx_top_mask(jnp.asarray(x),
                                     jnp.ones(n, bool), k))
    true_top = np.zeros(n, bool)
    true_top[np.argsort(-x)[:k]] = True
    assert sel.sum() == k
    assert sel[12345]
    assert (sel & true_top).sum() / k > 0.97


def test_approx_top_mask_exact_count_edges():
    from lightgbm_tpu.ops.sampling import approx_top_mask

    ones = jnp.ones(1000, jnp.float32)
    v = jnp.ones(1000, bool)
    assert np.asarray(approx_top_mask(ones, v, 100)).sum() == 100  # ties
    assert np.asarray(approx_top_mask(ones, v, 5000)).sum() == 1000
    assert np.asarray(approx_top_mask(ones, v, 0)).sum() == 0
    half = jnp.asarray(np.arange(1000) % 2 == 0)
    s = np.asarray(approx_top_mask(ones, half, 300))
    assert s.sum() == 300 and not (s & ~np.asarray(half)).any()


@pytest.fixture(scope="module")
def small_reg():
    rng = np.random.default_rng(3)
    X = rng.normal(size=(1500, 6)).astype(np.float32)
    y = (X[:, 0] + np.sin(X[:, 1]) + 0.1 * rng.normal(size=1500)
         ).astype(np.float32)
    return X, y


def test_tree_store_segments_match_host_loop(small_reg):
    """Fused segments stored stacked must predict identically to the
    per-round host loop, including staged prefixes and save/load."""
    X, y = small_reg
    ds = lgb.Dataset(X, label=y)
    ds.construct()
    p = {"objective": "regression", "num_leaves": 15, "verbosity": -1,
         "fused_segment_rounds": 7}
    b = lgb.train(p, ds, num_boost_round=20)     # 7+7+6 stacked segments
    ref = lgb.Booster(p, ds)
    for _ in range(20):
        ref.update()                             # per-round singles
    np.testing.assert_allclose(b.predict(X), ref.predict(X),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(b.predict(X, num_iteration=9),
                               ref.predict(X, num_iteration=9),
                               rtol=1e-5, atol=1e-5)
    # per-tree views materialize lazily and round-trip through save/load
    b2 = lgb.Booster(model_str=b.model_to_string())
    np.testing.assert_allclose(b2.predict(X), b.predict(X),
                               rtol=1e-6, atol=1e-6)


def test_batched_fused_kernel_parity():
    """The element-grid batched histogram kernel (wide-segment vmap path)
    must match the per-element reference, including feature blocking."""
    from lightgbm_tpu.ops.histogram import compute_histograms
    from lightgbm_tpu.ops.histogram_pallas import hist_fused_pallas_batched

    rng = np.random.default_rng(7)
    n, F, B, K, S, E = 3000, 6, 32, 24, 3, 4
    bins = jnp.asarray(rng.integers(0, B, (n, F)).astype(np.uint8))
    stats = jnp.asarray(rng.normal(0, 1, (E, n, S)).astype(np.float32))
    seg = jnp.asarray(rng.integers(-1, K + 1, (E, n)).astype(np.int32))
    got = hist_fused_pallas_batched(bins, stats, seg, K, B,
                                    hist_dtype="f32")
    assert got.shape == (E, K, F, B, S)
    for ei in range(E):
        ref = compute_histograms(bins, stats[ei], seg[ei], K, B,
                                 impl="jnp")
        np.testing.assert_allclose(np.asarray(got[ei]), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
    # feature-blocked path (52*256*126*4 = 6.7 MB accumulator exceeds the
    # 6 MB VMEM budget, forcing the f_blk-halving + pad/trim branch)
    F2, K2 = 52, 42
    bins2 = jnp.asarray(rng.integers(0, 256, (1024, F2)).astype(np.uint8))
    stats2 = jnp.asarray(rng.normal(0, 1, (2, 1024, 3)).astype(np.float32))
    seg2 = jnp.asarray(rng.integers(0, K2, (2, 1024)).astype(np.int32))
    g2 = hist_fused_pallas_batched(bins2, stats2, seg2, K2, 256,
                                   hist_dtype="f32")
    for ei in range(2):
        ref = compute_histograms(bins2, stats2[ei], seg2[ei], K2, 256,
                                 impl="jnp")
        np.testing.assert_allclose(np.asarray(g2[ei]), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


def test_parity_preset_expands_to_quality_config():
    import warnings as _w

    from lightgbm_tpu.config import parse_params

    p = parse_params({"objective": "binary", "preset": "parity"})
    # TRUE-STRICT order + EXACT f32 histograms on the XLA path (strict on
    # jnp is clean on this worker — the intermittent fault follows
    # strict+pallas; PERF_HISTORY.md "AUC parity — NORTH STAR MET")
    assert p.grow_policy == "leafwise"
    assert p.extra.get("hist_dtype") == "f32"
    assert p.extra.get("hist_impl") == "jnp"
    # explicit user keys still win over the preset
    p2 = parse_params({"objective": "binary", "preset": "parity",
                       "grow_policy": "frontier"})
    assert p2.grow_policy == "frontier"
    # unknown preset names warn instead of vanishing silently
    with _w.catch_warnings(record=True) as rec:
        _w.simplefilter("always")
        parse_params({"objective": "binary", "preset": "partiy"})
    assert any("preset" in str(r.message) for r in rec)


def test_fused_cv_multiclass_matches_host_loop():
    """VERDICT r3 #8: the fused configs-x-folds program now vmaps the
    class axis; its cv curve must track the host loop.  (Tolerance is
    looser than the single-output test: the fused path uses global class
    priors as init while the host loop re-derives them per fold — same
    known init difference the l2 fused test carries.)"""
    rng = np.random.default_rng(7)
    n = 1200
    X = rng.normal(size=(n, 6)).astype(np.float32)
    logits = np.stack([X[:, 0] + 0.5 * X[:, 1], X[:, 2] - X[:, 0],
                       0.8 * X[:, 3]], 1)
    y = logits.argmax(1).astype(np.float32)
    ds = lgb.Dataset(X, label=y)
    ds.construct()
    p = {"objective": "multiclass", "num_class": 3, "num_leaves": 15,
         "verbosity": -1, "learning_rate": 0.1}
    from lightgbm_tpu.config import parse_params
    from lightgbm_tpu.models.fused import fused_cv_eligible
    assert fused_cv_eligible(parse_params(p), None, None, ds)
    fused = lgb.cv(p, ds, num_boost_round=30, nfold=3, stratified=False,
                   early_stopping_rounds=5, seed=11)
    # eval_train_metric forces the host loop without changing training
    host = lgb.cv(p, ds, num_boost_round=30, nfold=3, stratified=False,
                  early_stopping_rounds=5, seed=11, eval_train_metric=True)
    fm = np.asarray(fused["valid multi_logloss-mean"])
    hm = np.asarray(host["valid multi_logloss-mean"])
    k = min(len(fm), len(hm))
    np.testing.assert_allclose(fm[:k], hm[:k], rtol=3e-2, atol=1e-3)
    assert fused.best_score == pytest.approx(host.best_score, rel=2e-2)


def test_tree_store_mutation_paths(small_reg):
    """pop / setitem / mixed update() + update_many on the lazy store."""
    X, y = small_reg
    ds = lgb.Dataset(X, label=y)
    ds.construct()
    p = {"objective": "regression", "num_leaves": 15, "verbosity": -1,
         "fused_segment_rounds": 5}
    b = lgb.Booster(p, ds)
    b.update_many(10)
    b.update()                                   # single after segments
    assert b.num_trees() == 11
    b.rollback_one_iter()                        # pop
    assert b.num_trees() == 10
    before = b.predict(X)
    t3 = b.trees[3]                              # materialize mid-segment
    b.trees[3] = t3                              # setitem round-trip
    np.testing.assert_allclose(b.predict(X), before, rtol=0, atol=0)
    leaves = b.predict(X[:8], pred_leaf=True)
    assert leaves.shape == (8, 10)
