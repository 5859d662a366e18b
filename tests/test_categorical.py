"""Categorical k-vs-rest subset splits (VERDICT r1 item 8; SURVEY.md §7 M4)."""

import numpy as np
import pytest

import lightgbm_tpu as lgb


@pytest.fixture(scope="module")
def cat_data():
    """Target driven by an UNORDERED category effect: ordered-threshold
    splits need many cuts, one subset split separates it exactly."""
    rng = np.random.default_rng(17)
    n, k = 5000, 30
    cat = rng.integers(0, k, n)
    # alternating category effect: orderings by code are useless
    effect = np.where(cat % 3 == 0, 2.0, np.where(cat % 3 == 1, -2.0, 0.0))
    dense = rng.normal(size=(n, 2)).astype(np.float32)
    y = (effect + 0.3 * dense[:, 0] + rng.normal(0, 0.1, n)).astype(np.float32)
    X = np.column_stack([cat.astype(np.float32), dense])
    return X, y


def test_subset_splits_beat_threshold_splits(cat_data):
    X, y = cat_data
    params = {"objective": "regression", "num_leaves": 15,
              "learning_rate": 0.3, "verbosity": -1, "min_data_in_leaf": 5}
    b_cat = lgb.train(dict(params), lgb.Dataset(X, label=y,
                                                categorical_feature=[0]),
                      num_boost_round=10)
    b_ord = lgb.train(dict(params), lgb.Dataset(X, label=y),
                      num_boost_round=10)
    r_cat = float(np.sqrt(np.mean((b_cat.predict(X) - y) ** 2)))
    r_ord = float(np.sqrt(np.mean((b_ord.predict(X) - y) ** 2)))
    # a %3-pattern category effect is a nightmare for ordered thresholds
    assert r_cat < r_ord * 0.8, (r_cat, r_ord)
    # and it must be genuinely good in absolute terms
    assert r_cat < 0.5, r_cat
    # trees actually contain categorical split nodes
    assert any(bool(np.asarray(t.is_cat_split).any()) for t in b_cat.trees)


def test_categorical_save_load_roundtrip(cat_data, tmp_path):
    X, y = cat_data
    params = {"objective": "regression", "num_leaves": 15, "verbosity": -1,
              "min_data_in_leaf": 5}
    b = lgb.train(params, lgb.Dataset(X, label=y, categorical_feature=[0]),
                  num_boost_round=8)
    path = str(tmp_path / "cat.json")
    b.save_model(path)
    b2 = lgb.Booster(model_file=path)
    np.testing.assert_allclose(b.predict(X[:300]), b2.predict(X[:300]),
                               rtol=1e-6, atol=1e-7)


def test_unseen_category_goes_right(cat_data):
    X, y = cat_data
    params = {"objective": "regression", "num_leaves": 7, "verbosity": -1,
              "min_data_in_leaf": 5}
    b = lgb.train(params, lgb.Dataset(X, label=y, categorical_feature=[0]),
                  num_boost_round=5)
    Xq = X[:10].copy()
    Xq[:, 0] = 999.0  # never seen at fit time
    pred = b.predict(Xq)
    assert np.all(np.isfinite(pred))


def test_max_cat_threshold_limits_subset_size(cat_data):
    X, y = cat_data
    params = {"objective": "regression", "num_leaves": 15, "verbosity": -1,
              "min_data_in_leaf": 5, "max_cat_threshold": 2}
    b = lgb.train(params, lgb.Dataset(X, label=y, categorical_feature=[0]),
                  num_boost_round=5)
    for t in b.trees:
        icb = np.asarray(t.is_cat_split)
        cm = np.asarray(t.cat_mask)
        for i in np.flatnonzero(icb):
            assert cm[i].sum() <= 2, cm[i].sum()


def test_cv_with_categoricals_runs(cat_data):
    X, y = cat_data
    res = lgb.cv({"objective": "regression", "num_leaves": 15,
                  "verbosity": -1, "min_data_in_leaf": 5},
                 lgb.Dataset(X, label=y, categorical_feature=[0]),
                 num_boost_round=10, nfold=3, early_stopping_rounds=5,
                 stratified=False)
    assert res.best_iter >= 1


def test_frontier_grower_supports_categoricals(cat_data):
    """Wave growth with categorical subset splits: quality must match the
    strict grower's on the unordered-category task."""
    X, y = cat_data
    base = {"objective": "regression", "num_leaves": 31,
            "learning_rate": 0.3, "verbosity": -1, "min_data_in_leaf": 5}
    ds = lambda: lgb.Dataset(X, label=y, categorical_feature=[0])
    b_wave = lgb.train(dict(base, grow_policy="frontier", wave_width=8),
                       ds(), num_boost_round=10)
    b_strict = lgb.train(dict(base, grow_policy="leafwise"), ds(),
                         num_boost_round=10)
    r_wave = float(np.sqrt(np.mean((b_wave.predict(X) - y) ** 2)))
    r_strict = float(np.sqrt(np.mean((b_strict.predict(X) - y) ** 2)))
    assert r_wave < r_strict * 1.2, (r_wave, r_strict)
    assert any(bool(np.asarray(t.is_cat_split).any()) for t in b_wave.trees)


# -- LightGBM's categorical split rules, held to the plain reference ---------
#
# ``benchmark/reference/cat_check.best_cat_gain`` is LightGBM's
# FindBestThresholdCategorical written as its loops, in float64 over the raw
# codes; ``find_best_split`` has to find the same best gain on the
# histogram of the same rows, and each rule has to matter: the reference
# with the rule switched off finds another answer.

CAT_HYPER = {"lambda_l2": 0.0, "min_sum_hessian_in_leaf": 1e-3,
             "min_data_in_leaf": 1, "cat_smooth": 10.0, "cat_l2": 10.0,
             "max_cat_threshold": 32, "max_cat_to_onehot": 4,
             "min_data_per_group": 100}


def _scan(code, g, h, levels, hyper=CAT_HYPER, bins=64):
    """The program's best category set on one categorical column whose
    codes ``code`` hold ``levels`` kept categories and the overflow bin
    ``levels``: ``(gain, bins of the set)``."""
    import jax.numpy as jnp

    from lightgbm_tpu.models.tree import build_cat_info
    from lightgbm_tpu.ops.split import CatColumns, SplitContext, find_best_split

    planes = np.stack([np.bincount(code, weights=w, minlength=bins)
                       for w in (g, h, np.ones_like(g))])[:, None, :]
    key = (hyper["cat_smooth"], hyper["cat_l2"], hyper["max_cat_threshold"],
           hyper["max_cat_to_onehot"], float(hyper["min_data_per_group"]))
    cats = CatColumns(is_cat=jnp.ones(1, bool),
                      cat_bins=jnp.asarray([levels], jnp.int32))
    ctx = SplitContext(jnp.float32(0.0), jnp.float32(hyper["lambda_l2"]),
                       jnp.float32(hyper["min_data_in_leaf"]),
                       jnp.float32(hyper["min_sum_hessian_in_leaf"]),
                       jnp.float32(0.0))
    bs = find_best_split(jnp.asarray(planes, jnp.float32), ctx,
                         jnp.ones(1), jnp.bool_(True),
                         build_cat_info(key, cats), bins_minor=True)
    return float(bs.gain), set(np.flatnonzero(np.asarray(bs.cat_mask)))


def _reference(code, g, h, levels, hyper=CAT_HYPER):
    from benchmark.reference.cat_check import best_cat_gain

    return best_cat_gain(code, np.arange(levels), g, h, hyper, 1.0)


def _rows(counts, means, seed=0):
    """Rows of categories ``0..len(counts)-1``, ``counts[k]`` of category
    ``k`` with gradients about ``means[k]``, shuffled; unit hessians."""
    rng = np.random.default_rng(seed)
    code = np.repeat(np.arange(len(counts)), counts)
    g = np.repeat(np.asarray(means, np.float64), counts) \
        + rng.normal(0, 0.05, len(code))
    perm = rng.permutation(len(code))
    return code[perm], g[perm], np.ones(len(code))


@pytest.mark.parametrize("seed", range(6))
def test_category_scan_finds_the_references_best_gain(seed):
    """Random columns of 2 to 40 categories, some rarer than cat_smooth or
    min_data_per_group, the overflow bin holding a share of the rows."""
    rng = np.random.default_rng(100 + seed)
    levels = int(rng.integers(2, 41))
    counts = rng.integers(1, 400, levels + 1)
    counts[rng.random(levels + 1) < 0.2] = rng.integers(1, 12)
    code, g, h = _rows(counts, rng.normal(0, 1, levels + 1), seed)
    got, _ = _scan(code, g, h, levels)
    want = _reference(code, g, h, levels)
    assert np.isfinite(want)
    assert got == pytest.approx(want, rel=2e-4)


def test_few_categories_split_one_against_the_rest():
    """At max_cat_to_onehot bins or fewer (3 categories and the overflow:
    4), one category goes left alone, at lambda_l2, though two against
    two gains more."""
    code, g, h = _rows([300, 300, 300, 300], [-1.0, -1.0, 1.0, 1.0])
    got, left = _scan(code, g, h, 3)
    assert len(left) == 1 and 3 not in left
    assert got == pytest.approx(_reference(code, g, h, 3), rel=1e-5)
    more = dict(CAT_HYPER, max_cat_to_onehot=1)
    got_sorted, left_sorted = _scan(code, g, h, 3, more)
    assert left_sorted == {0, 1} and got_sorted > 2 * got
    assert got_sorted == pytest.approx(_reference(code, g, h, 3, more),
                                       rel=1e-5)


def test_min_data_per_group_keeps_a_small_category_from_going_alone():
    """A category of 60 rows, the strongest: alone it holds fewer than
    min_data_per_group rows, so the first set evaluated is it and its
    neighbour."""
    code, g, h = _rows([60, 500, 500, 500, 500], [-3.0, -0.5, 0.0, 0.5, 0.2])
    got, left = _scan(code, g, h, 4)
    assert got == pytest.approx(_reference(code, g, h, 4), rel=1e-5)
    assert left != {0}
    alone = dict(CAT_HYPER, min_data_per_group=1)
    assert _scan(code, g, h, 4, alone)[0] != pytest.approx(got, rel=1e-3)
    assert _reference(code, g, h, 4, alone) == pytest.approx(
        _scan(code, g, h, 4, alone)[0], rel=1e-5)


def test_cat_smooth_keeps_rare_categories_out_of_the_sorted_scan():
    """Two categories of 6 rows, under cat_smooth's 10, carry the most
    extreme gradients: they are in no set, and with cat_smooth 1 they
    are."""
    code, g, h = _rows([6, 6, 400, 400, 400, 400],
                       [-5.0, 5.0, -0.5, 0.0, 0.3, 0.6])
    got, left = _scan(code, g, h, 6)
    assert not {0, 1} & left
    assert got == pytest.approx(_reference(code, g, h, 6), rel=1e-5)
    loose = dict(CAT_HYPER, cat_smooth=1.0, min_data_per_group=1)
    got_loose, left_loose = _scan(code, g, h, 6, loose)
    assert {0, 1} & left_loose
    assert got_loose == pytest.approx(_reference(code, g, h, 6, loose),
                                      rel=1e-5)


def test_at_most_half_the_used_categories_go_left():
    """Five usable categories: a set holds at most (5 + 1) / 2 = 3, from
    either end of the order, though four of them against the fifth and
    the overflow bin gains more."""
    code, g, h = _rows([300] * 6, [-1.0, -1.0, -1.0, -1.0, 1.0, 1.0])
    got, left = _scan(code, g, h, 5)
    assert len(left) <= 3
    assert got == pytest.approx(_reference(code, g, h, 5), rel=1e-5)
    uncapped = dict(CAT_HYPER, max_cat_threshold=64)
    # the cap is (used + 1) / 2 whatever max_cat_threshold says
    assert _scan(code, g, h, 5, uncapped)[0] == pytest.approx(got)


# -- the partition-fused kernels route category sets -------------------------

@pytest.mark.parametrize("num_features,num_bins", [(6, 64), (60, 256)],
                         ids=["single_block", "multi_block"])
def test_fused_kernels_route_category_sets_as_xla_does(num_features,
                                                        num_bins):
    """Interpret mode: a wave grower whose partition-fused kernels route
    every split, category sets included, grows the trees (splits, sets,
    leaf values, counts) and the row-to-leaf map of the XLA route."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.models.spec import WaveSchedule
    from lightgbm_tpu.models.tree import build_cat_info, grow_tree_logged
    from lightgbm_tpu.ops.histogram_pallas import _vmem_blocking
    from lightgbm_tpu.ops.split import CatColumns, SplitContext
    from lightgbm_tpu.utils import profiling

    rng = np.random.default_rng(num_features)
    n = 4000
    cats = [0, 2, num_features - 1]
    nb = rng.integers(3, 60, num_features)
    codes = np.stack([rng.integers(0, b, n) for b in nb], 1)
    eff = rng.normal(0, 1, 64)
    g = (eff[codes[:, 0]] + 0.5 * eff[codes[:, 2]] * (codes[:, 1] > 20)
         + eff[codes[:, -1]] + rng.normal(0, .3, n)).astype(np.float32)
    stats = jnp.asarray(np.stack([g, np.ones(n, np.float32),
                                  np.ones(n, np.float32)], -1))
    bins = jnp.asarray(codes.astype(np.uint8))
    key = (10.0, 10.0, 32, 4, 100.0)
    is_cat = np.isin(np.arange(num_features), cats)
    columns = CatColumns(is_cat=jnp.asarray(is_cat),
                         cat_bins=jnp.asarray(np.where(is_cat, nb - 1, 0),
                                              jnp.int32))
    ctx = SplitContext(jnp.float32(0), jnp.float32(0), jnp.float32(20),
                       jnp.float32(1e-3), jnp.float32(0))
    wave = WaveSchedule(8, "greedy", narrow_width=4)
    assert (_vmem_blocking(num_features, num_bins, 24)[1] > 1) == (
        num_features == 60)

    def grow(fused):
        return jax.jit(lambda: grow_tree_logged(
            bins, stats, jnp.ones(num_features), ctx, 31, num_bins, 0,
            wave=wave, cat_info=build_cat_info(key, columns),
            hist_impl="pallas", hist_dtype="f32",
            fuse_partition=fused))()

    # the grower notes where it routes the sets as it is traced
    tf, rf, _ = grow(True)
    assert profiling.snapshot()["facts"]["train.cat_route"] == "fused"
    tx, rx, _ = grow(False)
    assert profiling.snapshot()["facts"]["train.cat_route"] == "xla"
    assert np.asarray(tf.is_cat_split).sum() >= 3
    assert np.array_equal(np.asarray(rf), np.asarray(rx))
    for field in ("split_feature", "split_bin", "left", "right", "is_leaf",
                  "is_cat_split", "cat_mask", "count", "leaf_value"):
        assert np.array_equal(np.asarray(getattr(tf, field)),
                              np.asarray(getattr(tx, field))), field


# -- codes, dump and the reference ------------------------------------------

def test_device_codes_of_categorical_columns_are_the_hosts(monkeypatch):
    """``device_bin_codes`` of categorical columns: kept categories, the
    rare ones past max_bin - 1 (overflow), NaN, values the fit never saw
    and non-integer category values, beside a numeric column."""
    from lightgbm_tpu import dataset as dataset_mod
    from lightgbm_tpu.dataset import (ROW_PAD_MULTIPLE, BinMapper,
                                      device_bin_codes)

    rng = np.random.default_rng(3)
    n = 3 * ROW_PAD_MULTIPLE + 17
    X = np.empty((n, 3), np.float32)
    X[:, 0] = rng.zipf(1.3, n) % 300                 # many levels: overflow
    X[:, 1] = rng.normal(size=n)
    X[:, 2] = rng.choice([-2.5, 0.0, 0.1, 7.0, 1e6], n)
    X[rng.random(n) < 0.05, 0] = np.nan
    X[rng.random(n) < 0.05, 2] = np.nan
    mapper = BinMapper.fit(X, max_bin=31, categorical=[0, 2])
    assert mapper.n_bins[0] == 31 and mapper.nan_bin[0] == -1
    Q = X.copy()
    Q[:40, 0] = [1e4, -1.0, -0.0, 0.5] * 10           # never seen at fit
    Q[40:50, 2] = -0.0                                # the category 0.0
    monkeypatch.setattr(dataset_mod, "CODE_BLOCK_VALUES",
                        ROW_PAD_MULTIPLE * 3)
    n_pad = -(-n // ROW_PAD_MULTIPLE) * ROW_PAD_MULTIPLE
    codes, _, _ = device_bin_codes(Q, mapper, n_pad)
    host = mapper.transform(Q)
    assert np.asarray(codes)[:n].tobytes() == host.tobytes()
    over = len(mapper.upper_bounds[0])
    assert (host[np.isnan(Q[:, 0]), 0] == over).all()
    assert (host[:40:4, 0] == over).all() and (host[40:50, 2] ==
                                               host[Q[:, 2] == 0.0, 2][0]).all()


@pytest.fixture(scope="module")
def raw_cat_booster():
    """Categorical columns of non-contiguous raw values (negative,
    fractional, large) with NaN, beside numeric ones."""
    rng = np.random.default_rng(8)
    n = 6000
    vals = np.array([-2.5, 3.0, 17.0, 250.0, 1e6, 0.75, 42.0, 9.0])
    a = rng.choice(vals, n)
    b = rng.integers(0, 12, n).astype(np.float64)
    b[rng.random(n) < 0.1] = np.nan
    x = rng.normal(size=n)
    effect = dict(zip(vals, rng.normal(0, 1.5, len(vals))))
    y = (np.vectorize(effect.get)(a) + np.where(np.nan_to_num(b) % 3 == 0,
                                                1.0, -0.5)
         + 0.3 * x + rng.normal(0, 0.2, n)).astype(np.float32)
    X = np.column_stack([a, x, b]).astype(np.float32)
    params = {"objective": "regression", "num_leaves": 15,
              "learning_rate": 0.2, "verbosity": -1, "min_data_in_leaf": 5,
              "lambda_l2": 0.0, "hist_dtype": "f32"}
    ds = lgb.Dataset(X, label=y, categorical_feature=[0, 2])
    booster = lgb.Booster(params, ds)
    scores = []
    for _ in range(3):
        booster.update()
        scores.append(np.asarray(booster._pred_train)[:n].copy())
    return booster, X, y, scores


def test_dump_walks_raw_values_back_to_the_programs_scores(raw_cat_booster):
    from benchmark.reference import cat_check

    booster, X, y, _ = raw_cat_booster
    dump = booster.dump_model()
    sets = []

    def collect(node):
        if "left_child" in node:
            if node["decision_type"] == "==":
                assert isinstance(node["threshold"], str)
                assert node["default_left"] is False
                sets.append(cat_check.parse_set(node["threshold"]))
            collect(node["left_child"])
            collect(node["right_child"])

    for t in dump["tree_info"]:
        collect(t["tree_structure"])
    assert sets and all(len(s) for s in sets)
    # raw values, not bin numbers: every value is one of the column's
    raw = set(X[:, 0].tolist()) | set(X[~np.isnan(X[:, 2]), 2].tolist())
    assert set(np.concatenate(sets).tolist()) <= raw
    table = cat_check.Table(X, [0, 2])
    walk = np.zeros(len(X))
    for t in dump["tree_info"]:
        tree = cat_check.flatten_tree(t["tree_structure"], table)
        walk += t["shrinkage"] * tree["value"][cat_check.route(table, tree)]
    got = booster.predict(X, raw_score=True)
    np.testing.assert_allclose(walk, got - got.mean() + walk.mean(),
                               atol=1e-5)


def test_reference_holds_the_programs_trees(raw_cat_booster):
    """``cat_check`` recomputes the checked trees' leaves from the raw
    rows, routed by the dumped sets, and finds the program's values,
    counts and scores."""
    from benchmark.reference import cat_check

    booster, X, y, scores = raw_cat_booster
    table = cat_check.Table(X, [0, 2])
    trees = [cat_check.flatten_tree(t["tree_structure"], table)
             for t in booster.dump_model()["tree_info"]]
    hyper = {"learning_rate": 0.2, "lambda_l2": 0.0, "cat_l2": 10.0,
             "max_cat_to_onehot": 4, "max_bin": 255}
    # the regression objective's gradients: the reference's are binary
    # logloss's, so its leaf values are not compared here, only the rows
    # each leaf holds and the walk of the scores
    out = cat_check.check_rounds(table, y, trees, scores, 0.0, hyper)
    for rd in out["rounds"]:
        assert rd["leaf_count_off"] == 0 and rd["root_count_off"] == 0
        assert rd["cat_splits"] > 0


# -- the scan orders its categories by sorting the planes themselves ---------

def _gather_scan(hist, total, ctx, feature_mask, depth_ok, cat_info, mono,
                 lo, hi, p_out, rand_bins):
    """The reference: ``ops.split._categorical_scan`` as it was written
    with an ``argsort`` and ``take_along_axis`` gathers of the planes by
    the order.  Same contract, same downstream arithmetic."""
    import jax.numpy as jnp

    from lightgbm_tpu.ops.split import (NEG_INF, TIE_RTOL, _group_evaluated,
                                        constrained_leaf_output,
                                        leaf_objective_at, split_stats_valid)

    num_features, num_bins = hist.shape[1], hist.shape[2]
    g_, h_, c_ = hist[0], hist[1], hist[2]
    tg, th, tc = total[0], total[1], total[2]
    pos = jnp.arange(num_bins)[None, :]
    cat_bins = (jnp.full((num_features,), num_bins, jnp.int32)
                if cat_info.cat_bins is None else cat_info.cat_bins)
    onehot = cat_bins + 1 <= cat_info.max_cat_to_onehot
    in_range = pos < cat_bins[:, None]
    usable = (feature_mask[:, None] > 0) & depth_ok
    if mono is not None:
        usable &= mono[:, None] == 0
    if rand_bins is not None:
        usable &= pos == rand_bins[:, None]
    parent_obj = leaf_objective_at(p_out, tg, th, ctx)

    def gain_at(lg, lh, lc, c):
        rg, rh, rc = tg - lg, th - lh, tc - lc
        wl = constrained_leaf_output(lg, lh, lc, c, lo, hi, p_out)
        wr = constrained_leaf_output(rg, rh, rc, c, lo, hi, p_out)
        gain = (leaf_objective_at(wl, lg, lh, c)
                + leaf_objective_at(wr, rg, rh, c) - parent_obj)
        return gain, (lg, lh, lc, rg, rh, rc, wl, wr)

    gain_o, stats_o = gain_at(g_, h_, c_, ctx)
    valid_o = (split_stats_valid(c_, stats_o[5], h_, stats_o[4], gain_o, ctx)
               & in_range & usable)
    gain_o = jnp.where(valid_o, gain_o, NEG_INF)
    ctx_cat = ctx._replace(lambda_l2=ctx.lambda_l2 + cat_info.cat_l2)
    elig = in_range & (c_ >= cat_info.cat_smooth)
    used = jnp.sum(elig, axis=1, dtype=jnp.int32)
    ratio = g_ / (h_ + cat_info.cat_smooth)
    order_asc = jnp.argsort(jnp.where(elig, ratio, jnp.inf), axis=1,
                            stable=True)
    back = used[:, None] - 1 - pos
    order_desc = jnp.where(
        back >= 0,
        jnp.take_along_axis(order_asc, jnp.maximum(back, 0), axis=1),
        order_asc)
    span = jnp.minimum(used, jnp.minimum(cat_info.max_cat_threshold,
                                         (used + 1) // 2))
    k = min(cat_info.max_cat_threshold, num_bins)
    mdpg = cat_info.min_data_per_group

    def direction(order):
        cum = [jnp.cumsum(jnp.take_along_axis(x, order, axis=1), axis=1)
               for x in (g_, h_, c_)]
        gain_d, stats_d = gain_at(cum[0], cum[1], cum[2], ctx_cat)
        lc, rg, rh, rc = stats_d[2], stats_d[3], stats_d[4], stats_d[5]
        ok = ((lc >= ctx.min_data_in_leaf)
              & (stats_d[1] >= ctx.min_sum_hessian)
              & (rc >= ctx.min_data_in_leaf) & (rc >= mdpg)
              & (rh >= ctx.min_sum_hessian) & (pos < span[:, None]))
        ev = _group_evaluated(lc[:, :k], ok[:, :k], mdpg)
        ev = jnp.pad(ev, ((0, 0), (0, num_bins - k)))
        valid = ev & (gain_d > ctx.min_gain_to_split) & usable
        return jnp.where(valid, gain_d, NEG_INF), stats_d

    gain_a, stats_a = direction(order_asc)
    gain_d, stats_d = direction(order_desc)
    best_a = jnp.max(gain_a, axis=1, keepdims=True)
    beat = jnp.where(jnp.isfinite(best_a),
                     best_a + TIE_RTOL * jnp.abs(best_a), NEG_INF)
    gain_d = jnp.where(gain_d > beat, gain_d, NEG_INF)
    use_desc = gain_d > gain_a
    gain_s = jnp.maximum(gain_a, gain_d)
    oh = onehot[:, None]
    gain = jnp.where(oh, gain_o, gain_s)
    picks = tuple(jnp.where(oh, o, jnp.where(use_desc, d, a))
                  for o, a, d in zip(stats_o, stats_a, stats_d))

    def mask_at(hit):
        row = jnp.any(hit, axis=1)
        at = jnp.sum(jnp.where(hit, pos, 0))
        desc = jnp.any(hit & use_desc)
        order_f = jnp.sum(jnp.where(row[:, None],
                                    jnp.where(desc, order_desc, order_asc),
                                    0), axis=0)
        rank = jnp.argsort(order_f)
        one = jnp.any(row & onehot)
        return jnp.where(one, pos[0] == at, rank <= at)

    return gain, picks, mask_at


def _scan_case(case, seed, children=3, num_features=7, num_bins=40):
    """Planes ``[children, 3, F, B]`` of categorical columns whose sorted
    scans meet exact ties: bins of one ratio (equal gradient and hessian,
    other counts), categories of zero gradient (``+0`` and ``-0`` ratios),
    empty bins; and the scan's inputs for ``case``."""
    import jax.numpy as jnp

    from lightgbm_tpu.ops.split import CatInfo, SplitContext

    rng = np.random.default_rng(seed)
    shape = (children, num_features, num_bins)
    c = rng.integers(0, 300, shape).astype(np.float32)
    c[rng.random(shape) < 0.15] = 0.0
    h = np.round(c * rng.uniform(0.1, 0.3, shape)).astype(np.float32)
    g = np.round(rng.normal(0, 1, shape) * 8 * np.sqrt(c + 1)) / 4
    g[rng.random(shape) < 0.15] = 0.0
    g = np.where(rng.random(shape) < 0.5, g, -g).astype(np.float32)
    tie = rng.integers(0, num_bins, (children, num_features, 6))
    src = tie[..., :1]
    for arr in (g, h):
        np.put_along_axis(arr, tie, np.take_along_axis(arr, src, 2), 2)
    assert (g == 0).any() and np.signbit(g[g == 0]).any()
    hyper = dict(cat_smooth=10.0, cat_l2=10.0, max_cat_threshold=32,
                 max_cat_to_onehot=4, min_data_per_group=0.0)
    cat_bins = np.full(num_features, num_bins, np.int32)
    mono = rand_bins = None
    if case == "cat_smooth_and_short_columns":
        hyper["cat_smooth"] = 150.0
        cat_bins = rng.integers(2, num_bins + 1, num_features).astype(
            np.int32)
    elif case == "onehot_columns":
        hyper["max_cat_to_onehot"] = 12
        cat_bins = rng.choice([2, 3, 6, 11, num_bins], num_features).astype(
            np.int32)
    elif case == "min_data_per_group":
        hyper["min_data_per_group"] = 400.0
        hyper["max_cat_threshold"] = 8
    elif case == "rand_bins":
        rand_bins = jnp.asarray(rng.integers(0, num_bins, (children,
                                                           num_features)))
    elif case == "mono":
        mono = jnp.asarray(rng.choice([-1, 0, 0, 1], num_features))
    info = CatInfo(is_cat=jnp.ones(num_features, bool),
                   cat_smooth=jnp.float32(hyper["cat_smooth"]),
                   cat_l2=jnp.float32(hyper["cat_l2"]),
                   max_cat_threshold=hyper["max_cat_threshold"],
                   cat_bins=jnp.asarray(cat_bins),
                   max_cat_to_onehot=hyper["max_cat_to_onehot"],
                   min_data_per_group=jnp.float32(
                       hyper["min_data_per_group"]))
    ctx = SplitContext(jnp.float32(0.0), jnp.float32(0.0), jnp.float32(1.0),
                       jnp.float32(5.0), jnp.float32(0.0))
    planes = jnp.asarray(np.stack([g, h, c], 1))
    return planes, ctx, info, mono, rand_bins


def _same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.tobytes() == b.tobytes()


SCAN_CASES = ["ties_and_signed_zeros", "cat_smooth_and_short_columns",
              "onehot_columns", "min_data_per_group", "rand_bins", "mono"]


@pytest.mark.parametrize("case", SCAN_CASES)
def test_sorted_planes_scan_as_the_gathered_ones(case, monkeypatch):
    """``_categorical_scan`` sorts the planes with their key; the gather
    formulation it replaced is the reference: the same gain, the same
    eight numbers and the same category set at every (feature, position),
    to the bit, alone and inside ``find_best_split`` vmapped over a wave's
    children in ``bins_minor`` planes."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.ops import split

    planes, ctx, info, mono, rand_bins = _scan_case(case, seed=45)
    n, _, num_features, num_bins = planes.shape
    lo, hi = jnp.float32(-jnp.inf), jnp.float32(jnp.inf)
    mask = jnp.ones(num_features, jnp.float32)
    hits = jnp.eye(num_features * num_bins, dtype=bool).reshape(
        -1, num_features, num_bins)
    rb0 = None if rand_bins is None else rand_bins[0]

    def scan(fn, hist):
        total = jnp.cumsum(hist, axis=2)[:, :, -1:]
        p_out = split.leaf_output(total[0], total[1], ctx)
        gain, picks, mask_at = fn(hist, total, ctx, mask, jnp.bool_(True),
                                  info, mono, lo, hi, p_out, rb0)
        return gain, picks, jax.vmap(mask_at)(hits)

    for child in range(n):
        got = scan(split._categorical_scan, planes[child])
        want = scan(_gather_scan, planes[child])
        assert _same_bits(got[0], want[0]), "gain"
        assert np.isfinite(np.asarray(want[0])).any()
        for i, (a, b) in enumerate(zip(got[1], want[1])):
            assert _same_bits(a, b), f"pick {i}"
        assert _same_bits(got[2], want[2]), "mask_at"

    def wave():
        def score(h, rb=None):
            return split.find_best_split(h, ctx, mask, jnp.bool_(True), info,
                                         mono, rand_bins=rb, bins_minor=True)
        if rand_bins is None:
            return jax.vmap(score)(planes)
        return jax.vmap(score)(planes, rand_bins)

    got = wave()
    monkeypatch.setattr(split, "_categorical_scan", _gather_scan)
    want = wave()
    assert np.asarray(want.cat).any()
    for field in want._fields:
        assert _same_bits(getattr(got, field), getattr(want, field)), field


def test_wave_scan_gathers_no_plane():
    """The wave's vmapped scan over categorical columns holds no gather
    whose result has F x B elements or more: the categories are ordered by
    sorting the planes, not by gathering them."""
    import re

    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.ops import split

    planes, ctx, info, _, _ = _scan_case("ties_and_signed_zeros", seed=1,
                                         children=6)
    num_features, num_bins = planes.shape[2], planes.shape[3]

    def score(h):
        return split.find_best_split(h, ctx, jnp.ones(num_features),
                                     jnp.bool_(True), info, bins_minor=True)

    text = jax.jit(jax.vmap(score)).lower(planes).as_text()
    assert "stablehlo.sort" in text
    gathers = re.findall(
        r'"stablehlo\.gather".*-> tensor<((?:\d+x)*\d+)x\w+>', text)
    sizes = [int(np.prod([int(d) for d in m.split("x")])) for m in gathers]
    assert not [s for s in sizes if s >= num_features * num_bins], sizes
