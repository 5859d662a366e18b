"""EFB member splits: a bundled table grows the trees of the same table
unbundled (LightGBM's FixHistogram semantics), through the member view of
the scan, the range routing of every grower and walker, and the device's
merged codes."""

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.dataset import (BinMapper, FeatureBundler,
                                  device_bin_codes)
from lightgbm_tpu.ops.members import (go_left, member_tables, member_view,
                                      members_of, split_route)


def stations_table(n=3000, groups=5, per_group=5, seed=0):
    """A NaN-sparse table whose columns are exclusive by construction: a
    row passes at most one station of each group of ``per_group``, and a
    column is NaN where its station was not passed.  Regression labels with
    well-separated effects of the stations passed and of two dense columns,
    so that no two candidate splits come near a tie."""
    rng = np.random.default_rng(seed)
    f = groups * per_group
    X = np.full((n, f + 2), np.nan, np.float32)
    y = np.zeros(n)
    effect = rng.permutation(np.linspace(-4.0, 4.0, f)) * 1.7
    for g in range(groups):
        pick = rng.integers(0, per_group + 1, n)       # per_group: skipped
        for s in range(per_group):
            col = g * per_group + s
            on = pick == s
            X[on, col] = rng.integers(0, 12 + col, on.sum()) * 0.25
            y += on * (effect[col] + 0.3 * np.nan_to_num(X[:, col]))
    X[:, f] = rng.normal(size=n)
    X[:, f + 1] = rng.integers(0, 40, n)
    y += 2.5 * X[:, f] + 0.05 * X[:, f + 1] + rng.normal(0, 0.01, n)
    return X, y.astype(np.float32)


def flat(node, out):
    if "leaf_value" in node:
        out.append(("leaf", node["leaf_value"]))
        return out
    out.append((node["split_feature"], node["threshold"]))
    flat(node["left_child"], out)
    flat(node["right_child"], out)
    return out


PARAMS = dict(objective="regression", num_leaves=7, learning_rate=0.3,
              min_data_in_leaf=20, verbosity=-1)


@pytest.mark.parametrize("grow", ["leafwise", "frontier"])
def test_bundled_and_unbundled_grow_the_same_trees(grow):
    X, y = stations_table()
    p = dict(PARAMS, grow_policy=grow)
    on = lgb.Dataset(X, label=y)
    b_on = lgb.train(p, on, num_boost_round=3)
    assert on.bin_mapper.bundler is not None
    assert on.num_feature_ < X.shape[1]          # the bundles formed
    b_off = lgb.train(dict(p, enable_bundle=False),
                      lgb.Dataset(X, label=y,
                                  params={"enable_bundle": False}),
                      num_boost_round=3)
    for t_on, t_off in zip(b_on.dump_model()["tree_info"],
                           b_off.dump_model()["tree_info"]):
        a, b = flat(t_on["tree_structure"], []), flat(
            t_off["tree_structure"], [])
        assert [x[0] for x in a] == [x[0] for x in b]      # features
        for (fa, va), (_, vb) in zip(a, b):
            if fa == "leaf":
                assert va == pytest.approx(vb, rel=1e-6, abs=1e-9)
            else:
                assert va == vb                          # raw thresholds
    np.testing.assert_allclose(b_on.predict(X), b_off.predict(X),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(b_on._pred_train)[:len(y)],
                               np.asarray(b_off._pred_train)[:len(y)],
                               rtol=1e-6, atol=1e-6)


def test_bundled_trees_hold_against_the_sparse_reference():
    """The reference knows nothing of bundles: NaN right, raw thresholds;
    every number the sparse cell compares inside its configuration's
    limits (binary, as the cell)."""
    from benchmark.manifest import Manifest
    from benchmark.reference import gbdt_check, sparse_check

    X, y = stations_table(n=4000, seed=3)
    yb = (y > np.median(y)).astype(np.float32)
    hyper = dict(learning_rate=0.1, lambda_l2=0.0, num_leaves=15,
                 max_bin=255, min_sum_hessian_in_leaf=1.0)
    b = lgb.Booster(dict(objective="binary", num_leaves=15, verbosity=-1,
                         min_sum_hessian_in_leaf=1.0, min_data_in_leaf=1,
                         hist_dtype="f32"), lgb.Dataset(X, label=yb))
    scores = []
    for _ in range(3):
        b.update()
        scores.append(np.asarray(b._pred_train)[:len(yb)])
    assert b.train_set.bin_mapper.bundler is not None
    dump = b.dump_model()
    trees = [gbdt_check.flatten_tree(t["tree_structure"])
             for t in dump["tree_info"]]
    assert sum(sparse_check.dump_missing(t["tree_structure"])
               for t in dump["tree_info"]) == 0
    rounds = sparse_check.check_rounds(
        X, yb, trees, scores, b.init_score_, hyper, seed=5, split_nodes=4,
        order_leaves=4)["rounds"]
    man = Manifest()
    limits = man.config(man.cell("bosch-1m.train"))["limits"]
    for name in ("leaf_value_worst", "leaf_count_off", "score_abs",
                 "split_gain_short", "order_excess", "unsplit_leaves"):
        assert max(rd[name] for rd in rounds) <= limits[name], name


@pytest.mark.parametrize("default", ["nan", "zero", "middle"])
def test_range_rule_is_the_members_own_walk(default):
    """For every member and every threshold, ``go_left`` on the merged
    code sends a row left iff the member's own bin is ``<= t``, with the
    member's default bin at its NaN bin, at the bin of 0.0 and in the
    middle of its range."""
    nb = np.array([6, 9, 4])
    dflt = {"nan": nb - 1, "zero": np.array([0, 0, 0]),
            "middle": np.array([2, 5, 1])}[default]
    bundler = FeatureBundler([[0, 1, 2]], nb, dflt)
    m = members_of(type("M", (), {"bundler": bundler, "n_bins": nb}),
                   int(bundler.max_col_bins))
    for f in range(3):
        # rows where feature f takes each of its bins, the others default
        codes = np.tile(dflt, (nb[f], 1))
        codes[:, f] = np.arange(nb[f])
        merged = bundler.merge(codes)[:, 0].astype(np.int32)
        for t in range(nb[f] - 1):
            col, lo, hi, inv = split_route(m, np.int32(f), np.int32(t))
            assert int(col) == 0
            got = np.asarray(go_left(merged, lo, hi, inv))
            np.testing.assert_array_equal(got, np.arange(nb[f]) <= t)


def test_member_view_is_the_unbundled_histogram():
    from lightgbm_tpu.ops.histogram import compute_histograms

    X, y = stations_table(n=2000, seed=1)
    ds = lgb.Dataset(X, label=y).construct()
    mapper = ds.bin_mapper
    n, n_pad = len(y), int(ds.row_mask.shape[0])
    stats = np.random.default_rng(2).normal(size=(n_pad, 3))
    stats[n:] = 0.0                         # padding rows carry nothing
    stats = stats.astype(np.float32)
    seg = np.zeros(n_pad, np.int32)
    bundled = compute_histograms(ds.X_binned, stats, seg, 1,
                                 ds.num_bins)[0]
    view = np.asarray(member_view(np.moveaxis(np.asarray(bundled), -1, 0),
                                  members_of(mapper, ds.num_bins)))
    raw = np.zeros((n_pad, X.shape[1]), np.uint8)
    raw[:n] = mapper._transform_unbundled(X)
    plain = np.moveaxis(np.asarray(compute_histograms(
        raw, stats, seg, 1, ds.num_bins)[0]), -1, 0)
    # a member's default bin, the node total less its other bins, is the
    # one the unbundled table counts (to float32 rounding)
    tabs = member_tables(mapper.bundler, mapper.n_bins, ds.num_bins)
    assert (tabs["dflt"] < ds.num_bins).sum() > 0
    np.testing.assert_allclose(view, plain, rtol=1e-5, atol=2e-4)


def test_device_codes_of_a_bundled_table_are_the_hosts(monkeypatch):
    import lightgbm_tpu.dataset as dataset_mod

    X, _ = stations_table(n=5000, seed=4)
    mapper = BinMapper.fit(X, min_data_in_bin=1)
    codes = mapper._transform_unbundled(X)
    mapper.bundler = FeatureBundler.fit(codes, mapper.n_bins)
    assert mapper.bundler is not None
    monkeypatch.setattr(dataset_mod, "CODE_BLOCK_VALUES",
                        1024 * X.shape[1])
    n_pad = -(-len(X) // 256) * 256
    got, blocks, conflicts = device_bin_codes(X, mapper, n_pad)
    got = np.asarray(got)
    assert blocks == 5 and int(conflicts) == 0
    assert got[:len(X)].tobytes() == mapper.transform(X).tobytes()
    assert not got[len(X):].any()
    # a conflicting row is counted once, and keeps its last member's code
    Xc = X.copy()
    g = mapper.bundler.groups[-1]
    Xc[7, g[0]] = Xc[~np.isnan(Xc[:, g[0]]), g[0]][0]
    Xc[7, g[1]] = Xc[~np.isnan(Xc[:, g[1]]), g[1]][0]
    got, _, conflicts = device_bin_codes(Xc, mapper, n_pad)
    assert int(conflicts) == mapper.bundler.conflict_rows(
        mapper._transform_unbundled(Xc)) >= 1
    assert np.asarray(got)[:len(X)].tobytes() == \
        mapper.transform(Xc).tobytes()


def test_a_permutation_of_the_columns_gives_the_same_bundles():
    X, _ = stations_table(n=3000, seed=6)
    perm = np.random.default_rng(9).permutation(X.shape[1])

    def bundles(table):
        mapper = BinMapper.fit(table)
        b = FeatureBundler.fit(mapper._transform_unbundled(table),
                               mapper.n_bins)
        return [g for g in b.groups if len(g) > 1]

    a = sorted(tuple(sorted(g)) for g in bundles(X))
    b = sorted(tuple(sorted(perm[f] for f in g))
               for g in bundles(X[:, perm]))
    assert a == b and len(a) > 0


@pytest.mark.parametrize("nan_seen", [True, False])
def test_a_dump_walked_on_rows_with_nan_is_the_models_prediction(nan_seen):
    """``missing_type`` and ``default_left`` in the dump say where NaN
    goes: a walk that honours them on rows with NaN gives ``predict``."""
    X, y = stations_table(n=2000, seed=8)
    if not nan_seen:
        X = np.nan_to_num(X, nan=-1.0)
    b = lgb.train(dict(PARAMS, num_leaves=15), lgb.Dataset(X, label=y),
                  num_boost_round=4)
    rows = X[:300].copy()
    rows[::3, :] = np.nan
    dump = b.dump_model()
    kinds = set()

    def walk(node, x):
        while "leaf_value" not in node:
            v = x[node["split_feature"]]
            kinds.add((node["missing_type"], node["default_left"]))
            if np.isnan(v):
                if node["missing_type"] == "None":
                    v = 0.0
                else:
                    node = node["left_child" if node["default_left"]
                                else "right_child"]
                    continue
            node = node["left_child" if v <= node["threshold"]
                        else "right_child"]
        return node["leaf_value"]

    lr = dump["tree_info"][0]["shrinkage"]
    want = b.predict(rows, raw_score=True)
    got = [b.init_score_ + lr * sum(walk(t["tree_structure"], x)
                                    for t in dump["tree_info"]) for x in rows]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    if nan_seen:
        assert ("NaN", False) in kinds and ("NaN", True) not in kinds
    else:
        assert {k[0] for k in kinds} == {"None"}


def mixed_bins_table(n=3000, f=60, seed=4):
    """``f`` dense columns of 2 to 255 distinct values (two blocks of the
    fused kernels' feature rows at the tree's width), regression labels
    from a few of them."""
    rng = np.random.default_rng(seed)
    values = np.resize(np.array([2, 9, 17, 40, 100, 255]), f)
    X = np.stack([rng.integers(0, v, n) for v in values], 1).astype(
        np.float32)
    y = (X[:, 0] * 0.7 - 0.02 * X[:, 5] + 0.1 * X[:, 3] * (X[:, 1] > 4)
         + 0.05 * X[:, 10] + rng.normal(0, 0.1, n))
    return X, y.astype(np.float32)


@pytest.mark.parametrize("table", ["bundled", "plain_two_blocks"])
def test_onehot_heights_grow_the_same_trees(table, monkeypatch):
    """The partition-fused grower (forced onto the CPU's interpret mode)
    with its kernels' rows ordered by one-hot height grows the trees it
    grows with every one-hot ``num_bins`` tall: the same dump, the leaf
    values equal to the bit, the same training scores.  A bundled table
    (one feature block, its members routed by range) and a plain one of
    60 columns (two blocks, routed from the gathered rows)."""
    from lightgbm_tpu.ops import histogram_pallas
    from lightgbm_tpu.utils import profiling

    X, y = stations_table() if table == "bundled" else mixed_bins_table()
    params = dict(PARAMS, num_leaves=31, min_data_in_leaf=10,
                  grow_policy="frontier", hist_impl="pallas")
    if table != "bundled":
        params["enable_bundle"] = False
    by_height = []
    real = histogram_pallas.hist_partition_fused_pallas

    def spy(*args, **kwargs):
        by_height.append(kwargs["row_of"] is not None)
        return real(*args, **kwargs)

    monkeypatch.setattr(histogram_pallas, "hist_partition_fused_pallas", spy)

    def train():
        ds = lgb.Dataset(X, label=y, params={
            "enable_bundle": table == "bundled"})
        booster = lgb.train(params, ds, num_boost_round=3)
        assert (ds.bin_mapper.bundler is not None) == (table == "bundled")
        return booster, profiling.snapshot()["facts"]

    sorted_rows, facts = train()
    assert by_height and all(by_height)
    assert 0 < facts["train.onehot_bin_share"] < 0.9
    del by_height[:]
    monkeypatch.setattr(histogram_pallas, "onehot_heights",
                        lambda col_bins, num_bins: None)
    full, facts = train()
    assert by_height and not any(by_height)
    assert facts["train.onehot_bin_share"] == 1.0
    assert sorted_rows.dump_model() == full.dump_model()
    np.testing.assert_array_equal(np.asarray(sorted_rows._pred_train),
                                  np.asarray(full._pred_train))
