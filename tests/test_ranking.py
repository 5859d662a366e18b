"""LambdaRank + NDCG (MSLR north-star config — VERDICT r1 item 4).

Synthetic ranked data: each query has docs with hidden utility; graded
relevance labels are a noisy discretization.  LambdaRank's NDCG@5 must
clearly beat a pointwise-regression baseline trained on the same features.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.ranking import (
    LambdaRank,
    RankEvalContext,
    _pack_groups,
    eval_ranking,
    ndcg_at_k,
)


def make_ranked(n_queries=120, docs_lo=8, docs_hi=24, f=6, seed=0):
    rng = np.random.default_rng(seed)
    sizes = rng.integers(docs_lo, docs_hi + 1, n_queries)
    n = int(sizes.sum())
    X = rng.normal(0, 1, (n, f))
    # hidden utility: nonlinear in the first three features
    u = (1.2 * X[:, 0] + np.sin(2 * X[:, 1]) + 0.6 * X[:, 2] ** 2
         + 0.3 * rng.normal(0, 1, n))
    # graded relevance 0..4 by within-query quantile
    y = np.zeros(n, np.float64)
    start = 0
    for s in sizes:
        q = u[start:start + s]
        ranks = q.argsort().argsort()
        y[start:start + s] = np.minimum(4, (5 * ranks) // s)
        start += s
    return X, y, sizes


def ndcg_of_scores(scores, y, sizes, k=5):
    doc_idx, valid = _pack_groups(sizes)
    gains = np.where(valid, (2.0 ** y[doc_idx] - 1) * valid, 0.0)
    s = jnp.asarray(np.where(valid, scores[doc_idx], -np.inf), jnp.float32)
    per_q = ndcg_at_k(s, jnp.asarray(gains, jnp.float32),
                      jnp.asarray(valid), k)
    return float(np.mean(np.asarray(per_q)))


def test_ndcg_metric_sanity():
    # perfect ordering -> 1.0; inverted ordering is worse
    sizes = np.array([5, 7])
    y = np.array([0, 1, 2, 3, 4, 0, 0, 1, 2, 3, 4, 4], np.float64)
    perfect = ndcg_of_scores(y.astype(np.float64), y, sizes)
    inverted = ndcg_of_scores(-y.astype(np.float64), y, sizes)
    assert perfect == pytest.approx(1.0, abs=1e-6)
    assert inverted < 0.8


def test_lambdarank_beats_pointwise():
    X, y, sizes = make_ranked()
    params = dict(objective="lambdarank", num_leaves=15, learning_rate=0.1,
                  min_data_in_leaf=5, verbosity=-1)
    ds = lgb.Dataset(X, label=y, group=sizes)
    rk = lgb.train(params, ds, num_boost_round=60)
    scores_rk = rk.predict(X)

    reg = lgb.train(dict(objective="regression", num_leaves=15,
                         learning_rate=0.1, min_data_in_leaf=5,
                         verbosity=-1),
                    lgb.Dataset(X, label=y), num_boost_round=60)
    scores_reg = reg.predict(X)

    n5_rk = ndcg_of_scores(scores_rk, y, sizes)
    n5_reg = ndcg_of_scores(scores_reg, y, sizes)
    assert n5_rk > 0.8
    assert n5_rk >= n5_reg - 0.005  # at least parity, usually clearly better

    # and it must clearly beat random ordering
    rng = np.random.default_rng(0)
    n5_rand = ndcg_of_scores(rng.normal(0, 1, len(y)), y, sizes)
    assert n5_rk > n5_rand + 0.1


def test_lambdarank_requires_group():
    X, y, _ = make_ranked(n_queries=10)
    with pytest.raises(ValueError, match="group"):
        lgb.train(dict(objective="lambdarank", verbosity=-1),
                  lgb.Dataset(X, label=y), num_boost_round=2)


def test_ndcg_eval_during_training():
    X, y, sizes = make_ranked(n_queries=60, seed=3)
    Xv, yv, sv = make_ranked(n_queries=20, seed=4)
    ds = lgb.Dataset(X, label=y, group=sizes)
    dv = lgb.Dataset(Xv, label=yv, group=sv)
    booster = lgb.train(dict(objective="lambdarank", num_leaves=15,
                             min_data_in_leaf=5, verbosity=-1,
                             eval_at=[3, 5]),
                        ds, num_boost_round=10, valid_sets=[dv],
                        valid_names=["va"])
    res = booster.eval_valid()
    names = {r[1] for r in res}
    assert names == {"ndcg@3", "ndcg@5"}
    assert all(r[3] for r in res)  # higher_better
    assert all(0.0 <= r[2] <= 1.0 for r in res)


def test_lambdarank_cv_group_aware():
    X, y, sizes = make_ranked(n_queries=40, seed=5)
    res = lgb.cv(dict(objective="lambdarank", num_leaves=7,
                      min_data_in_leaf=5, verbosity=-1, eval_at=[5]),
                 lgb.Dataset(X, label=y, group=sizes),
                 num_boost_round=8, nfold=3,
                 early_stopping_rounds=5)
    key = [k for k in res if k.endswith("-mean")]
    assert key, res.keys()
    assert res.best_iter >= 1
    # ndcg is higher-better: best_score must be positive (no sign flip)
    assert 0.0 < res.best_score <= 1.0


def test_lgbm_ranker_sklearn():
    X, y, sizes = make_ranked(n_queries=50, seed=7)
    from lightgbm_tpu.sklearn import LGBMRanker
    r = LGBMRanker(n_estimators=20, num_leaves=15, min_child_samples=5)
    r.fit(X, y, group=sizes)
    s = r.predict(X)
    assert s.shape == (len(y),)
    assert ndcg_of_scores(s, y, sizes) > 0.75


def test_truncation_level_changes_gradients():
    X, y, sizes = make_ranked(n_queries=30, seed=9)
    import jax
    from lightgbm_tpu.config import parse_params

    n = len(y)
    pred = jnp.asarray(np.random.default_rng(0).normal(0, 1, n), jnp.float32)
    w = jnp.ones(n, jnp.float32)

    def grads(trunc):
        p = parse_params(dict(objective="lambdarank",
                              lambdarank_truncation_level=trunc))
        obj = LambdaRank(p)
        obj.set_group(sizes, y, n)
        g, h = obj.grad_hess(pred, jnp.asarray(y, jnp.float32), w)
        return np.asarray(g)

    g_full = grads(30)
    g_t1 = grads(1)
    assert not np.allclose(g_full, g_t1)
    # gradients sum to ~0 per query (pairwise antisymmetry)
    assert abs(g_full.sum()) < 1e-2


def test_lambdarank_refit_with_group():
    import numpy as np
    import lightgbm_tpu as lgb

    rng = np.random.default_rng(41)
    n_q, g_sz = 48, 12
    n = n_q * g_sz
    X = rng.normal(size=(n, 4)).astype(np.float32)
    y = np.clip(np.floor(X[:, 0] + 0.3 * rng.normal(size=n)) + 2,
                0, 4).astype(np.float32)
    group = np.full(n_q, g_sz)
    b = lgb.train({"objective": "lambdarank", "num_leaves": 7,
                   "verbosity": -1},
                  lgb.Dataset(X, label=y, group=group), num_boost_round=6)
    # refit on the second half (regrouped)
    half = n // 2
    ref = b.refit(X[half:], y[half:], group=np.full(n_q // 2, g_sz),
                  decay_rate=0.5)
    for t0, t1 in zip(b.trees, ref.trees):
        np.testing.assert_array_equal(np.asarray(t0.split_feature),
                                      np.asarray(t1.split_feature))
    assert not np.allclose(np.asarray(b.trees[0].leaf_value),
                           np.asarray(ref.trees[0].leaf_value))
    import pytest
    with pytest.raises(ValueError, match="group="):
        b.refit(X[half:], y[half:])


def _map_oracle(scores, y, sizes, k):
    """Numpy AP@k per query: binary relevance label>0, denominator
    min(num_relevant, k); empty-relevance queries count 1."""
    out = []
    start = 0
    for s in sizes:
        sc, yy = scores[start:start + s], y[start:start + s]
        start += s
        order = np.argsort(-sc, kind="stable")
        rel = (yy[order] > 0).astype(np.float64)
        npos = rel.sum()
        if npos == 0:
            out.append(1.0)
            continue
        hits = np.cumsum(rel)[:k]
        r = rel[:k]
        ap = np.sum(r * hits / (1.0 + np.arange(len(r)))) / min(npos, k)
        out.append(ap)
    return float(np.mean(out))


def test_map_matches_numpy_oracle():
    X, y, sizes = make_ranked(n_queries=50, seed=7)
    rng = np.random.default_rng(1)
    scores = rng.normal(0, 1, len(y))
    ds = lgb.Dataset(X, label=y, group=sizes)
    ds.construct()
    for k in (1, 3, 5, 10):
        got = eval_ranking(jnp.asarray(scores, jnp.float32), ds, [k],
                           metrics=("map",))
        assert got[0][0] == f"map@{k}"
        assert got[0][1] == pytest.approx(
            _map_oracle(scores, y, sizes, k), abs=1e-5)


def test_map_eval_and_early_stopping():
    X, y, sizes = make_ranked(n_queries=60, seed=3)
    Xv, yv, sv = make_ranked(n_queries=20, seed=4)
    ds = lgb.Dataset(X, label=y, group=sizes)
    dv = lgb.Dataset(Xv, label=yv, group=sv)
    booster = lgb.train(dict(objective="lambdarank", num_leaves=15,
                             min_data_in_leaf=5, verbosity=-1,
                             metric=["map"], eval_at=[5]),
                        ds, num_boost_round=8, valid_sets=[dv],
                        valid_names=["va"])
    res = booster.eval_valid()
    assert {r[1] for r in res} == {"map@5"}
    assert all(0.0 <= r[2] <= 1.0 for r in res)

    # early stopping driven by map must engage (higher_better respected)
    evals = {}
    booster2 = lgb.train(dict(objective="lambdarank", num_leaves=15,
                              min_data_in_leaf=5, verbosity=-1,
                              metric=["map"], eval_at=[5],
                              early_stopping_rounds=3),
                         ds, num_boost_round=200, valid_sets=[dv],
                         valid_names=["va"],
                         callbacks=[lgb.record_evaluation(evals)])
    assert booster2.best_iteration >= 1
    assert len(evals["va"]["map@5"]) < 200  # stopped early


# ---------------------------------------------------------------------------
# PR 37: queries packed by length, the pairs LightGBM visits, the groups as
# operands of the round program; held against benchmark/reference/rank_check
# (numpy float64, a plain loop over queries, nothing of lightgbm_tpu)
# ---------------------------------------------------------------------------

# one set with a query of every awkward length: 1 and 2 documents, one under,
# at and over a block width, one longer than the truncation level, one of
# several blocks' width; 6 documents with all labels equal
MIXED_SIZES = np.array([1, 2, 7, 8, 9, 33, 130, 6, 40, 64])


def _mixed_case(scores, seed=0):
    rng = np.random.default_rng(seed)
    n = int(MIXED_SIZES.sum())
    y = rng.integers(0, 5, n).astype(np.float32)
    at = int(MIXED_SIZES[:7].sum())
    y[at:at + 6] = 2.0                       # a query with all labels equal
    s = rng.normal(0, 1, n).astype(np.float32)
    if scores == "ties":
        s = np.round(s * 2) / 2              # exact ties inside every query
    elif scores == "zeros":
        s[:] = 0.0                           # round 1: every score ties
    return y, s


def _lambdas(sizes, y, s, pad=5, **params):
    from lightgbm_tpu.config import parse_params

    n = len(y)
    obj = LambdaRank(parse_params(dict(objective="lambdarank", **params)))
    obj.set_group(sizes, y, n + pad)
    pred = jnp.asarray(np.concatenate([s, np.full(pad, 7.0, np.float32)]))
    g, h = obj.grad_hess(pred, None, jnp.ones(n + pad, jnp.float32))
    return obj, np.asarray(g), np.asarray(h)


@pytest.mark.parametrize("norm", [True, False])
@pytest.mark.parametrize("scores", ["random", "ties", "zeros"])
def test_lambdas_equal_the_reference_loop(scores, norm):
    """The packed pair blocks against LightGBM's double loop in float64:
    float32 sums of up to 130 pair terms of size <= 12, so 5e-6 absolute."""
    from benchmark.reference import rank_check

    y, s = _mixed_case(scores)
    obj, g, h = _lambdas(MIXED_SIZES, y, s, lambdarank_norm=norm)
    assert len(obj.layout[1]) >= 5 and obj.layout[0] == 0   # several blocks
    g_ref, h_ref = rank_check.lambdas(
        s, y, MIXED_SIZES, dict(sigmoid=1.0, lambdarank_truncation_level=30,
                                lambdarank_norm=norm))
    n = len(y)
    np.testing.assert_allclose(g[:n], g_ref, atol=5e-6, rtol=1e-5)
    np.testing.assert_allclose(h[:n], h_ref, atol=5e-6, rtol=1e-5)
    assert np.abs(g_ref).max() > 0.1
    # no floor under the hessians: the all-equal query and the padding rows
    # hold exact zeros
    at = int(MIXED_SIZES[:7].sum())
    assert not g[at:at + 6].any() and not h[at:at + 6].any()
    assert not g[n:].any() and not h[n:].any()


@pytest.mark.parametrize("trunc", [1, 5, 1251])
def test_truncation_is_the_window_lightgbm_visits(trunc):
    from benchmark.reference import rank_check

    y, s = _mixed_case("random", seed=3)
    _, g, h = _lambdas(MIXED_SIZES, y, s, lambdarank_truncation_level=trunc)
    g_ref, h_ref = rank_check.lambdas(
        s, y, MIXED_SIZES, dict(lambdarank_truncation_level=trunc))
    np.testing.assert_allclose(g[:len(y)], g_ref, atol=5e-6, rtol=1e-5)
    np.testing.assert_allclose(h[:len(y)], h_ref, atol=5e-6, rtol=1e-5)


def test_packed_layout_equals_one_wide_block(monkeypatch):
    """Packing by length changes the slots, not the sums."""
    from lightgbm_tpu import ranking

    y, s = _mixed_case("ties", seed=5)
    packed, g, h = _lambdas(MIXED_SIZES, y, s)
    monkeypatch.setattr(ranking, "_width_ladder",
                        lambda longest: np.array([136], np.int64))
    wide, g1, h1 = _lambdas(MIXED_SIZES, y, s)
    assert wide.layout == (0, ((len(MIXED_SIZES), 136),))
    assert wide.facts["rank_doc_slots"] == 10 * 136 > \
        packed.facts["rank_doc_slots"] >= MIXED_SIZES.sum()
    assert wide.facts["rank_pairs_visited"] == \
        packed.facts["rank_pairs_visited"] == sum(
            min(30, n - 1) * (n - 1) - min(30, n - 1) * (min(30, n - 1) - 1)
            // 2 for n in MIXED_SIZES)
    np.testing.assert_allclose(g, g1, atol=2e-6)
    np.testing.assert_allclose(h, h1, atol=2e-6)


def test_uniform_shortcut_equals_the_gathered_block():
    """Equal lengths: one block by reshape.  The same queries with one odd
    query behind them go through the gather: the same numbers."""
    sizes = np.full(20, 12)
    rng = np.random.default_rng(2)
    y = rng.integers(0, 5, 240).astype(np.float32)
    s = rng.normal(0, 1, 240).astype(np.float32)
    obj, g, h = _lambdas(sizes, y, s)
    assert obj.layout == (12, ((20, 16),))
    assert obj.groups["row_slot"] is None
    odd, g1, h1 = _lambdas(np.append(sizes, 5), np.append(y, np.zeros(5)),
                           np.append(s, np.ones(5, np.float32)))
    assert odd.layout == (0, ((1, 8), (20, 16)))
    np.testing.assert_allclose(g[:240], g1[:240], atol=1e-6)
    np.testing.assert_allclose(h[:240], h1[:240], atol=1e-6)
    assert not g1[240:].any()


def test_every_row_is_written_exactly_once(monkeypatch):
    """A block that answers 1 in every document's slot and 0 in its
    padding: each row of the table reads 1, each padding row 0."""
    def ones(self, scores, blk):
        col = jnp.arange(scores.shape[1])[None, :]
        valid = (col < blk["size"][:, None]).astype(jnp.float32)
        return valid, 2.0 * valid

    monkeypatch.setattr(LambdaRank, "_block_lambdas", ones)
    y, s = _mixed_case("random")
    _, g, h = _lambdas(MIXED_SIZES, y, s)
    n = len(y)
    assert (g[:n] == 1.0).all() and (h[:n] == 2.0).all()
    assert not g[n:].any() and not h[n:].any()


def test_padding_slots_hold_exact_zeros():
    """Whatever score a padding slot carries (the next query's document, or
    the table's padding), the block's answer there is exactly 0."""
    y, s = _mixed_case("random", seed=8)
    obj, _, _ = _lambdas(MIXED_SIZES, y, s)
    for (q, g), blk in zip(obj.layout[1], obj.groups["blocks"]):
        scores = jnp.asarray(np.random.default_rng(g).normal(
            0, 5, (q, g)).astype(np.float32))
        g_q, h_q = obj._block_lambdas(scores, blk)
        pad = np.arange(g)[None, :] >= np.asarray(blk["size"])[:, None]
        assert not np.asarray(g_q)[pad].any()
        assert not np.asarray(h_q)[pad].any()


def _ranked_dataset(seed, n_queries=40):
    """Different rows and labels, the SAME query sizes."""
    sizes = np.random.default_rng(100).integers(2, 40, n_queries)
    X, y, _ = make_ranked(n_queries=n_queries, seed=seed)
    n = int(sizes.sum())
    reps = -(-n // len(y))
    X, y = np.tile(X, (reps, 1))[:n], np.tile(y, reps)[:n]
    return lgb.Dataset(X.astype(np.float32), label=y, group=sizes)


RANK_PARAMS = dict(objective="lambdarank", num_leaves=7, min_data_in_leaf=5,
                   verbosity=-1)


def test_two_boosters_on_equal_shapes_share_one_round_program():
    """The groups are operands and the key is the layout's shapes: a second
    training on the same shapes finds the first one's compiled round."""
    from lightgbm_tpu.models.gbdt import _objective_static_key

    a = lgb.Booster(dict(RANK_PARAMS), _ranked_dataset(1))
    b = lgb.Booster(dict(RANK_PARAMS), _ranked_dataset(2))
    key = _objective_static_key(a.obj, a.params)
    assert key == _objective_static_key(b.obj, b.params) == a._obj_key
    assert not any(isinstance(part, LambdaRank) for part in key)
    hash(key)
    assert key[-1] == a.obj.layout and a.obj is not b.obj
    fn_a, args_a = a._fused_segment(1)
    fn_b, args_b = b._fused_segment(1)
    assert fn_a is fn_b
    # the operands end with the layout's tensors, the table's bundles
    # (none: this table has no bundle), the order of its columns by the
    # height of their one-hots (an operand: any order is one program) and
    # its categorical columns (none: this table has no categorical column)
    assert args_a[-4] is a._groups and args_b[-4] is b._groups
    assert args_a[-3] is None and args_b[-3] is None
    assert args_a[-2] is a._col_order and args_b[-2] is b._col_order
    assert args_a[-1] is None and args_b[-1] is None
    before = fn_a._cache_size()
    a.update_many(1)
    b.update_many(1)
    assert fn_a._cache_size() - before <= 1       # one program, not two
    assert not np.allclose(np.asarray(a._pred_train),
                           np.asarray(b._pred_train))
    # other group sizes are another layout, and another program
    c = lgb.Booster(dict(RANK_PARAMS), _ranked_dataset(1, n_queries=41))
    assert c._obj_key != a._obj_key


def test_update_many_equals_single_updates_under_lambdarank():
    a = lgb.Booster(dict(RANK_PARAMS), _ranked_dataset(3))
    b = lgb.Booster(dict(RANK_PARAMS), _ranked_dataset(3))
    a.update_many(3)
    for _ in range(3):
        b.update()
    np.testing.assert_allclose(np.asarray(a._pred_train),
                               np.asarray(b._pred_train), atol=1e-6)
    assert a.current_iteration() == b.current_iteration() == 3


def test_rank_facts_are_noted_for_the_round_program():
    from lightgbm_tpu.utils import profiling

    b = lgb.Booster(dict(RANK_PARAMS), _ranked_dataset(4))
    b._fused_segment(1)
    facts = profiling.snapshot()["facts"]
    assert facts["train.rank_queries"] == 40
    assert facts["train.rank_truncation"] == 30
    assert facts["train.rank_blocks"] == [list(s) for s in b.obj.layout[1]]
    assert facts["train.rank_doc_slots"] >= b.train_set.num_data_
    assert facts["train.rank_pair_slots"] >= facts["train.rank_pairs_visited"]
    assert "lgbtpu.rank.pack" in profiling.snapshot()["spans"]


def test_mslr_like_has_the_stated_shape():
    from lightgbm_tpu.utils.datasets import make_mslr_like

    X, y, sizes = make_mslr_like(6000, 27, 80, seed=3, docs_hi=400)
    assert X.shape == (6000, 27) and X.dtype == np.float32
    assert sizes.sum() == 6000 and sizes.min() == 1 and sizes.max() == 400
    assert set(np.unique(y)) <= {0.0, 1.0, 2.0, 3.0, 4.0}
    assert len(np.unique(X[:, 0])) < 16 and len(np.unique(X[:, 5])) < 255
    X2, y2, s2 = make_mslr_like(6000, 27, 80, seed=3, docs_hi=400)
    assert (X == X2).all() and (y == y2).all() and (sizes == s2).all()
