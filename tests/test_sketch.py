"""Streaming quantile sketch + from_blocks construction tests (ISSUE 7).

Pins the exactness contract documented in data/sketch.py: bit-identical
BinMapper on the exact fast path, exact at any n for bounded-vocabulary
columns, eps-rank-bounded edges on the GK path — plus the from_blocks
input validation surface.
"""

import numpy as np
import pytest

from lightgbm_tpu.data.sketch import (GKSummary, StreamingBinMapperBuilder,
                                      _FeatureSketch)
from lightgbm_tpu.dataset import (BinMapper, Dataset, _weighted_quantile,
                                  numeric_bin_bounds)


def _mapper_equal(a: BinMapper, b: BinMapper) -> bool:
    if not np.array_equal(a.n_bins, b.n_bins):
        return False
    if not np.array_equal(a.nan_bin, b.nan_bin):
        return False
    return all(np.array_equal(ua, ub)
               for ua, ub in zip(a.upper_bounds, b.upper_bounds))


def _mixed_matrix(n, seed=0):
    """Continuous + low-cardinality + constant + NaN-bearing columns."""
    rng = np.random.default_rng(seed)
    cont = rng.normal(0, 1, n)
    lowcard = rng.integers(0, 7, n).astype(np.float64)
    const = np.full(n, 3.25)
    withnan = rng.normal(2, 5, n)
    withnan[rng.random(n) < 0.1] = np.nan
    return np.column_stack([cont, lowcard, const, withnan])


# ---------------------------------------------------------------- exact path

def test_exact_fast_path_bit_identical():
    X = _mixed_matrix(3000)
    ref = BinMapper.fit(X, max_bin=63, min_data_in_bin=3)
    b = StreamingBinMapperBuilder(num_features=X.shape[1])
    for lo in range(0, len(X), 700):          # ragged last block on purpose
        b.update(X[lo:lo + 700])
    assert _mapper_equal(b.finalize(max_bin=63, min_data_in_bin=3), ref)


@pytest.mark.parametrize("max_bin", [15, 63, 255])
def test_exact_path_max_bin_aware(max_bin):
    X = _mixed_matrix(2500, seed=1)
    ref = BinMapper.fit(X, max_bin=max_bin, min_data_in_bin=3)
    b = StreamingBinMapperBuilder(num_features=X.shape[1]).update(X)
    got = b.finalize(max_bin=max_bin, min_data_in_bin=3)
    assert _mapper_equal(got, ref)
    assert int(got.n_bins.max()) <= max_bin + 1   # +1 for the nan bin


def test_exact_path_single_vs_many_blocks_identical():
    X = _mixed_matrix(2048, seed=2)
    one = StreamingBinMapperBuilder(4).update(X).finalize(63, 3)
    b = StreamingBinMapperBuilder(4)
    for lo in range(0, 2048, 256):
        b.update(X[lo:lo + 256])
    assert _mapper_equal(b.finalize(63, 3), one)


# ------------------------------------------------------------- distinct path

def test_distinct_path_exact_past_capacity():
    # bounded vocabulary: past the exact buffer the tally path must still
    # reproduce the UNSAMPLED in-memory fit bit-for-bit at any n
    rng = np.random.default_rng(3)
    X = rng.integers(0, 40, (6000, 1)).astype(np.float64) / 7.0
    ref = BinMapper.fit(X, max_bin=25, min_data_in_bin=3)
    b = StreamingBinMapperBuilder(1, capacity=500)
    for lo in range(0, 6000, 900):
        b.update(X[lo:lo + 900])
    assert b._sketches[0].mode == "distinct"
    assert _mapper_equal(b.finalize(max_bin=25, min_data_in_bin=3), ref)


def test_weighted_quantile_matches_numpy_linear():
    rng = np.random.default_rng(4)
    distinct = np.unique(rng.normal(0, 3, 200))
    counts = rng.integers(1, 9, len(distinct)).astype(np.int64)
    expanded = np.repeat(distinct, counts)
    qs = np.linspace(0.0, 1.0, 41)[1:-1]
    got = _weighted_quantile(distinct, counts, qs)
    want = np.quantile(expanded, qs, method="linear")
    assert np.array_equal(got, want)          # bitwise, incl. _lerp branch


@pytest.mark.parametrize("case,draw,budget,min_data_in_bin", [
    ("quantiles", lambda rng: rng.normal(0, 3, 5_000), 63, 3),
    ("quantiles_under_a_floor", lambda rng: rng.lognormal(0, 2, 5_000),
     255, 50),
    ("own_bins", lambda rng: rng.integers(0, 40, 5_000), 63, 1),
    ("own_bins_merged", lambda rng: rng.integers(0, 250, 5_000), 255, 50),
    ("float32", lambda rng: rng.normal(0, 3, 5_000).astype(np.float32),
     63, 3),
])
def test_sorted_sample_and_summary_give_the_same_bounds(case, draw, budget,
                                                        min_data_in_bin):
    """The three ways into ``numeric_bin_bounds`` are one result: the sorted
    sample BinMapper.fit hands over, the sample in any order, and the
    (distinct, counts) summary the streaming sketch keeps."""
    vals = draw(np.random.default_rng(11))
    distinct, counts = np.unique(vals.astype(np.float64), return_counts=True)
    from_sorted = numeric_bin_bounds(budget, min_data_in_bin,
                                     sorted_vals=np.sort(vals))
    from_summary = numeric_bin_bounds(budget, min_data_in_bin,
                                      distinct=distinct, counts=counts)
    from_vals = numeric_bin_bounds(budget, min_data_in_bin, vals=vals)
    assert len(from_sorted) > 1
    assert from_sorted.tobytes() == from_summary.tobytes()
    assert from_sorted.tobytes() == from_vals.tobytes()


# ------------------------------------------------------------------- GK path

def _gk_rank_errors(summary, vals, qs):
    srt = np.sort(vals)
    n = len(vals)
    errs = []
    for q, v in zip(qs, summary.query(qs)):
        rank = np.searchsorted(srt, v, side="right")
        errs.append(abs(rank - q * n) / n)
    return np.asarray(errs)


def test_gk_intervals_stay_honest():
    # the load-bearing property: every tuple's TRUE rank sits inside its
    # claimed [rmin, rmin + d] (banding debt is widened into d, never
    # silently dropped) — the query error bound rests on this
    rng = np.random.default_rng(12)
    vals = rng.lognormal(0, 1, 40_000)
    sk = _FeatureSketch(capacity=1000, eps=5e-3, max_distinct=128)
    for lo in range(0, len(vals), 3000):
        sk.update(vals[lo:lo + 3000])
    assert sk.mode == "gk"
    srt = np.sort(vals)
    rmin = np.cumsum(sk.gk.g)
    for i, v in enumerate(sk.gk.v):
        rank = np.searchsorted(srt, v, side="right")
        assert rmin[i] <= rank <= rmin[i] + sk.gk.d[i]


def test_gk_path_rank_error_within_eps():
    rng = np.random.default_rng(5)
    vals = rng.normal(0, 1, 50_000)
    eps = 1e-2
    sk = _FeatureSketch(capacity=1000, eps=eps, max_distinct=256)
    for lo in range(0, len(vals), 4096):
        sk.update(vals[lo:lo + 4096])
    assert sk.mode == "gk"
    qs = np.linspace(0.0, 1.0, 101)[1:-1]
    errs = _gk_rank_errors(sk.gk, vals, qs)
    assert errs.max() <= eps
    # the summary stays compact: O(1/eps) tuples, not O(n)
    assert len(sk.gk.v) < 20 / eps


def test_gk_merge_bound():
    rng = np.random.default_rng(6)
    a_vals = rng.normal(0, 1, 20_000)
    b_vals = rng.normal(2, 1, 20_000)
    eps = 1e-2
    a, b = GKSummary(eps), GKSummary(eps)
    for s, vals in ((a, a_vals), (b, b_vals)):
        for lo in range(0, len(vals), 4096):
            dv, dc = np.unique(vals[lo:lo + 4096], return_counts=True)
            s.insert_distinct(dv, dc.astype(np.int64))
    a.merge(b)
    assert a.n == 40_000
    qs = np.linspace(0.0, 1.0, 51)[1:-1]
    # documented merged bound: eps·n_a + eps·n_b = 2·eps·n
    errs = _gk_rank_errors(a, np.concatenate([a_vals, b_vals]), qs)
    assert errs.max() <= 2 * eps


def test_gk_bounds_close_to_exact():
    rng = np.random.default_rng(7)
    vals = rng.normal(0, 1, 30_000)
    sk = _FeatureSketch(capacity=1000, eps=1e-3, max_distinct=64)
    sk.update(vals)
    ub = sk.bounds(budget=63, min_data_in_bin=3)
    exact = numeric_bin_bounds(63, 3, vals=vals)
    assert len(ub) == len(exact)
    # edges are quantiles of a smooth CDF: eps-rank error -> small value gap
    assert np.max(np.abs(ub - exact)) < 0.05


# ------------------------------------------------------- builder validation

def test_builder_validation():
    with pytest.raises(ValueError, match="num_features"):
        StreamingBinMapperBuilder(0)
    with pytest.raises(ValueError, match="eps"):
        StreamingBinMapperBuilder(3, eps=0.9)
    b = StreamingBinMapperBuilder(3)
    with pytest.raises(ValueError, match="ragged"):
        b.update(np.zeros((10, 4)))
    with pytest.raises(ValueError, match="2-D"):
        b.update(np.zeros((2, 3, 4)))
    with pytest.raises(ValueError, match="no rows"):
        StreamingBinMapperBuilder(3).finalize()


# ---------------------------------------------------- from_blocks validation

def _blocks(n=1024, f=5, nb=4, seed=0, with_y=True):
    rng = np.random.default_rng(seed)
    X = rng.normal(0, 1, (n, f)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    step = n // nb
    out = []
    for lo in range(0, n, step):
        if with_y:
            out.append((X[lo:lo + step], y[lo:lo + step]))
        else:
            out.append(X[lo:lo + step])
    return out


def test_from_blocks_rejects_one_shot_generator():
    gen = (b for b in _blocks())
    with pytest.raises(ValueError, match="one-shot generator"):
        Dataset.from_blocks(gen, params={"stream_block_rows": 256})


def test_from_blocks_rejects_ragged_features():
    blocks = _blocks(with_y=False)
    blocks[2] = blocks[2][:, :3]
    with pytest.raises(ValueError, match="feature"):
        Dataset.from_blocks(blocks,
                            params={"stream_block_rows": 256}).construct()


def test_from_blocks_rejects_dtype_mismatch():
    blocks = _blocks(with_y=False)
    blocks[1] = blocks[1].astype(np.float64)
    with pytest.raises(ValueError, match="dtype"):
        Dataset.from_blocks(blocks,
                            params={"stream_block_rows": 256}).construct()


def test_from_blocks_rejects_bad_tuple_and_double_label():
    blocks = _blocks()
    bad = blocks[:1] + [(blocks[1][0], blocks[1][1], None, None)]
    with pytest.raises(ValueError, match=r"\(X, y\)"):
        Dataset.from_blocks(bad, params={"stream_block_rows": 256})
    with pytest.raises(ValueError, match="label"):
        Dataset.from_blocks(_blocks(),
                            label=np.zeros(1024, np.float32),
                            params={"stream_block_rows": 256})


def test_from_blocks_rejects_empty_and_bad_block_rows():
    with pytest.raises(ValueError, match="no rows|empty"):
        Dataset.from_blocks([], params={"stream_block_rows": 256})
    with pytest.raises(ValueError, match="multiple"):
        Dataset.from_blocks(_blocks(), params={"stream_block_rows": 100})


def test_from_blocks_binned_codes_match_in_memory():
    rng = np.random.default_rng(11)
    X = rng.normal(0, 1, (1500, 6)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    params = {"max_bin": 63, "stream_block_rows": 512}
    ref = Dataset(X, label=y, params=dict(params)).construct()
    blocks = [(X[lo:lo + 512], y[lo:lo + 512]) for lo in range(0, 1500, 512)]
    ds = Dataset.from_blocks(blocks, params=dict(params)).construct()
    assert ds.is_streamed and ds.block_store is not None
    got = ds.block_store.gather_rows(np.arange(1500))
    want = np.asarray(ref.X_binned)[:1500]
    assert np.array_equal(got, want.astype(got.dtype))
    assert np.array_equal(np.asarray(ds.y)[:1500], y)   # y pads to 256-mult
