"""Binner + Dataset container tests."""

import jax
import numpy as np
import pytest

from lightgbm_tpu import dataset as dataset_mod
from lightgbm_tpu.dataset import (BinMapper, Dataset, ROW_PAD_MULTIPLE,
                                  code_block_rows, device_bin_codes)
from lightgbm_tpu.utils import profiling


def test_binner_few_distinct_values_get_own_bins():
    X = np.array([[1.0], [2.0], [2.0], [3.0], [1.0]])
    bm = BinMapper.fit(X, max_bin=255, min_data_in_bin=1)
    codes = bm.transform(X)
    assert codes[:, 0].tolist() == [0, 1, 1, 2, 0]
    assert bm.n_bins[0] == 3


def test_binner_min_data_in_bin_merges_sparse_values():
    # 3 distinct values with counts 5/1/5: the middle singleton cannot hold
    # its own bin at min_data_in_bin=3 (LightGBM GreedyFindBin behavior)
    X = np.array([[1.0]] * 5 + [[2.0]] + [[3.0]] * 5)
    bm = BinMapper.fit(X, max_bin=255, min_data_in_bin=3)
    codes = bm.transform(X)
    assert bm.n_bins[0] == 2
    assert codes[0, 0] != codes[-1, 0]


def test_binner_quantile_mode_monotone():
    rng = np.random.default_rng(0)
    X = rng.normal(0, 1, (10000, 1))
    bm = BinMapper.fit(X, max_bin=16)
    codes = bm.transform(X)
    assert codes.max() <= 15
    # monotone: larger raw value -> bin code >= smaller's
    order = np.argsort(X[:, 0])
    assert (np.diff(codes[order, 0].astype(int)) >= 0).all()
    # roughly equal-frequency bins
    counts = np.bincount(codes[:, 0], minlength=16)
    assert counts.min() > 10000 / 16 * 0.5


def test_binner_nan_gets_dedicated_bin():
    X = np.array([[1.0], [np.nan], [2.0], [3.0]])
    bm = BinMapper.fit(X, max_bin=255, min_data_in_bin=1)
    codes = bm.transform(X)
    assert codes[1, 0] == bm.nan_bin[0]
    assert codes[1, 0] == bm.n_bins[0] - 1


def test_binner_reused_for_valid_data():
    rng = np.random.default_rng(1)
    X = rng.normal(0, 1, (5000, 3))
    bm = BinMapper.fit(X, max_bin=64)
    X2 = rng.normal(0, 1, (100, 3))
    codes = bm.transform(X2)
    # out-of-range values clamp to edge bins
    lo = np.full((1, 3), -100.0)
    hi = np.full((1, 3), 100.0)
    assert (bm.transform(lo) == 0).all()
    assert (bm.transform(hi) == bm.n_bins - 1 - (bm.nan_bin >= 0)).all()


def test_dataset_construct_pads_rows():
    rng = np.random.default_rng(2)
    X = rng.normal(0, 1, (1000, 4))
    y = rng.normal(0, 1, 1000)
    ds = Dataset(X, label=y).construct()
    assert ds.num_data() == 1000
    assert ds.X_binned.shape[0] % ROW_PAD_MULTIPLE == 0
    assert float(ds.row_mask.sum()) == 1000
    assert float(ds.w[1000:].sum()) == 0.0


def test_dataset_reference_shares_bin_mapper():
    rng = np.random.default_rng(3)
    X = rng.normal(0, 1, (500, 2))
    y = rng.normal(0, 1, 500)
    dtrain = Dataset(X, label=y).construct()
    dvalid = Dataset(rng.normal(0, 1, (100, 2)), label=rng.normal(0, 1, 100),
                     reference=dtrain).construct()
    assert dvalid.bin_mapper is dtrain.bin_mapper


def test_dataset_subset():
    rng = np.random.default_rng(4)
    X = rng.normal(0, 1, (800, 3))
    y = rng.normal(0, 1, 800)
    ds = Dataset(X, label=y).construct()
    sub = ds.subset(np.arange(100))
    assert sub.num_data() == 100
    assert sub.bin_mapper is ds.bin_mapper
    np.testing.assert_allclose(sub.get_label(), y[:100])


def test_dataset_pandas_feature_names():
    pd = pytest.importorskip("pandas")
    df = pd.DataFrame({"a": [1.0, 2, 3, 4], "b": [4.0, 3, 2, 1]})
    ds = Dataset(df, label=[1.0, 2, 3, 4]).construct()
    assert ds.feature_names == ["a", "b"]


def test_categorical_binning():
    """LightGBM's categorical rule: categories ranked by count, none past
    the second seen fewer than ``min_data_in_bin`` times; the rest (and
    NaN) share the overflow bin after the kept ones, which lie in the
    order of their values."""
    X = np.array([[0.0], [1.0], [2.0], [2.0], [7.0], [np.nan]])
    bm = BinMapper.fit(X, max_bin=255, categorical=[0])
    codes = bm.transform(X)
    # 2 (twice) and 0 (the first of the ones seen once) are kept
    assert codes[:, 0].tolist() == [0, 2, 1, 1, 2, 2]
    assert bm.is_categorical[0] and bm.n_bins[0] == 3
    assert bm.nan_bin[0] == -1
    every = BinMapper.fit(X, max_bin=255, min_data_in_bin=1, categorical=[0])
    assert every.transform(X)[:, 0].tolist() == [0, 1, 2, 2, 3, 4]
    # at most max_bin - 1 categories: the most frequent
    few = BinMapper.fit(X, max_bin=2, min_data_in_bin=1, categorical=[0])
    assert few.upper_bounds[0].tolist() == [2.0]
    assert few.transform(X)[:, 0].tolist() == [1, 1, 0, 0, 1, 1]


# -- bin codes assigned on the device ---------------------------------------
F32 = np.float32
TINY = np.finfo(F32).smallest_subnormal
BLOCK = ROW_PAD_MULTIPLE       # rows of a block in these tests


def _mapper(bounds, nan_bins=None):
    """A numeric mapper of the given float64 bounds; ``nan_bins[f]`` true
    gives feature ``f`` a NaN bin."""
    bounds = [np.asarray(b, np.float64) for b in bounds]
    nan_bins = nan_bins or [False] * len(bounds)
    nan_bin = np.array([len(b) + 1 if has else -1
                        for b, has in zip(bounds, nan_bins)], np.int32)
    n_bins = np.array([len(b) + 1 + has for b, has in zip(bounds, nan_bins)],
                      np.int32)
    return BinMapper(bounds, nan_bin, n_bins)


def _table(columns, n):
    """float32 ``[n, F]``: each column's planted values first, then normal
    draws spread over the float32 range's middle."""
    rng = np.random.default_rng(len(columns) * 1000 + n)
    X = (rng.standard_normal((n, len(columns)))
         * 10.0 ** rng.integers(-3, 4, (n, 1))).astype(F32)
    for f, planted in enumerate(columns):
        planted = np.asarray(planted, F32)
        X[:len(planted), f] = planted
    return X


def _around(values):
    """Each value as float32 (rounded to nearest), and its float32
    neighbours on both sides."""
    with np.errstate(over="ignore"):
        v = np.asarray(values, np.float64).astype(F32)
    return np.concatenate([v, np.nextafter(v, F32(np.inf)),
                           np.nextafter(v, F32(-np.inf))])


SPECIALS = np.array([0.0, -0.0, TINY, -TINY, 1e-40, -1e-40, np.inf, -np.inf,
                     np.finfo(F32).max, np.finfo(F32).min, np.nan], F32)
NANS = np.array([0x7FC00000, 0xFFC00000, 0x7F800001, 0xFFFFFFFF],
                np.uint32).view(F32)
REPRESENTABLE = [-1.5, 0.25, 3.0, 1024.0]
NOT_REPRESENTABLE = [-1.0 / 3.0, 0.1, 0.1 + 1e-12, 16777217.0]
EDGES_254 = np.sort(np.random.default_rng(27).standard_normal(254))


def _planted_cases():
    wide = _around(EDGES_254[::16])
    return {
        "value_equal_to_bound_and_neighbours": (
            [REPRESENTABLE], [_around(REPRESENTABLE)], BLOCK),
        "bounds_not_float32_representable": (
            [NOT_REPRESENTABLE], [_around(NOT_REPRESENTABLE)], BLOCK),
        "signed_zeros": (
            [[0.0], [-0.0], [-1.0, 1.0]], [SPECIALS] * 3, BLOCK),
        "bounds_below_the_denormals": (
            [[-1e-50, 1e-50], [-1e-46, 0.0, 1e-46]], [SPECIALS] * 2, BLOCK),
        "denormal_bounds_and_values": (
            [[-3e-39, -float(TINY), float(TINY), 1e-40, 1.00001e-40, 3e-39]],
            [np.concatenate([SPECIALS, _around([1e-40, -3e-39, 3e-39])])],
            BLOCK),
        "infinite_values_and_huge_bounds": (
            [[-1e300, float(np.finfo(F32).min), float(np.finfo(F32).max),
              1e300], [-np.inf, 0.0, np.inf]],
            [np.concatenate([SPECIALS, _around([np.finfo(F32).max])])] * 2,
            BLOCK),
        "nan_with_nan_bin": (
            [[-1.0, 1.0], EDGES_254[:253]], [NANS, NANS], BLOCK, [True, True]),
        "nan_without_nan_bin_takes_zeros_bin": (
            [[-1.0, 1.0], [0.0], [0.5, 2.0], [-2.0, -0.5]], [NANS] * 4, BLOCK),
        "constant_column_has_no_edges": (
            [[], [0.0], []], [SPECIALS] * 3, BLOCK, [False, False, True]),
        "different_edge_counts": (
            [[0.0], EDGES_254[:7], EDGES_254, []], [SPECIALS, wide, wide, wide],
            BLOCK, [True, False, False, False]),
        "rows_not_a_multiple_of_block_or_pad": (
            [EDGES_254, REPRESENTABLE], [wide, SPECIALS], 2 * BLOCK + 77),
        "two_blocks": ([EDGES_254, REPRESENTABLE], [wide, SPECIALS],
                       2 * BLOCK),
        "last_block_overlaps_by_all_but_one_row": (
            [EDGES_254, REPRESENTABLE], [wide, SPECIALS], BLOCK + 1),
    }


@pytest.mark.parametrize("case", list(_planted_cases()))
def test_device_codes_are_the_hosts_bytes(case, monkeypatch):
    bounds, planted, n, *nan_bins = _planted_cases()[case]
    mapper = _mapper(bounds, *nan_bins)
    X = _table(planted, n)
    monkeypatch.setattr(dataset_mod, "CODE_BLOCK_VALUES", BLOCK * len(bounds))
    assert code_block_rows(len(bounds)) == BLOCK
    n_pad = -(-n // ROW_PAD_MULTIPLE) * ROW_PAD_MULTIPLE
    codes, blocks, conflicts = device_bin_codes(X, mapper, n_pad)
    assert conflicts is None             # no bundle, nothing to count
    codes = np.asarray(codes)
    host = mapper._transform_unbundled(X)
    assert codes.dtype == np.uint8 and codes.shape == (n_pad, len(bounds))
    assert codes[:n].tobytes() == host.tobytes()
    assert not codes[n:].any()
    assert blocks == -(-n // BLOCK)
    # the planted values reach more than one bin wherever there is an edge
    for f, b in enumerate(bounds):
        assert len(np.unique(host[:, f])) > (len(b) > 0)


def test_device_codes_of_a_fitted_mapper_with_missing_values(monkeypatch):
    rng = np.random.default_rng(5)
    X = rng.standard_normal((3 * BLOCK + 5, 5)).astype(F32)
    X[rng.random(X.shape) < 0.05] = np.nan
    X[:, 3] = np.round(X[:, 3])            # few distinct values: midpoints
    X[:, 4] = 7.0
    mapper = BinMapper.fit(X, max_bin=255, min_data_in_bin=1)
    assert (mapper.nan_bin[:4] >= 0).all()
    monkeypatch.setattr(dataset_mod, "CODE_BLOCK_VALUES", BLOCK * 5)
    codes, blocks, _ = device_bin_codes(X, mapper, 4 * BLOCK)
    assert blocks == 4
    assert np.asarray(codes)[:len(X)].tobytes() == \
        mapper._transform_unbundled(X).tobytes()


def _dense(n, num_features, dtype):
    return np.random.default_rng(n).standard_normal(
        (n, num_features)).astype(dtype)


def _bundling(n, num_features, dtype):
    X = np.zeros((n, num_features), dtype)
    for f in range(num_features):          # mutually exclusive, 95 % zeros
        X[f::20, f] = 1.0 + (np.arange(len(X[f::20])) % 7)
    return X


@pytest.mark.parametrize("case,make,kwargs,backend,patched,path", [
    ("float64", _dense, {}, "tpu", True, "host"),
    ("categorical_column", _dense, {"categorical_feature": [1]}, "tpu", True,
     "device"),
    ("bundling_sparse", _bundling, {}, "tpu", True, "device"),
    ("bundling_off_sparse", _bundling, {"params": {"enable_bundle": False}},
     "tpu", True, "device"),
    ("small_like_a_diamonds_fold", _dense, {}, "tpu", False, "host"),
    ("cpu_backend", _dense, {}, "cpu", True, "host"),
    ("large_dense_float32", _dense, {}, "tpu", True, "device"),
])
def test_codes_path_is_chosen_from_the_table(case, make, kwargs, backend,
                                             patched, path, monkeypatch):
    n, num_features = (45_900, 6) if not patched else (3 * BLOCK + 9, 6)
    X = make(n, num_features, np.float64 if case == "float64" else F32)
    # the choice asks which backend is the default; the tests' is the CPU
    assert jax.default_backend() == "cpu"
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if patched:      # three blocks and a part of this table; else as shipped
        monkeypatch.setattr(dataset_mod, "CODE_BLOCK_VALUES",
                            BLOCK * num_features)
    profiling.reset()
    ds = Dataset(X, label=np.zeros(n), **kwargs).construct()
    snap = profiling.snapshot()
    assert snap["facts"]["dataset.codes_path"] == path
    (codes_span,) = [r for r in snap["ring"]
                     if r["name"] == "lgbtpu.dataset.codes"]
    assert codes_span["fields"]["path"] == path
    assert codes_span["fields"]["blocks"] == (4 if path == "device" else 0)
    assert snap["counts"].get("dataset.codes.device_rows", 0) == \
        (n if path == "device" else 0)
    assert (ds.bin_mapper.bundler is not None) == (case == "bundling_sparse")
    # whichever path: the codes the host's mapper gives, above zero rows
    got = np.asarray(ds.X_binned)
    assert got[:n].tobytes() == ds.bin_mapper.transform(
        X.astype(np.float64)).tobytes()
    assert got.shape[0] % ROW_PAD_MULTIPLE == 0 and not got[n:].any()


def test_code_block_is_sized_in_values():
    assert dataset_mod.CODE_BLOCK_VALUES == 1 << 23
    assert code_block_rows(28) == 299_520
    assert code_block_rows(700) == 11_776
    assert code_block_rows(1 << 30) == ROW_PAD_MULTIPLE


# ------------------------------------------------ edges: one sort a column
#
# The ORACLE: BinMapper.fit as it stood until PR 38 (a float64 copy of each
# column's sample, np.unique for the distinct values, np.quantile for the
# bounds), kept here as the plain reference.  It shares nothing with the code
# under test; the fit has to give its bytes.

def _oracle_numeric_bounds(budget, min_data_in_bin, vals):
    distinct, counts = np.unique(vals, return_counts=True)
    n_vals = int(counts.sum())
    budget_eff = budget
    if min_data_in_bin > 1:
        budget_eff = max(1, min(budget, n_vals // min_data_in_bin))
    if len(distinct) <= budget_eff:
        mids = (distinct[:-1] + distinct[1:]) / 2.0
        if min_data_in_bin > 1 and len(distinct) > 1:
            keep, acc = [], 0
            for i in range(len(distinct) - 1):
                acc += counts[i]
                if acc >= min_data_in_bin and \
                        counts[i + 1:].sum() >= min_data_in_bin:
                    keep.append(mids[i])
                    acc = 0
            ub = np.asarray(keep)
        else:
            ub = mids
    else:
        qs = np.linspace(0.0, 1.0, budget_eff + 1)[1:-1]
        ub = np.unique(np.quantile(vals, qs, method="linear"))
        if len(ub) > 1:
            ub = ub[np.concatenate(([True], np.diff(ub) > 0))]
    return np.asarray(ub, dtype=np.float64)


def _oracle_fit(X, max_bin=255, min_data_in_bin=3, categorical=(),
                sample_cnt=200_000, seed=1):
    n, num_features = X.shape
    rng = np.random.default_rng(seed)
    if n > sample_cnt:
        idx = rng.choice(n, size=sample_cnt, replace=False)
    else:
        idx = slice(None)
    cat = set(int(c) for c in categorical)
    bounds = []
    nan_bin = np.full(num_features, -1, dtype=np.int32)
    n_bins = np.ones(num_features, dtype=np.int32)
    is_cat = np.zeros(num_features, dtype=bool)
    for f in range(num_features):
        col = np.asarray(X[idx, f], dtype=np.float64)
        has_nan = bool(np.isnan(col).any())
        vals = col[~np.isnan(col)]
        budget = max_bin - (1 if has_nan else 0)
        if f in cat:
            # LightGBM's categorical rule: by count, at most max_bin - 1,
            # none past the second under min_data_in_bin; NaN shares the
            # overflow bin
            is_cat[f] = True
            has_nan = False
            uniq, cnts = np.unique(vals, return_counts=True)
            keep = []
            for i in sorted(range(len(uniq)), key=lambda i: (-cnts[i], i)):
                if len(keep) == max_bin - 1 or (
                        len(keep) > 1 and cnts[i] < min_data_in_bin):
                    break
                keep.append(uniq[i])
            ub = np.sort(np.asarray(keep, np.float64))
        elif len(vals) == 0:
            ub = np.zeros(0)
        else:
            ub = _oracle_numeric_bounds(budget, min_data_in_bin, vals)
        ub = np.asarray(ub, dtype=np.float64)
        nb = len(ub) + 1
        if has_nan:
            nan_bin[f] = nb
            nb += 1
        bounds.append(ub)
        n_bins[f] = nb
    return BinMapper(bounds, nan_bin, n_bins, is_cat)


def _with_nan(share):
    def column(rng, n):
        col = rng.normal(0, 1, n)
        col[rng.random(n) < share] = np.nan
        return col
    return column


def _with_infs(each):
    def column(rng, n):
        col = rng.normal(0, 1, n)
        col[:each], col[each:2 * each] = np.inf, -np.inf
        return rng.permutation(col)
    return column


def _both_zeros(rng, n):
    col = rng.integers(-3, 4, n).astype(np.float64)
    col[(col == 0) & (rng.random(n) < 0.5)] = -0.0
    assert np.signbit(col[col == 0]).any() and not np.signbit(col[col == 0]).all()
    return col


def _ties_at_a_quantile(rng, n):
    col = rng.normal(0, 1, n)
    col[rng.random(n) < 0.4] = 0.25          # 40 % of the rows on one value
    return col


EDGE_COLUMNS = {
    "normal": lambda rng, n: rng.normal(0, 1, n),
    "lognormal": lambda rng, n: rng.lognormal(0, 2, n),
    "integers_12": lambda rng, n: rng.integers(0, 12, n).astype(np.float64),
    "integers_250": lambda rng, n: rng.integers(0, 250, n).astype(np.float64),
    "constant": lambda rng, n: np.full(n, 3.5),
    "all_nan": lambda rng, n: np.full(n, np.nan),
    "nan_1pct": _with_nan(0.01),
    "nan_60pct": _with_nan(0.6),
    "infs_few": _with_infs(3),
    "infs_many": _with_infs(150),
    "both_zeros": _both_zeros,
    "ties_at_a_quantile": _ties_at_a_quantile,
}
EDGE_ROWS = 5_000         # two gather tasks: EDGE_GATHER_ROWS and a part


def _edge_table(kinds, n=EDGE_ROWS, dtype=F32, seed=0):
    rng = np.random.default_rng(seed)
    return np.stack([EDGE_COLUMNS[k](rng, n) for k in kinds],
                    axis=1).astype(dtype)


def _mixed(num_features, **kwargs):
    kinds = [k for k in EDGE_COLUMNS if k != "both_zeros"]
    return _edge_table([kinds[f % len(kinds)] for f in range(num_features)],
                       **kwargs)


def _strided(dtype):
    wide = np.zeros((2 * EDGE_ROWS, 3 * 11), dtype)
    wide[::2, ::3] = _mixed(11, dtype=dtype)
    return wide[::2, ::3]


def _edge_cases():
    for kind in EDGE_COLUMNS:
        yield f"column_{kind}", lambda kind=kind: _edge_table([kind]), {}
    for min_data in (1, 3, 50):
        for max_bin in (16, 63, 255):
            yield (f"min_data_{min_data}_max_bin_{max_bin}",
                   lambda: _mixed(11),
                   {"min_data_in_bin": min_data, "max_bin": max_bin})
    for dtype in (F32, np.float64):
        name = np.dtype(dtype).name
        yield f"{name}_c_order", lambda dtype=dtype: _mixed(11, dtype=dtype), {}
        yield (f"{name}_f_order", lambda dtype=dtype: np.asfortranarray(
            _mixed(11, dtype=dtype)), {})
        yield f"{name}_strided_view", lambda dtype=dtype: _strided(dtype), {}
    yield "int64_table", lambda: np.random.default_rng(0).integers(
        -40, 400, (EDGE_ROWS, 3)), {}
    yield "fewer_rows_than_sample_cnt", lambda: _mixed(11), {}
    yield "more_rows_than_sample_cnt", lambda: _mixed(11), {
        "sample_cnt": 3_000}
    yield "no_rows", lambda: np.zeros((0, 3), F32), {}
    yield ("categorical_beside_numeric",
           lambda: _edge_table(["normal", "integers_12", "integers_250",
                                "nan_1pct"]), {"categorical": [1, 2],
                                               "max_bin": 63})
    for num_features in (1, 63, 64, 65, 129):   # a block is 64 columns
        yield (f"columns_{num_features}",
               lambda num_features=num_features: _mixed(num_features, n=600),
               {})
    for threads in (1, 4):
        yield f"threads_{threads}", lambda: _mixed(70), {"threads": threads}


@pytest.mark.parametrize("case,make,kwargs", [
    pytest.param(*c, id=c[0]) for c in _edge_cases()])
def test_fit_gives_the_oracles_edges(case, make, kwargs, monkeypatch):
    """One sort a column gives the edges that np.unique + np.quantile over a
    float64 copy gave, bit for bit, and so the same codes."""
    kwargs = dict(kwargs)
    if "threads" in kwargs:
        monkeypatch.setattr(dataset_mod, "EDGE_THREADS", kwargs.pop("threads"))
    X = make()
    before = X.tobytes()
    got = BinMapper.fit(X, **kwargs)
    want = _oracle_fit(X, **kwargs)
    assert X.tobytes() == before               # sorted in a copy, not in place
    assert got.num_features == want.num_features == X.shape[1]
    assert got.nan_bin.tobytes() == want.nan_bin.tobytes()
    assert got.n_bins.tobytes() == want.n_bins.tobytes()
    assert got.is_categorical.tolist() == want.is_categorical.tolist()
    for f, (ub, ref) in enumerate(zip(got.upper_bounds, want.upper_bounds)):
        assert ub.dtype == ref.dtype == np.float64
        if case == "column_both_zeros":
            # which zero a sort puts first is the sort's own, then as now:
            # equal values, not equal bytes
            assert np.array_equal(ub, ref), f
        else:
            assert ub.tobytes() == ref.tobytes(), (f, ub, ref)
    assert got._transform_unbundled(X).tobytes() == \
        want._transform_unbundled(X).tobytes()
    assert dataset_mod.EDGE_BLOCK_COLUMNS == 64
    blocks = -(-X.shape[1] // 64)
    sample_rows = min(len(X), kwargs.get("sample_cnt", 200_000))
    assert got.fit_counts["blocks"] == blocks
    assert got.fit_counts["sample_rows"] == sample_rows
    assert got.fit_counts["columns_sorted"] + \
        got.fit_counts["columns_other"] == X.shape[1]


def test_edges_span_says_what_the_fit_did():
    n = 900
    X = _edge_table(["normal", "integers_12", "all_nan", "lognormal",
                     "nan_60pct", "integers_250"], n=n)
    profiling.reset()
    Dataset(X, label=np.zeros(n), categorical_feature=[1]).construct()
    snap = profiling.snapshot()
    assert snap["facts"]["dataset.edges_path"] == "one_sort"
    (edges_span,) = [r for r in snap["ring"]
                     if r["name"] == "lgbtpu.dataset.edges"]
    assert edges_span["fields"] == {
        "columns_sorted": 4, "columns_other": 2, "blocks": 1,
        "sample_rows": n}
