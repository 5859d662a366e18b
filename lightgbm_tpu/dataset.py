"""Binned dataset container — the `lgb.Dataset` equivalent.

Reference contract (SURVEY.md §2B): ``lgb.Dataset(X, label=)`` wraps a dense
numeric matrix + label, lazily binned with ≤``max_bin`` (default 255) bins per
feature, and is reusable across many trainings (the reference reuses one
``dtrain`` across a 108-config sweep — r/gridsearchCV.R:52,108).

TPU-first design (SURVEY.md §7): the binned matrix is a device-resident
``uint8[rows_padded, features]`` with rows padded to a lane-friendly multiple
so it can later be row-sharded over a ``jax.sharding.Mesh`` without reshapes.
Labels/weights ride alongside as f32.  The bin EDGES (a one-time, per-feature
quantile sketch of a 200k-row sample) are found on host in numpy — O(n log n)
scalar work that XLA has no advantage on — and give the bin-upper-bound table
used both for training data and for mapping validation/prediction inputs into
the same bins.  The bin CODES of a large dense float32 table are assigned on
the device, in fixed-size row blocks (:func:`device_bin_codes`); every other
table is coded by the host loop (:meth:`BinMapper._transform_unbundled`).  The
two give the same bytes.
"""

from __future__ import annotations

import functools
import hashlib
import os
import warnings
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from .config import Params, parse_params
from .utils import profiling
from .utils.profiling import span

ROW_PAD_MULTIPLE = 256  # lane-friendly and shard-friendly (divides by 2,4,8 devices)
# Values of one row block of the device's bin-code pass: 32 MiB of float32 a
# transfer, two in flight.  A table of fewer rows than one block is coded by
# the host loop, which costs it under a second (0.1 us a value) where the
# device's program would first have to be built for its shape.
CODE_BLOCK_VALUES = 1 << 23
# Columns of one block of :meth:`BinMapper.fit`'s sample (at most 51 MB of
# float32, the sample being at most ``sample_cnt`` rows), the rows one task
# gathers into it (a task's rows by the block's columns stay in cache until
# they are written out turned), and the threads that gather and sort.  None
# of the three changes a result.
EDGE_BLOCK_COLUMNS = 64
EDGE_GATHER_ROWS = 4096
EDGE_THREADS = min(8, os.cpu_count() or 1)


class FeatureBundler:
    """Exclusive Feature Bundling (EFB) — LightGBM's sparse-feature trick.

    Mutually-exclusive sparse features (rarely non-default on the same row)
    are merged into one histogram column whose bin axis concatenates the
    members' non-default bin ranges; histogram passes then scale with the
    number of BUNDLES, not features (upstream ``FindGroups``/``EFB`` in
    dataset construction; SURVEY.md §2C EFB row, BASELINE.md Criteo config).

    TPU-native formulation: bundling is a recoding at bin time (uint8 in,
    uint8 out), made on the device with the codes where the table allows
    it (:func:`device_bin_codes`), so the kernels histogram fewer columns.
    A tree still splits on ONE original feature, as upstream's do: the
    split scan reads each member's histogram out of its column's
    (``ops.members``), and rows route by the range of merged codes that
    the member's split sends left.  Trees, dumps and predictions are in
    the original feature space; the bundles are a training-time layout.

    ``groups`` covers every original feature exactly once; singleton groups
    pass through unchanged.  Merged code layout per multi-feature group:
    bin 0 = every member at its default bin; member j's non-default bins
    occupy ``[offset_j, offset_j + n_bins_j - 2]`` (its default bin is
    squeezed out).  Conflicting rows (two members non-default — allowed up
    to ``max_conflict_rate``) keep the LAST member's value.
    """

    SAMPLE_ROWS = 50_000   # leading rows whose codes :meth:`fit` reads

    def __init__(self, groups: List[List[int]], member_bins: np.ndarray,
                 default_bins: np.ndarray):
        self.groups = [list(map(int, g)) for g in groups]
        self.member_bins = np.asarray(member_bins, np.int64)
        self.default_bins = np.asarray(default_bins, np.int64)
        self.offsets: List[Optional[np.ndarray]] = []
        self.col_bins: List[int] = []
        for g in self.groups:
            if len(g) == 1:
                self.offsets.append(None)
                self.col_bins.append(int(self.member_bins[g[0]]))
            else:
                offs, o = [], 1
                for f in g:
                    offs.append(o)
                    o += int(self.member_bins[f]) - 1
                self.offsets.append(np.asarray(offs, np.int64))
                self.col_bins.append(o)

    @property
    def num_columns(self) -> int:
        return len(self.groups)

    @property
    def max_col_bins(self) -> int:
        return max(self.col_bins)

    @property
    def num_bundled(self) -> int:
        """Original features that share their column with another."""
        return sum(len(g) for g in self.groups if len(g) > 1)

    def merge(self, codes: np.ndarray) -> np.ndarray:
        """Original per-feature codes [n, F] -> bundled codes [n, B]."""
        out = np.zeros((codes.shape[0], len(self.groups)), np.uint8)
        for c, g in enumerate(self.groups):
            if len(g) == 1:
                out[:, c] = codes[:, g[0]]
                continue
            col = np.zeros(codes.shape[0], np.int64)
            for f, o in zip(g, self.offsets[c]):
                cf = codes[:, f].astype(np.int64)
                dflt = self.default_bins[f]
                nz = cf != dflt
                adj = cf - (cf > dflt)
                col = np.where(nz, o + adj, col)
            out[:, c] = col.astype(np.uint8)
        return out

    def conflict_rows(self, codes: np.ndarray) -> int:
        """Rows of original codes [n, F] on which two members of one
        bundle are off their default bins."""
        hit = np.zeros(codes.shape[0], bool)
        for g in self.groups:
            if len(g) > 1:
                nd = codes[:, g] != self.default_bins[g][None, :]
                hit |= np.count_nonzero(nd, axis=1) > 1
        return int(np.count_nonzero(hit))

    def device_tables(self) -> dict:
        """Slot tables of :func:`device_bin_codes`' merge, ``[B, J]`` for J
        the largest group: slot j of column c is its j-th member (``feat``),
        whose non-default codes land at ``off`` + code - (code > ``dflt``)
        and whose default gives way to the slots before it; a column of one
        feature is its feature's codes (``dflt`` past every code), the
        unused slots have ``valid`` 0.  Members merge in group order, so a
        conflicting row keeps the last one's code, as :meth:`merge`."""
        width = max(len(g) for g in self.groups)
        shape = (len(self.groups), width)
        feat = np.zeros(shape, np.int32)
        off = np.zeros(shape, np.int32)
        dflt = np.full(shape, 1 << 16, np.int32)
        valid = np.zeros(shape, bool)
        for c, g in enumerate(self.groups):
            feat[c, :len(g)] = g
            valid[c, :len(g)] = True
            if len(g) > 1:
                off[c, :len(g)] = self.offsets[c]
                dflt[c, :len(g)] = self.default_bins[g]
        return dict(feat=feat, off=off, dflt=dflt, valid=valid)

    @staticmethod
    def fit(codes: np.ndarray, n_bins: np.ndarray,
            max_conflict_rate: float = 0.0, max_merged_bins: int = 256,
            sparse_threshold: float = 0.8, sample: int = SAMPLE_ROWS,
            exclude: Optional[np.ndarray] = None
            ) -> Optional["FeatureBundler"]:
        """Greedy conflict-bounded bundling (upstream FindGroups).

        Only sufficiently sparse features (default-bin frequency >=
        ``sparse_threshold``, LightGBM's kSparseThreshold) are candidates;
        returns None when no multi-feature bundle forms (bundling dense
        data would only distort histograms for zero gain).

        Candidates are placed most non-default first, ties broken by a
        digest of the column's sample codes and then its bin count, never
        by its position: any order of a table's columns gives the same
        bundles, in that order (a column identical to another may trade
        places with it).  Each is placed into the first bundle it fits,
        its conflicts with every bundle counted at once over bit-packed
        rows.
        """
        n, num_features = codes.shape
        if num_features < 3:
            return None
        samp = codes[: min(n, sample)]
        ns = len(samp)
        default_bins = np.array(
            [np.bincount(samp[:, f], minlength=int(n_bins[f])).argmax()
             for f in range(num_features)], np.int64)
        nondef = samp != default_bins[None, :]
        nd_count = nondef.sum(axis=0)
        eligible = nd_count <= (1.0 - sparse_threshold) * ns
        if exclude is not None:
            eligible &= ~np.asarray(exclude, bool)
        budget = max_conflict_rate * ns

        cand = np.flatnonzero(eligible)
        digest = np.array([int.from_bytes(hashlib.blake2b(
            np.ascontiguousarray(samp[:, f]).tobytes(),
            digest_size=8).digest(), "little") for f in cand], np.uint64)
        order = cand[np.lexsort((np.asarray(n_bins)[cand], digest,
                                 -nd_count[cand]))]
        words = np.packbits(nondef[:, order], axis=0, bitorder="little")
        words = np.ascontiguousarray(words.T)     # [candidates, ceil(ns/8)]
        occ = np.zeros((len(order), words.shape[1]), np.uint8)
        conflicts = np.zeros(len(order), np.int64)
        bins = np.zeros(len(order), np.int64)
        members: List[List[int]] = []
        for i, f in enumerate(order):
            f, nb = int(f), int(n_bins[f])
            k = len(members)
            extra = np.bitwise_count(occ[:k] & words[i]).sum(
                axis=1, dtype=np.int64)
            fits = np.flatnonzero((conflicts[:k] + extra <= budget)
                                  & (bins[:k] + nb - 1 <= max_merged_bins))
            if len(fits):
                b = int(fits[0])
                members[b].append(f)
                conflicts[b] += extra[b]
            else:
                b = k
                members.append([f])
                bins[b] = 1
            occ[b] |= words[i]
            bins[b] += nb - 1
        multi = [m for m in members if len(m) > 1]
        if not multi:
            return None
        bundled_feats = {f for m in multi for f in m}
        groups = [[f] for f in range(num_features) if f not in bundled_feats]
        groups += [sorted(m) for m in multi]
        return FeatureBundler(groups, n_bins, default_bins)


def _linear_quantile(n: int, qs: np.ndarray, value_at) -> np.ndarray:
    """``np.quantile(v, qs, method="linear")`` of ``n`` values, given
    ``value_at(positions)`` of their ascending order.

    Replicates numpy's linear interpolation bit-for-bit (virtual index
    ``h = q*(n-1)``, and numpy's ``_lerp`` computes ``b - (b-a)*(1-t)``
    when ``t >= 0.5`` instead of ``a + (b-a)*t`` — the branch matters for
    bitwise parity).
    """
    h = np.asarray(qs, np.float64) * (n - 1)
    lo = np.floor(h).astype(np.int64)
    gamma = h - lo
    hi = np.minimum(lo + 1, n - 1)
    v_lo = value_at(lo)
    v_hi = value_at(hi)
    d = v_hi - v_lo
    return np.where(gamma >= 0.5, v_hi - d * (1.0 - gamma),
                    v_lo + d * gamma)


def _weighted_quantile(distinct: np.ndarray, counts: np.ndarray,
                       qs: np.ndarray) -> np.ndarray:
    """:func:`_linear_quantile` on weighted distinct values WITHOUT
    expanding them, so the streaming sketch's bounded-distinct path yields
    the SAME bounds the in-memory fit would have produced from the expanded
    sample (tests/test_sketch.py pins this against np.quantile).
    """
    cum = np.cumsum(counts)                 # value i ends at position cum[i]-1
    return _linear_quantile(
        int(counts.sum()), qs,
        lambda at: distinct[np.searchsorted(cum, at, side="right")])


def numeric_bin_bounds(budget: int, min_data_in_bin: int,
                       vals: Optional[np.ndarray] = None,
                       distinct: Optional[np.ndarray] = None,
                       counts: Optional[np.ndarray] = None,
                       sorted_vals: Optional[np.ndarray] = None
                       ) -> np.ndarray:
    """Numeric-feature bound finder shared by :meth:`BinMapper.fit` and the
    streaming sketch builder (``data.sketch``).

    Given the finite sample in ascending order (``sorted_vals``, float32 or
    float64), in any order (``vals``: sorted here) or as its ``(distinct,
    counts)`` summary, honors ``min_data_in_bin`` (budget cap + greedy
    sparse-bin merge) exactly as the historical in-memory fit did.  The
    distinct values are where neighbours of the sorted sample differ, and
    the quantiles are read off it by :func:`_linear_quantile` (off the
    summary by :func:`_weighted_quantile`), which is ``np.quantile``'s
    arithmetic: the streaming builder is bit-compatible with the in-memory
    fit whenever both see the same sample.
    """
    if vals is not None:
        sorted_vals = np.sort(vals)
    if sorted_vals is not None:
        n_vals = len(sorted_vals)
        # a distinct value starts where a neighbour differs: what
        # np.unique(..., return_counts=True) does after its sort
        starts = np.empty(n_vals, bool)
        starts[:1] = True
        np.not_equal(sorted_vals[1:], sorted_vals[:-1], out=starts[1:])
        n_distinct = np.count_nonzero(starts)
    else:
        n_vals = int(counts.sum())
        n_distinct = len(distinct)
    if n_vals == 0:
        return np.zeros(0)
    budget_eff = budget
    if min_data_in_bin > 1:
        budget_eff = max(1, min(budget, n_vals // min_data_in_bin))
    if n_distinct <= budget_eff:
        if sorted_vals is not None:
            starts = np.flatnonzero(starts)
            distinct = sorted_vals[starts].astype(np.float64)   # exact
            counts = np.diff(starts, append=n_vals)
        mids = (distinct[:-1] + distinct[1:]) / 2.0
        if min_data_in_bin > 1 and len(distinct) > 1:
            # greedily merge adjacent sparse distinct values until each
            # bin reaches the floor
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
        if sorted_vals is not None:
            ub = _linear_quantile(
                n_vals, qs, lambda at: sorted_vals[at].astype(np.float64))
        else:
            ub = _weighted_quantile(distinct, counts, qs)
        ub = np.unique(ub)
        # drop near-duplicate bounds
        if len(ub) > 1:
            ub = ub[np.concatenate(([True], np.diff(ub) > 0))]
    return np.asarray(ub, dtype=np.float64)


class BinMapper:
    """Per-feature quantile binning table (LightGBM BinMapper equivalent).

    For each feature stores ascending ``upper_bounds`` such that raw value v
    maps to bin ``searchsorted(upper_bounds, v, side='left')``; the last bound
    is +inf.  NaN maps to the dedicated last bin (index ``n_bins-1``) when the
    feature has missing values, else to the bin of 0.0.

    Two implementations assign codes, and give the same bytes.  The host loop
    (:meth:`_transform_unbundled`: one float64 ``searchsorted`` a feature)
    takes any table, and is what prediction, serving and ``from_blocks``
    run: codes in the original feature space, the space trees split in.
    ``Dataset.construct`` hands a float32 table of at least one row block
    (``CODE_BLOCK_VALUES``) to :func:`device_bin_codes`, which counts on
    the device the bounds below each value, from :meth:`device_tables`
    (matches a categorical column's value against its categories), and
    merges an EFB table's bundles in the same program.
    """

    def __init__(self, upper_bounds: List[np.ndarray], nan_bin: np.ndarray,
                 n_bins: np.ndarray, is_categorical: Optional[np.ndarray] = None):
        self.upper_bounds = upper_bounds          # list of f64[n_bins_f - 1] finite bounds
        self.nan_bin = nan_bin                    # i32[F]: bin index for NaN (or -1)
        self.n_bins = n_bins                      # i32[F]: bins actually used per feature
        self.num_features = len(upper_bounds)
        self.is_categorical = (
            is_categorical if is_categorical is not None
            else np.zeros(self.num_features, dtype=bool)
        )
        self.bundler: Optional[FeatureBundler] = None  # EFB (attach post-fit)
        # what :meth:`fit` did (the fields of the span ``lgbtpu.dataset.edges``)
        self.fit_counts: Dict[str, int] = {}

    @property
    def max_num_bins(self) -> int:
        if self.bundler is not None:
            return self.bundler.max_col_bins
        return int(self.n_bins.max()) if len(self.n_bins) else 1

    @staticmethod
    def fit(
        X: np.ndarray,
        max_bin: int = 255,
        min_data_in_bin: int = 3,
        categorical: Sequence[int] = (),
        sample_cnt: int = 200_000,
        seed: int = 1,
    ) -> "BinMapper":
        """Build bin bounds per feature via (sampled) quantiles.

        Mirrors LightGBM's GreedyFindBin behavior loosely: distinct values get
        their own bins when few; otherwise equal-frequency quantile bins;
        a dedicated NaN bin is appended when the feature has missing values.

        The sample is taken ``EDGE_BLOCK_COLUMNS`` columns at a time, laid
        out so that each column's values are contiguous, and each column is
        sorted ONCE, in the table's own dtype; its NaN count, its distinct
        values and its quantiles are all read off the sorted array.  The
        gather and the sorts release the GIL and run on a small pool; what
        comes out depends on neither the pool nor the block.
        """
        n, num_features = X.shape
        rng = np.random.default_rng(seed)
        if n > sample_cnt:
            # a column is sorted, so its sample may be read in the table's
            # row order, which walks the memory forward
            idx = np.sort(rng.choice(n, size=sample_cnt, replace=False))
        else:
            idx = np.arange(n)
        dtype = X.dtype if X.dtype in (np.float32, np.float64) else np.float64
        is_cat = np.isin(np.arange(num_features),
                         [int(c) for c in categorical])
        bounds: List[np.ndarray] = []
        nan_bin = np.full(num_features, -1, dtype=np.int32)
        n_bins = np.ones(num_features, dtype=np.int32)
        columns_sorted = 0
        sample = np.empty((min(EDGE_BLOCK_COLUMNS, num_features), len(idx)),
                          dtype)

        def gather(block, f0, r0):
            rows = idx[r0:r0 + EDGE_GATHER_ROWS]
            block[:, r0:r0 + len(rows)] = X[rows, f0:f0 + len(block)].T

        with ThreadPoolExecutor(EDGE_THREADS) as pool:
            for f0 in range(0, num_features, EDGE_BLOCK_COLUMNS):
                block = sample[:min(EDGE_BLOCK_COLUMNS, num_features - f0)]
                # reading the results raises what a task raised
                list(pool.map(functools.partial(gather, block, f0),
                              range(0, len(idx), EDGE_GATHER_ROWS)))
                list(pool.map(np.ndarray.sort, block))          # NaNs last
                for f, col in enumerate(block, start=f0):
                    vals = col[:np.searchsorted(col, np.nan)]
                    # a categorical column's NaN shares the overflow bin
                    has_nan = len(vals) < len(col) and not is_cat[f]
                    ub = BinMapper._sorted_column_bounds(
                        vals, max_bin - (1 if has_nan else 0),
                        min_data_in_bin, is_cat[f])
                    n_bins[f] = len(ub) + 1
                    if has_nan:
                        nan_bin[f] = n_bins[f]
                        n_bins[f] += 1
                    bounds.append(ub)
                    columns_sorted += len(vals) > 0 and not is_cat[f]
        mapper = BinMapper(bounds, nan_bin, n_bins, is_cat)
        mapper.fit_counts = dict(
            columns_sorted=columns_sorted,
            columns_other=num_features - columns_sorted,
            blocks=-(-num_features // EDGE_BLOCK_COLUMNS),
            sample_rows=len(idx))
        return mapper

    @staticmethod
    def _sorted_column_bounds(vals: np.ndarray, budget: int,
                              min_data_in_bin: int,
                              categorical: bool) -> np.ndarray:
        """One column's bounds from its finite sample in ascending order
        (a categorical column's: the values of the categories it keeps,
        :meth:`category_values`)."""
        if categorical:
            ub = BinMapper.category_values(vals, budget, min_data_in_bin)
        else:
            # honor min_data_in_bin (LightGBM GreedyFindBin) — shared
            # with the streaming sketch builder (data.sketch), which
            # must stay bit-compatible with this in-memory path
            ub = numeric_bin_bounds(budget, min_data_in_bin,
                                    sorted_vals=vals)
        return np.asarray(ub, dtype=np.float64)

    @staticmethod
    def category_values(vals: np.ndarray, max_bin: int,
                        min_data_in_bin: int) -> np.ndarray:
        """The categories a categorical column keeps, by LightGBM's
        categorical ``BinMapper::FindBin``: the sample's distinct values
        ranked by count (ties: the smaller value first), at most ``max_bin
        - 1`` of them, and none past the second whose count is under
        ``min_data_in_bin``.  Returned in ascending order: a value's bin is
        its place among them (the exact-match lookup of
        :meth:`_transform_unbundled` and :func:`device_bin_codes`); every
        other value, and NaN, has the overflow bin after them, which no
        category set of a split holds.

        Departures from LightGBM:

        * the cap.  LightGBM's loop goes on while the kept categories cover
          less than 99 % of the sampled rows OR fewer than ``max_bin`` bins
          are used (``used_cnt < cut_cnt || num_bin_ < max_bin``), so where
          the ``max_bin - 1`` most common categories cover less than 99 %
          it keeps more categories, past ``max_bin``, until they do (or the
          ``min_data_in_bin`` cut stops it).  Here at most ``max_bin - 1``
          are kept and the rest share the overflow bin, because a code is
          one byte and the histogram kernels' one-hots are at most 256
          bins tall.  This moves splits: a category past the cap can never
          be sent left on its own;
        * none of the others moves a split: its bins follow the count
          ranking and its overflow bin is bin 0, here the bins follow the
          values and the overflow bin is the last (a subset split does not
          read the bins' order); it casts values to int and sends negative
          ones to NaN, here a category is a float32 value matched
          exactly."""
        uniq, cnts = np.unique(vals, return_counts=True)
        rank = np.argsort(-cnts, kind="stable")[:max(max_bin - 1, 0)]
        rare = np.flatnonzero(cnts[rank] < min_data_in_bin)
        rare = rare[rare > 1]
        if len(rare):
            rank = rank[:rare[0]]
        return np.sort(uniq[rank])

    def transform(self, X: np.ndarray) -> np.ndarray:
        """Map raw features to the training layout's codes uint8[n, C]:
        bundled columns when EFB is active (prediction walks the original
        features' codes, :meth:`_transform_unbundled`)."""
        codes = self._transform_unbundled(X)
        if self.bundler is not None:
            return self.bundler.merge(codes)
        return codes

    def _transform_unbundled(self, X: np.ndarray) -> np.ndarray:
        n, num_features = X.shape
        assert num_features == self.num_features, (
            f"feature count mismatch: {num_features} vs {self.num_features}")
        out = np.empty((n, num_features), dtype=np.uint8)
        for f in range(num_features):
            col = np.asarray(X[:, f], dtype=np.float64)
            if self.is_categorical[f]:
                cats = self.upper_bounds[f]
                idx = np.searchsorted(cats, col).clip(0, max(len(cats) - 1, 0))
                if len(cats) > 0:
                    hit = cats[idx] == col
                    codes = np.where(hit, idx, len(cats))  # overflow bin
                else:
                    codes = np.zeros(n, dtype=np.int64)
            else:
                codes = np.searchsorted(self.upper_bounds[f], col, side="left")
            if self.nan_bin[f] >= 0:
                codes = np.where(np.isnan(col), self.nan_bin[f], codes)
            elif not self.is_categorical[f]:
                # no NaN seen at fit time: LightGBM converts missing to zero
                # (BinMapper::ValueToBin with missing_type=None — ADVICE r1),
                # i.e. NaN lands in the bin containing 0.0
                zero_bin = int(np.searchsorted(self.upper_bounds[f], 0.0,
                                               side="left"))
                codes = np.where(np.isnan(col), zero_bin, codes)
            # (categorical NaN already routed to the overflow bin above)
            out[:, f] = codes.astype(np.uint8)
        return out

    def device_tables(self):
        """``(edge_keys i32[F, E], nan_code i32[F], cats)`` for
        :func:`device_bin_codes`.

        A float32 ``x`` is above a float64 bound ``u`` iff it is above
        ``d(u)``, ``u`` rounded to float32 toward -inf, and two float32
        order as their :func:`_order_keys` do, so ``searchsorted(u, x,
        "left")`` is the count of ``key(x) > key(d(u_j))``.  Rows are padded
        to the widest edge count with the largest key, which nothing is
        above.  ``nan_code`` is where :meth:`_transform_unbundled` sends
        NaN: the NaN bin, or the bin of 0.0 where fit saw no NaN (a
        categorical column's overflow bin).

        A categorical column's row holds its categories' values: the count
        below a value is its category's place, and the value is the
        category only where its key is one of ``cats["keys"]`` (the key of
        a category that float32 holds exactly; the largest key, which only
        a NaN has, where it does not), else it has the overflow bin,
        ``cats["overflow"]`` (-1 for a numeric column).  ``cats`` is
        ``None`` for a table with no categorical column."""
        width = max([len(ub) for ub in self.upper_bounds] + [1])
        top = np.iinfo(np.int32).max
        edge_keys = np.full((self.num_features, width), top, np.int32)
        nan_code = np.asarray(self.nan_bin, np.int32).copy()
        has_cats = bool(self.is_categorical.any())
        cat_keys = np.full_like(edge_keys, top) if has_cats else None
        overflow = np.full(self.num_features, -1, np.int32)
        for f, ub in enumerate(self.upper_bounds):
            ub = np.asarray(ub, np.float64)
            with np.errstate(over="ignore"):
                down = ub.astype(np.float32)
            exact = down.astype(np.float64) == ub
            above = down.astype(np.float64) > ub
            down[above] = np.nextafter(down[above], np.float32(-np.inf))
            # a NaN bound is above every value on the host too
            edge_keys[f, :len(ub)] = np.where(
                np.isnan(ub), edge_keys[f, :len(ub)],
                _order_keys(down.view(np.int32)))
            if self.is_categorical[f]:
                overflow[f] = len(ub)
                cat_keys[f, :len(ub)] = np.where(
                    exact, edge_keys[f, :len(ub)], top)
                if nan_code[f] < 0:
                    nan_code[f] = len(ub)
            elif nan_code[f] < 0:
                nan_code[f] = np.searchsorted(ub, 0.0, side="left")
        cats = (None if not has_cats
                else {"keys": cat_keys, "overflow": overflow})
        return edge_keys, nan_code, cats

    def bin_upper_bound(self, feature: int, bin_idx: int) -> float:
        """Raw-value threshold corresponding to `bin <= bin_idx` (for model dump)."""
        ub = self.upper_bounds[feature]
        if bin_idx < len(ub):
            return float(ub[bin_idx])
        return float("inf")

    # -- persistence glue (single JSON schema shared by the model file and
    # the packed serving artifact — utils.serialize owns the layout) -------
    def to_dict(self) -> dict:
        from .utils.serialize import mapper_to_dict
        return mapper_to_dict(self)

    @staticmethod
    def from_dict(d: dict) -> "BinMapper":
        from .utils.serialize import mapper_from_dict
        return mapper_from_dict(d)


_F32_MAGNITUDE = 0x7FFFFFFF
_F32_INF = 0x7F800000


def _order_keys(bits):
    """float32 bit patterns (int32, numpy or jax) -> int32 that order as the
    floats do: sign and magnitude to two's complement, so -0.0 and +0.0
    share key 0 and denormals keep their place whatever the device does to
    them in float arithmetic.  NaNs lie beyond both infinities."""
    mag = bits & _F32_MAGNITUDE
    return (bits >> 31 ^ mag) - (bits >> 31)


def code_block_rows(num_features: int) -> int:
    """Rows of one block of :func:`device_bin_codes`."""
    rows = CODE_BLOCK_VALUES // max(int(num_features), 1)
    return max(rows // ROW_PAD_MULTIPLE, 1) * ROW_PAD_MULTIPLE


def _block_codes(bits, edge_keys, nan_code, cats=None):
    """``int32[F, B]``: the codes of one row block (``bits[B, F]``: the bit
    patterns of its float32 values).  Integer compares only; rows lie on
    the minor axis while the edges are counted.  ``cats``
    (:meth:`BinMapper.device_tables`): a categorical column's value that
    is none of its categories takes the overflow bin."""
    key = _order_keys(bits).T                                   # [F, B]
    above = key[:, None, :] > edge_keys[:, :, None]
    code = jnp.sum(above, axis=1, dtype=jnp.int32)
    if cats is not None:
        hit = jnp.any(key[:, None, :] == cats["keys"][:, :, None], axis=1)
        over = cats["overflow"][:, None]
        code = jnp.where((over >= 0) & ~hit, over, code)
    is_nan = (key > _F32_INF) | (key < -_F32_INF)
    return jnp.where(is_nan, nan_code[:, None], code)


@functools.partial(jax.jit, donate_argnums=0)
def _write_code_block(codes, bits, at, edge_keys, nan_code, cats=None):
    """One row block's codes written into rows ``at...`` of ``codes``."""
    code = _block_codes(bits, edge_keys, nan_code, cats)
    codes = jax.lax.dynamic_update_slice(
        codes, code.T.astype(jnp.uint8), (at, 0))
    return codes, at


@functools.partial(jax.jit, donate_argnums=0)
def _write_bundled_block(codes, bits, at, skip, edge_keys, nan_code, slots,
                         cats=None):
    """:func:`_write_code_block` for a bundled table: the block's codes of
    every original feature merged into its columns in the same program,
    slot by slot of ``FeatureBundler.device_tables`` (``slots``), and the
    block's rows from ``skip`` on on which two members of one column are
    off their defaults counted (the last block overlaps the one before)."""
    code = _block_codes(bits, edge_keys, nan_code, cats)

    def slot(j, carry):
        merged, nondefault = carry
        member = code[slots["feat"][:, j]]                      # [C, B]
        d = slots["dflt"][:, j, None]
        on = (member != d) & slots["valid"][:, j, None]
        merged = jnp.where(on, slots["off"][:, j, None] + member
                           - (member > d), merged)
        return merged, nondefault + on

    zero = jnp.zeros((slots["feat"].shape[0], code.shape[1]), jnp.int32)
    merged, nondefault = jax.lax.fori_loop(
        0, slots["feat"].shape[1], slot, (zero, zero))
    rows_new = jnp.arange(code.shape[1]) >= skip
    conflicts = jnp.sum(jnp.any(nondefault > 1, axis=0) & rows_new,
                        dtype=jnp.int32)
    codes = jax.lax.dynamic_update_slice(
        codes, merged.T.astype(jnp.uint8), (at, 0))
    return codes, conflicts


def device_bin_codes(X: np.ndarray, mapper: BinMapper, n_pad: int):
    """``uint8[n_pad, C]`` on the device: the bytes of ``mapper.transform
    (X)`` above ``n_pad - len(X)`` rows of zeros, for a float32 ``X`` of at
    least one block's rows; a bundled table's columns are merged in the
    block's own program.

    One program for one block shape; the last block ends at the last row
    and so overlaps the one before it.  A block's transfer is in flight
    while the block before it is coded, and a third is not sent before the
    first is done, so the table is never whole on the device.  Returns the
    array (not waited for), the number of blocks and, for a bundled table,
    the device's count of rows with a conflict in some bundle (not waited
    for; ``None`` without bundles)."""
    n, num_features = X.shape
    rows = code_block_rows(num_features)
    assert X.dtype == np.float32 and X.flags.c_contiguous and n >= rows
    edge_keys, nan_code, cats = mapper.device_tables()
    edge_keys, nan_code = jnp.asarray(edge_keys), jnp.asarray(nan_code)
    if cats is not None:
        cats = {k: jnp.asarray(v) for k, v in cats.items()}
    bundler = mapper.bundler
    slots = (None if bundler is None else
             {k: jnp.asarray(v) for k, v in bundler.device_tables().items()})
    width = num_features if bundler is None else bundler.num_columns
    codes = jnp.zeros((n_pad, width), jnp.uint8)
    starts = list(range(0, n - rows, rows)) + [n - rows]
    done = []        # a token of each block in flight, two at most
    conflicts = None if slots is None else []
    for i, at in enumerate(starts):
        if len(done) == 2:
            done.pop(0).block_until_ready()
        bits = jax.device_put(X[at:at + rows].view(np.int32))
        if slots is None:
            codes, token = _write_code_block(codes, bits, at, edge_keys,
                                             nan_code, cats)
        else:
            skip = starts[i - 1] + rows - at if i else 0
            codes, token = _write_bundled_block(
                codes, bits, at, max(skip, 0), edge_keys, nan_code, slots,
                cats)
            conflicts.append(token)
        done.append(token)
    if conflicts is not None:
        conflicts = jnp.sum(jnp.stack(conflicts))
    return codes, len(starts), conflicts


def _to_2d_float_array(data: Any, keep_float32: bool = False) -> np.ndarray:
    """Accept numpy / pandas / list-of-lists; return f64 ndarray [n, F]
    (a float32 table as it is, if ``keep_float32``)."""
    if hasattr(data, "to_numpy"):  # pandas DataFrame/Series
        data = data.to_numpy()
    arr = np.asarray(data)
    if arr.dtype == object:
        arr = arr.astype(np.float64)
    if arr.ndim == 1:
        arr = arr[:, None]
    if arr.ndim != 2:
        raise ValueError(f"data must be 2-D, got shape {arr.shape}")
    dtype = (np.float32 if keep_float32 and arr.dtype == np.float32
             else np.float64)
    return np.ascontiguousarray(arr, dtype=dtype)


def _to_1d_float_array(x: Any) -> np.ndarray:
    if hasattr(x, "to_numpy"):
        x = x.to_numpy()
    arr = np.asarray(x, dtype=np.float64).reshape(-1)
    return arr


class Dataset:
    """`lgb.Dataset` equivalent: lazily-binned training data container.

    >>> dtrain = Dataset(X, label=y)
    >>> booster = lgb.train(params, dtrain, num_boost_round=200)

    Validation sets must share the training set's bin mapper; pass
    ``reference=dtrain`` exactly as in LightGBM.
    """

    def __init__(
        self,
        data: Any,
        label: Any = None,
        *,
        weight: Any = None,
        group: Any = None,
        init_score: Any = None,
        reference: Optional["Dataset"] = None,
        feature_name: Union[str, Sequence[str]] = "auto",
        categorical_feature: Union[str, Sequence[Union[int, str]]] = "auto",
        params: Optional[Dict[str, Any]] = None,
        free_raw_data: bool = False,
    ):
        self.raw_data = data
        self._label = None if label is None else _to_1d_float_array(label)
        self._weight = None if weight is None else _to_1d_float_array(weight)
        self._group = None if group is None else np.asarray(group, dtype=np.int64).reshape(-1)
        self._init_score = None if init_score is None else _to_1d_float_array(init_score)
        self.reference = reference
        self.params: Dict[str, Any] = dict(params or {})
        self.free_raw_data = free_raw_data
        self._feature_name_arg = feature_name
        self._categorical_feature_arg = categorical_feature

        # the reference's mapper is resolved lazily at construct() time: at
        # creation the reference may not be constructed yet (the standard
        # create_valid-before-train pattern), and binding None here would
        # silently fit a DIFFERENT binning for the valid set
        self._reference: Optional["Dataset"] = reference
        self.bin_mapper: Optional[BinMapper] = (
            reference.bin_mapper if reference is not None else None)
        self._constructed = False
        self.num_data_: Optional[int] = None
        self.num_feature_: Optional[int] = None
        self.feature_names: Optional[List[str]] = None
        # device-side products (filled by construct())
        self.X_binned = None      # jnp.uint8 [n_pad, F]
        self.y = None             # jnp.float32 [n_pad]
        self.w = None             # jnp.float32 [n_pad] (0 on padding)
        self.row_mask = None      # jnp.float32 [n_pad] 1/0 validity
        self.group_id = None      # jnp.int32 [n_pad] query ids for ranking (-1 pad)
        # out-of-core state (filled by from_blocks(); X_binned stays None
        # and the binned codes live host-side in a data.BlockStore)
        self.is_streamed = False
        self.block_store = None

    # -- lightgbm-compatible introspection ---------------------------------
    def num_data(self) -> int:
        self.construct()
        return int(self.num_data_)

    def num_feature(self) -> int:
        """Original (pre-EFB) feature count — the user-facing surface; the
        training column count is ``num_feature_`` (fewer when bundled)."""
        self.construct()
        return int(getattr(self, "raw_num_feature_", None)
                   or self.num_feature_)

    def get_label(self) -> Optional[np.ndarray]:
        return self._label

    def set_label(self, label) -> "Dataset":
        self._label = None if label is None else _to_1d_float_array(label)
        if self._constructed and self._label is not None:
            self._device_put_targets()
        return self

    def get_weight(self) -> Optional[np.ndarray]:
        return self._weight

    def set_weight(self, weight) -> "Dataset":
        self._weight = None if weight is None else _to_1d_float_array(weight)
        if self._constructed:
            self._device_put_targets()
        return self

    def get_group(self) -> Optional[np.ndarray]:
        return self._group

    def set_group(self, group) -> "Dataset":
        self._group = None if group is None else np.asarray(group, dtype=np.int64).reshape(-1)
        if self._constructed:
            self._device_put_targets()
        return self

    def get_init_score(self) -> Optional[np.ndarray]:
        return self._init_score

    def set_init_score(self, init_score) -> "Dataset":
        self._init_score = None if init_score is None else _to_1d_float_array(init_score)
        return self

    def feature_num_bin(self, feature: int) -> int:
        """Number of bins a feature actually uses (LightGBM
        ``Dataset.feature_num_bin``); original-feature indexed."""
        self.construct()
        return int(self.bin_mapper.n_bins[int(feature)])

    def get_feature_name(self) -> List[str]:
        self.construct()
        return list(self.feature_names)

    def get_field(self, name: str):
        return {
            "label": self._label, "weight": self._weight,
            "group": self._group, "init_score": self._init_score,
        }[name]

    def set_field(self, name: str, value) -> "Dataset":
        return getattr(self, f"set_{name}")(value)

    # -- construction -------------------------------------------------------
    def _resolve_feature_names(self, num_features: int) -> List[str]:
        fn = self._feature_name_arg
        if fn == "auto" or fn is None:
            if hasattr(self.raw_data, "columns"):
                return [str(c) for c in self.raw_data.columns]
            return [f"Column_{i}" for i in range(num_features)]
        names = list(fn)
        if len(names) != num_features:
            raise ValueError("feature_name length mismatch")
        return [str(c) for c in names]

    def _resolve_categorical(self, feature_names: List[str]) -> List[int]:
        cf = self._categorical_feature_arg
        if cf == "auto" or cf is None:
            return []
        out = []
        for c in cf:
            if isinstance(c, str):
                if c not in feature_names:
                    raise ValueError(f"categorical_feature '{c}' not in feature names")
                out.append(feature_names.index(c))
            else:
                out.append(int(c))
        return sorted(set(out))

    def construct(self) -> "Dataset":
        if self._constructed:
            return self
        if isinstance(self.raw_data, str):
            # a path: reload a save_binary() artifact (LightGBM's
            # Dataset('train.bin') contract)
            path = self.raw_data
            if self.free_raw_data:
                self.raw_data = None
            self._load_binary(path)
            return self

        with span("lgbtpu.dataset.construct") as fields:
            self._construct_from_rows()
            fields.update(rows=self.num_data_, features=self.num_feature_)
        return self

    def _construct_from_rows(self) -> None:
        """Edges, codes, bundle and the copy to the device, each under its
        own span (``lgbtpu.dataset.*``).  The codes are assigned on the
        device where the table allows it (:func:`device_bin_codes`), and
        then waited for inside ``.codes``; the host's codes are copied in
        ``.put``, which is not waited for here."""
        p = parse_params(self.params, warn_unknown=False)
        with span("lgbtpu.dataset.to_float"):
            X = _to_2d_float_array(self.raw_data, keep_float32=True)
        n, num_features = X.shape
        self.num_data_ = n
        self.num_feature_ = num_features
        self.feature_names = self._resolve_feature_names(num_features)
        cat_idx = self._resolve_categorical(self.feature_names)

        if self.bin_mapper is None and self._reference is not None:
            self._reference.construct()
            self.bin_mapper = self._reference.bin_mapper
        if self.bin_mapper is None:
            profiling.note("dataset.edges_path", "one_sort")
            with span("lgbtpu.dataset.edges") as fields:
                self.bin_mapper = BinMapper.fit(
                    X, max_bin=p.max_bin, min_data_in_bin=p.min_data_in_bin,
                    categorical=cat_idx, seed=p.data_random_seed)
                fields.update(self.bin_mapper.fit_counts)
            if p.enable_bundle:
                with span("lgbtpu.dataset.bundle") as fields:
                    # whether a bundle forms is read from the leading rows
                    head = X[:FeatureBundler.SAMPLE_ROWS]
                    bundler = self.bin_mapper.bundler = FeatureBundler.fit(
                        self.bin_mapper._transform_unbundled(head),
                        self.bin_mapper.n_bins,
                        max_conflict_rate=p.max_conflict_rate,
                        exclude=self.bin_mapper.is_categorical)
                    fields.update(
                        columns=num_features if bundler is None
                        else bundler.num_columns,
                        bundles=0 if bundler is None else sum(
                            len(g) > 1 for g in bundler.groups),
                        members=0 if bundler is None
                        else bundler.num_bundled,
                        sample_rows=len(head))
        mapper = self.bin_mapper
        self.raw_num_feature_ = num_features
        if mapper.is_categorical.any():
            profiling.note("dataset.categorical_features",
                           int(mapper.is_categorical.sum()))
        if mapper.bundler is not None:
            num_features = mapper.bundler.num_columns
            self.num_feature_ = num_features
            profiling.note("dataset.bundle_columns", num_features)
            profiling.note("dataset.bundled_features",
                           mapper.bundler.num_bundled)

        n_pad = -(-n // ROW_PAD_MULTIPLE) * ROW_PAD_MULTIPLE
        # on a CPU backend the device is the host, and its loop the faster
        # program for it: XLA:CPU counts edges at a tenth of the loop's rate
        on_device = (X.dtype == np.float32
                     and n >= code_block_rows(self.raw_num_feature_)
                     and jax.default_backend() != "cpu")
        path = "device" if on_device else "host"
        profiling.note("dataset.codes_path", path)
        conflicts = None
        with span("lgbtpu.dataset.codes", path=path, blocks=0) as fields:
            if on_device:
                self.X_binned, fields["blocks"], conflicts = \
                    device_bin_codes(X, mapper, n_pad)
                jax.block_until_ready(self.X_binned)
                profiling.add("dataset.codes.device_rows", n)
            else:
                codes = mapper._transform_unbundled(X)
                if mapper.bundler is not None:
                    conflicts = mapper.bundler.conflict_rows(codes)
                    codes = mapper.bundler.merge(codes)
        if conflicts is not None:
            profiling.note("dataset.bundle_conflict_rows", int(conflicts))
        with span("lgbtpu.dataset.put"):
            if not on_device:
                pad = n_pad - n
                if pad:
                    codes = np.concatenate(
                        [codes, np.zeros((pad, num_features), np.uint8)],
                        axis=0)
                self.X_binned = jnp.asarray(codes)
            mask = np.zeros(n_pad, dtype=np.float32)
            mask[:n] = 1.0
            self.row_mask = jnp.asarray(mask)
            self._device_put_targets()
        self._constructed = True
        if self.free_raw_data:
            self.raw_data = None

    def _device_put_targets(self) -> None:
        n, n_pad = self.num_data_, int(self.row_mask.shape[0]) if self.row_mask is not None else None
        if n_pad is None:
            return
        pad = n_pad - n
        if self._label is not None:
            y = np.asarray(self._label, dtype=np.float32)
            if len(y) != n:
                raise ValueError(f"label length {len(y)} != num_data {n}")
            self.y = jnp.asarray(np.concatenate([y, np.zeros(pad, np.float32)]))
        w = np.ones(n, dtype=np.float32) if self._weight is None else np.asarray(self._weight, np.float32)
        if len(w) != n:
            raise ValueError(f"weight length {len(w)} != num_data {n}")
        self.w = jnp.asarray(np.concatenate([w, np.zeros(pad, np.float32)]))
        if self._group is not None:
            if self._group.sum() != n:
                raise ValueError("group sizes must sum to num_data")
            gid = np.repeat(np.arange(len(self._group)), self._group).astype(np.int32)
            self.group_id = jnp.asarray(np.concatenate([gid, np.full(pad, -1, np.int32)]))
        else:
            self.group_id = None  # clear any stale copy (e.g. via subset())

    # -- out-of-core construction -------------------------------------------
    @classmethod
    def from_blocks(cls, blocks, label=None, *, weight=None,
                    params: Optional[Dict[str, Any]] = None,
                    feature_name: Union[str, Sequence[str]] = "auto",
                    reference: Optional["Dataset"] = None,
                    ) -> "Dataset":
        """Build a STREAMED dataset from row blocks without materializing
        the raw matrix (ISSUE 7 tentpole: the HBM ceiling becomes the
        [block_rows, F] transfer buffer, not the [n, F] matrix).

        ``blocks`` is either a sequence of blocks or a ZERO-ARG CALLABLE
        returning a fresh iterator (two passes are needed: quantile-sketch
        fit, then binning); a one-shot generator is rejected.  Each block
        is a 2-D ``[rows, F]`` array or an ``(X, y)`` / ``(X, y, w)``
        tuple; all blocks must agree on the feature count and dtype
        (ValueError otherwise).  ``max_bin`` / ``min_data_in_bin`` /
        ``stream_*`` knobs come from ``params`` exactly as in-memory
        construction; the BinMapper is fit by the one-pass mergeable
        sketch (``data.sketch``) — bit-identical to the in-memory fit
        whenever total rows stay within the sketch capacity AND the
        in-memory fit's 200k sampling threshold.

        Streaming scope: numeric features only (no categorical subset
        splits, no EFB — bundling needs global co-occurrence stats), and
        labels/weights/masks stay device-resident (O(n) vectors; the
        [n, F] code matrix is what streaming evicts from HBM).

        ``reference`` (r15) pins the binning schema: the new Dataset
        reuses ``reference``'s already-fit BinMapper verbatim (the
        sketch-fit pass is skipped) so growing data keeps an IDENTICAL
        schema digest across generations — the contract model-file /
        checkpoint continuation enforces.  ``reference`` may be an
        earlier streamed or in-memory Dataset (must be constructed, no
        EFB bundling).
        """
        from .data import BlockStore, StreamingBinMapperBuilder

        if callable(blocks):
            make_iter = blocks
        elif hasattr(blocks, "__len__"):
            make_iter = lambda: iter(blocks)  # noqa: E731
        else:
            raise ValueError(
                "from_blocks needs two passes over the blocks (sketch fit, "
                "then binning) — pass a list/tuple or a zero-arg callable "
                "returning a fresh iterator, not a one-shot generator")

        def split_block(b, idx):
            ys = ws = None
            if isinstance(b, tuple):
                if len(b) == 2:
                    x, ys = b
                elif len(b) == 3:
                    x, ys, ws = b
                else:
                    raise ValueError(
                        f"block {idx}: tuples must be (X, y) or (X, y, w), "
                        f"got length {len(b)}")
            else:
                x = b
            x = np.asarray(x)
            if x.ndim == 1:
                x = x[:, None]
            if x.ndim != 2:
                raise ValueError(
                    f"block {idx}: blocks must be 2-D [rows, F], got shape "
                    f"{x.shape}")
            return x, ys, ws

        p = parse_params(dict(params or {}), warn_unknown=False)
        block_rows = int(p.extra.get("stream_block_rows", 131072))
        if block_rows <= 0 or block_rows % ROW_PAD_MULTIPLE:
            raise ValueError(
                f"stream_block_rows={block_rows} must be a positive "
                f"multiple of {ROW_PAD_MULTIPLE} (bit-identity with the "
                "in-memory row_chunk path needs lane-aligned blocks)")

        ref_mapper = None
        if reference is not None:
            ref_mapper = getattr(reference, "bin_mapper", reference)
            if ref_mapper is None:
                raise ValueError(
                    "reference= Dataset has no fitted BinMapper — call "
                    "construct() on it (or train with it) first")
            if getattr(ref_mapper, "bundler", None) is not None:
                raise ValueError(
                    "reference= Dataset was built with EFB bundling, "
                    "which streamed datasets do not support — rebuild "
                    "the reference with enable_bundle=false")

        # pass 1: streaming quantile sketch -> BinMapper (skipped when a
        # reference pins the schema; the loop still validates blocks and
        # collects labels/weights)
        builder = None
        first_dtype = None
        y_parts: List[np.ndarray] = []
        w_parts: List[np.ndarray] = []
        blocks_have_y = blocks_have_w = False
        saw_block = False
        for idx, b in enumerate(make_iter()):
            x, ys, ws = split_block(b, idx)
            if not saw_block:
                saw_block = True
                first_dtype = x.dtype
                if ref_mapper is None:
                    builder = StreamingBinMapperBuilder(
                        x.shape[1],
                        capacity=int(p.extra.get("stream_sketch_capacity",
                                                 200_000)),
                        eps=float(p.extra.get("stream_sketch_eps", 1e-3)))
                blocks_have_y = ys is not None
                blocks_have_w = ws is not None
            if x.dtype != first_dtype:
                raise ValueError(
                    f"block {idx}: dtype {x.dtype} != block 0's "
                    f"{first_dtype} — blocks must agree on dtype")
            if (ys is not None) != blocks_have_y or \
                    (ws is not None) != blocks_have_w:
                raise ValueError(
                    f"block {idx}: inconsistent (X, y[, w]) tuple shape "
                    "across blocks")
            if builder is not None:
                builder.update(x)   # raises on ragged feature counts
            elif x.shape[1] != ref_mapper.num_features:
                raise ValueError(
                    f"block {idx}: {x.shape[1]} features != reference "
                    f"Dataset's {ref_mapper.num_features}")
            if ys is not None:
                y_parts.append(np.asarray(ys, np.float64).reshape(-1))
            if ws is not None:
                w_parts.append(np.asarray(ws, np.float64).reshape(-1))
        if not saw_block:
            raise ValueError("from_blocks: empty block iterator")
        if blocks_have_y and label is not None:
            raise ValueError(
                "labels supplied both per-block and via label= — pick one")
        mapper = (ref_mapper if ref_mapper is not None
                  else builder.finalize(max_bin=p.max_bin,
                                        min_data_in_bin=p.min_data_in_bin))

        # pass 2: bin each block and pack the codes host-side
        writer = BlockStore.writer(block_rows)
        for idx, b in enumerate(make_iter()):
            x, _, _ = split_block(b, idx)
            writer.append(mapper._transform_unbundled(
                np.ascontiguousarray(x, dtype=np.float64)))
        store = writer.finish()
        n, num_features = store.num_rows, store.num_features

        ds = cls.__new__(cls)
        ds.raw_data = None
        ds._label = (np.concatenate(y_parts) if blocks_have_y
                     else None if label is None else _to_1d_float_array(label))
        ds._weight = (np.concatenate(w_parts) if blocks_have_w
                      else None if weight is None
                      else _to_1d_float_array(weight))
        ds._group = None
        ds._init_score = None
        ds.reference = ds._reference = None
        ds.params = dict(params or {})
        ds.free_raw_data = False
        ds._feature_name_arg = feature_name
        ds._categorical_feature_arg = None
        ds.bin_mapper = mapper
        ds.num_data_ = n
        ds.num_feature_ = num_features
        ds.raw_num_feature_ = num_features
        ds.feature_names = ds._resolve_feature_names(num_features)
        ds.X_binned = None
        ds.is_streamed = True
        ds.block_store = store
        # O(n) per-row vectors stay device-resident, sized to the store's
        # padded extent so per-block dynamic slices never go ragged
        mask = np.zeros(store.padded_rows, dtype=np.float32)
        mask[:n] = 1.0
        ds.row_mask = jnp.asarray(mask)
        ds.y = ds.w = ds.group_id = None
        ds._device_put_targets()
        ds._constructed = True
        return ds

    # -- lightgbm API surface ------------------------------------------------
    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, params=None) -> "Dataset":
        return Dataset(data, label=label, weight=weight, group=group,
                       init_score=init_score, reference=self, params=params or self.params)

    def save_binary(self, filename: str) -> "Dataset":
        """Persist the CONSTRUCTED (binned) dataset to one .npz file
        (LightGBM ``Dataset.save_binary``): bin codes, labels/weights/
        groups/init_score, bin mapper and EFB bundle map ride along, so
        ``Dataset(filename)`` reloads without the raw data or a re-binning
        pass."""
        import json as _json

        from .utils.serialize import mapper_to_dict

        self.construct()
        if self.is_streamed:
            raise ValueError(
                "save_binary is not supported for streamed datasets — the "
                "binned codes live host-side in the BlockStore, not as one "
                "materialized matrix")
        if not filename.endswith(".npz"):
            filename += ".npz"  # numpy appends it anyway; keep load in sync
        n = self.num_data_
        payload = {
            "codes": np.asarray(self.X_binned)[:n],
            "mapper_json": np.frombuffer(
                _json.dumps(mapper_to_dict(self.bin_mapper)).encode(),
                dtype=np.uint8),
            "feature_names": np.asarray(self.feature_names, dtype=object),
            "raw_num_feature": np.int64(
                getattr(self, "raw_num_feature_", None)
                or self.num_feature_),
        }
        for name, arr in (("label", self._label), ("weight", self._weight),
                          ("group", self._group),
                          ("init_score", self._init_score)):
            if arr is not None:
                payload[name] = np.asarray(arr)
        np.savez_compressed(filename, **payload)
        return self

    def _load_binary(self, filename: str) -> None:
        import json as _json

        from .utils.serialize import mapper_from_dict

        import os

        if not os.path.exists(filename) and not filename.endswith(".npz"):
            filename += ".npz"  # save_binary normalizes to .npz
        with np.load(filename, allow_pickle=True) as z:
            codes = z["codes"].astype(np.uint8)
            self.bin_mapper = mapper_from_dict(
                _json.loads(bytes(z["mapper_json"]).decode()))
            self.feature_names = [str(s) for s in z["feature_names"]]
            self.raw_num_feature_ = int(z["raw_num_feature"])
            # constructor arguments take precedence over the stored fields
            # (Dataset(path, label=new_y) means the NEW labels)
            if self._label is None and "label" in z:
                self._label = z["label"]
            if self._weight is None and "weight" in z:
                self._weight = z["weight"]
            if self._group is None and "group" in z:
                self._group = z["group"]
            if self._init_score is None and "init_score" in z:
                self._init_score = z["init_score"]
        self._from_codes(codes)

    def subset(self, used_indices, params=None) -> "Dataset":
        """Row-subset sharing this dataset's bin mapper (used by cv folds)."""
        self.construct()
        if self.is_streamed:
            raise ValueError(
                "subset is not supported for streamed datasets")
        used = np.asarray(used_indices, dtype=np.int64)
        codes = np.asarray(self.X_binned)[: self.num_data_][used]
        sub = Dataset.__new__(Dataset)
        sub.__dict__.update(self.__dict__)
        sub.raw_data = None
        sub._constructed = False
        sub.params = dict(params or self.params)
        sub._label = None if self._label is None else self._label[used]
        sub._weight = None if self._weight is None else self._weight[used]
        sub._group = None
        sub._init_score = None if self._init_score is None else self._init_score[used]
        sub._from_codes(codes)
        return sub

    def _from_codes(self, codes: np.ndarray) -> None:
        n, num_features = codes.shape
        self.num_data_ = n
        self.num_feature_ = num_features
        n_pad = -(-n // ROW_PAD_MULTIPLE) * ROW_PAD_MULTIPLE
        pad = n_pad - n
        if pad:
            codes = np.concatenate([codes, np.zeros((pad, num_features), np.uint8)], axis=0)
        self.X_binned = jnp.asarray(codes)
        mask = np.zeros(n_pad, dtype=np.float32)
        mask[:n] = 1.0
        self.row_mask = jnp.asarray(mask)
        self._device_put_targets()
        self._constructed = True

    @property
    def num_bins(self) -> int:
        """Padded bin-axis size (power-of-two-ish for kernel friendliness)."""
        self.construct()
        return max(2, self.bin_mapper.max_num_bins)

    @property
    def col_is_categorical(self) -> np.ndarray:
        """Categorical flag per original feature, the space every split is
        in (categoricals are never bundled: each is a column of its own)."""
        self.construct()
        return np.asarray(self.bin_mapper.is_categorical, bool)
