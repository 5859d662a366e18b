"""Each fused kernel's one-hot as tall as its columns' bins: the feature
rows ordered by height (``histogram_pallas.onehot_order``, an operand),
each block looping over its runs of one height (``feature_layout``, from
the sorted heights alone), the histograms handed back in the table's
column order.  Interpret mode on the CPU: the histograms must be
the reference's exactly (the statistics are small integers, so every sum
is exact in float32 whatever its order) and the routing codes the same.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.ops.histogram_pallas import (ONEHOT_ROW_ALIGN,
                                               TURNED_MAX_K, _vmem_blocking,
                                               feature_layout,
                                               hist_fused_prepared,
                                               hist_partition_fused_pallas,
                                               onehot_heights, onehot_order,
                                               prepare_wave_operands)

B, S, TREE_W, NARROW_W, N = 256, 3, 42, 16, 3000
MIXED_BINS = (2, 9, 17, 40, 100, 255)


def _col_bins(num_features, seed):
    """Mixed bin counts, every count of ``MIXED_BINS`` present, in a
    shuffled column order."""
    rng = np.random.default_rng(seed)
    bins = np.resize(np.asarray(MIXED_BINS), num_features)
    return tuple(int(b) for b in rng.permutation(bins))


def _table(col_bins, seed):
    rng = np.random.default_rng(seed)
    codes = np.stack([rng.integers(0, b, N) for b in col_bins], axis=1)
    g = rng.integers(-4, 5, N).astype(np.float32)
    stats = np.stack([g, np.ones(N, np.float32), np.ones(N, np.float32)], -1)
    return codes.astype(np.int32), stats


def _layout(col_bins, width=TREE_W):
    """``(layout, order, row_of)`` of a table's columns: the loops of its
    rows by height, the column of each row, the row of each column."""
    f = len(col_bins)
    f_blk = _vmem_blocking(f, B, 3 * width)[0]
    heights = onehot_heights(col_bins, B) or (B,) * f
    order = onehot_order(heights)
    row_of = np.argsort(order).astype(np.int32)
    return feature_layout(f, f_blk, B, tuple(sorted(heights))), order, row_of


def test_heights_round_to_the_packing_and_stop_at_num_bins():
    assert ONEHOT_ROW_ALIGN == 16
    assert onehot_heights((2, 16, 17, 100, 241, 255, 256), 256) == (
        16, 16, 32, 112, 256, 256, 256)
    assert onehot_heights((255, 250, 241), 255) is None    # all 255 tall
    assert onehot_heights((1, 2, 255), 255) == (16, 16, 255)


@pytest.mark.parametrize("num_features", [28, 136, 2000])
def test_one_height_is_the_identity_and_one_loop_a_block(num_features):
    """A table whose columns all take one height keeps row ``c`` for
    column ``c``, and each block one loop over its columns: the last
    block's over its real rows only (the padding skipped).  The heights of
    another table (a screened round's compacted columns) are ``num_bins``
    for every row."""
    f_blk = _vmem_blocking(num_features, B, 3 * TREE_W)[0]
    other = tuple(sorted(onehot_heights(_col_bins(num_features + 1, 0), B)))
    for heights in (None, (B,) * num_features, (128,) * num_features,
                    other):
        layout = feature_layout(num_features, f_blk, B, heights)
        if heights not in (None, other):
            assert (onehot_order(heights) == np.arange(num_features)).all()
        tall = B if heights in (None, other) else heights[0]
        n_fblk = -(-num_features // f_blk)
        tail = num_features - (n_fblk - 1) * f_blk
        assert layout.runs == ((((f_blk, tall),),) * (n_fblk - 1)
                               + (((tail, tall),),))
        assert layout.rows_looped == num_features


@pytest.mark.parametrize("num_features", [12, 136, 660])
def test_mixed_heights_sort_the_rows_and_keep_the_blocks(num_features):
    col_bins = _col_bins(num_features, num_features)
    heights = onehot_heights(col_bins, B)
    layout, order, row_of = _layout(col_bins)
    f_blk = layout.f_blk
    assert len(layout.runs) == -(-num_features // f_blk)    # no new block
    assert sorted(order) == list(range(num_features))
    rows = [heights[c] for c in order]
    assert rows == sorted(rows)                              # shortest first
    assert all(order[row_of[c]] == c for c in range(num_features))
    # another order of the same columns: the same loops (one program)
    perm = np.random.default_rng(1).permutation(num_features)
    assert _layout([col_bins[c] for c in perm])[0] == layout
    for b, block in enumerate(layout.runs):
        assert [h for _, h in block] == sorted({h for _, h in block})
        assert sum(r for r, _ in block) == len(rows[b * f_blk:
                                                     (b + 1) * f_blk])
    assert layout.rows_looped == num_features
    assert layout.onehot_rows == sum(heights)
    assert layout.onehot_rows < num_features * B


def _pv(col_bins, codes, w, rng, ranges):
    """A wave of ``w`` splits (wave rank == leaf id, rows in leaves 0 ..
    w + 1): each split's column, threshold and side, and where ``ranges``
    a range of codes ``[lo, thr]`` inverted by turns, as an EFB member's
    split routes.  ``(pv [8, n] with row 1 the ROW of the column under
    the layout's order, wcol, the reference's enc and segments)``."""
    n, f = codes.shape
    wcol = (np.arange(w) * 37 + f - 1) % f
    thr = np.array([rng.integers(0, col_bins[c]) for c in wcol])
    lo = (np.array([rng.integers(0, t + 1) for t in thr]) if ranges
          else np.zeros(w, int))
    inv = (np.arange(w) % 2 == 1) if ranges else np.zeros(w, bool)
    dl = rng.integers(0, 2, w).astype(bool)
    leaf = rng.integers(0, w + 2, n)
    sel = leaf < w
    lf = np.where(sel, leaf, 0)
    v = codes[np.arange(n), wcol[lf]]
    go_left = ((v >= lo[lf]) & (v <= thr[lf])) != inv[lf]
    enc = np.where(sel, 2 * leaf + np.where(go_left, 0, 1) + 1, 0)
    seg = np.where(sel & (go_left == dl[lf]), leaf, w)
    return (sel, lf, wcol, thr, lo, inv, dl, leaf), enc, seg


def _pv_rows(fields, row_of):
    sel, lf, wcol, thr, lo, inv, dl, leaf = fields
    rows = wcol if row_of is None else row_of[wcol]
    z = np.zeros(sel.shape, np.float32)
    return np.stack([
        sel.astype(np.float32), np.where(sel, rows[lf], 0).astype(np.float32),
        np.where(sel, thr[lf], 0).astype(np.float32),
        np.where(sel, 2 * leaf, 0).astype(np.float32),
        np.where(sel, dl[lf], 0).astype(np.float32),
        np.where(sel, lo[lf], 0).astype(np.float32),
        np.where(sel, inv[lf], 0).astype(np.float32), z]), rows


def _reference_hist(codes, stats, seg, segments):
    """Planes ``[segments, S, F, B]``: each segment's rows added into the
    bins of their codes, column by column."""
    n, f = codes.shape
    hist = np.zeros((segments, f, B, S), np.float32)
    for w in range(segments):
        rows = np.flatnonzero(seg == w)
        for c in range(f):
            np.add.at(hist[w, c], (codes[rows, c],), stats[rows])
    return hist.transpose(0, 3, 1, 2)


@pytest.mark.parametrize("ranges", [False, True], ids=["plain", "efb_range"])
@pytest.mark.parametrize("width", [TREE_W, NARROW_W],
                         ids=["wave_unturned", "narrow_turned"])
@pytest.mark.parametrize("num_features", [12, 136],
                         ids=["single_block", "multi_block"])
def test_partition_pass_by_height_is_the_reference(num_features, width,
                                                   ranges):
    """A wave pass (dot unturned) and a narrow pass (turned) on operands
    prepared at the tree's width with the layout, as the frontier grower
    runs them: the histograms of every column in the table's order, and
    the routing codes, exactly the reference's.  136 columns are 5 blocks
    of 32, the last holding 8 rows and 24 of padding."""
    col_bins = _col_bins(num_features, 7 * num_features + width)
    codes, stats = _table(col_bins, num_features + width)
    layout, order, row_of = _layout(col_bins)
    assert (len(layout.runs) > 1) == (num_features > 45)
    assert (order != np.arange(num_features)).any()
    rng = np.random.default_rng(width)
    fields, enc_ref, seg = _pv(col_bins, codes, width, rng, ranges)
    pv, rows = _pv_rows(fields, row_of)
    bins_t, stats_t, chunk = prepare_wave_operands(
        jnp.asarray(codes.astype(np.uint8)), jnp.asarray(stats), B, TREE_W,
        jnp.asarray(order))
    pv_t = jnp.asarray(np.pad(pv, ((0, 0), (0, bins_t.shape[1] - N))))
    hist, enc = jax.jit(lambda: hist_partition_fused_pallas(
        bins_t, stats_t, pv_t, width, B, chunk, hist_dtype="bf16",
        wfeat=jnp.asarray(rows, jnp.int32), num_features=num_features,
        f_blk=layout.f_blk, layout=layout, row_of=jnp.asarray(row_of)))()
    assert (width == NARROW_W) == (3 * width <= TURNED_MAX_K)
    np.testing.assert_array_equal(np.asarray(enc)[:N], enc_ref)
    np.testing.assert_array_equal(np.asarray(hist),
                                  _reference_hist(codes, stats, seg, width))


@pytest.mark.parametrize("hist_dtype", ["bf16", "f32"])
@pytest.mark.parametrize("num_features", [12, 136],
                         ids=["single_block", "multi_block"])
def test_root_pass_by_height_is_the_reference(num_features, hist_dtype):
    """The root pass (``hist_fused_prepared``, every row in segment 0) on
    the wave's operands and layout; "f32" is its two calls over hi/lo."""
    col_bins = _col_bins(num_features, num_features)
    codes, stats = _table(col_bins, 3 * num_features)
    layout, order, row_of = _layout(col_bins)
    bins_t, stats_t, chunk = prepare_wave_operands(
        jnp.asarray(codes.astype(np.uint8)), jnp.asarray(stats), B, TREE_W,
        jnp.asarray(order))
    n_pad = bins_t.shape[1]
    hist = jax.jit(lambda: hist_fused_prepared(
        bins_t, stats_t, jnp.zeros((1, n_pad), jnp.int32), 1, B, chunk,
        layout.f_blk, num_features, hist_dtype=hist_dtype,
        layout=layout, row_of=jnp.asarray(row_of)))()
    ref = _reference_hist(codes, stats, np.zeros(N, int), 1)
    np.testing.assert_array_equal(np.asarray(hist), ref.transpose(0, 2, 3, 1))


def _dot_loops(jaxpr):
    """``(trips, one-hot height)`` of each per-feature loop of a TURNED
    kernel's jaxpr (a loop whose body holds a dot: ``[K, height]`` out),
    in the order traced."""
    def dots(j):
        for e in j.eqns:
            if e.primitive.name == "dot_general":
                yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from dots(sub)

    found = []
    for eqn in jaxpr.eqns:
        subs = list(jax.core.jaxprs_in_params(eqn.params))
        if eqn.primitive.name == "scan" and any(dots(subs[0])):
            dot = next(dots(subs[0]))
            found.append((eqn.params["length"],
                          dot.outvars[0].aval.shape[-1]))
        else:
            for sub in subs:
                found += _dot_loops(sub)
    return found


def _kernel_jaxpr(fn, *args):
    for eqn in jax.make_jaxpr(fn)(*args).jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return eqn.params["jaxpr"]
    raise AssertionError("no kernel")


@pytest.mark.parametrize("mixed", [False, True], ids=["one_height", "mixed"])
def test_a_kernel_branches_once_per_list_of_runs(mixed):
    """The narrow kernel at 660 columns (17 blocks of 40, the last of 20):
    one height holds the parent's two loops (full blocks, the tail); mixed
    heights hold one loop per run of each DISTINCT list of runs, at that
    run's height, and no more."""
    f = 660
    col_bins = _col_bins(f, 1) if mixed else (255,) * f
    layout, order, row_of = _layout(col_bins)
    assert layout.f_blk == 40 and len(layout.runs) == 17
    shapes = (jax.ShapeDtypeStruct((1024, f), jnp.uint8),
              jax.ShapeDtypeStruct((1024, S), jnp.float32),
              jax.ShapeDtypeStruct((NARROW_W,), jnp.int32),
              jax.ShapeDtypeStruct((f,), jnp.int32),
              jax.ShapeDtypeStruct((f,), jnp.int32))

    def narrow(b, s, wfeat, order, row_of):
        bins_t, stats_t, chunk = prepare_wave_operands(b, s, B, TREE_W,
                                                       order)
        return hist_partition_fused_pallas(
            bins_t, stats_t, jnp.zeros((8, bins_t.shape[1]), jnp.float32),
            NARROW_W, B, chunk, interpret=True, hist_dtype="bf16",
            wfeat=wfeat, num_features=f, f_blk=40, layout=layout,
            row_of=row_of)

    loops = _dot_loops(_kernel_jaxpr(narrow, *shapes))
    want = []
    for block in dict.fromkeys(layout.runs):         # distinct, in order
        want += [(rows, min(h, B)) for rows, h in block]
    assert loops == want
    if not mixed:
        assert loops == [(40, B), (20, B)]
