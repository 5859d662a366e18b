"""Histogram op: matmul formulation vs numpy oracle, segments, chunking."""

import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu.ops.histogram import compute_histograms


def _numpy_hist(bins, stats, seg, K, B):
    n, F = bins.shape
    S = stats.shape[1]
    out = np.zeros((K, F, B, S), np.float64)
    for i in range(n):
        if 0 <= seg[i] < K:
            for f in range(F):
                out[seg[i], f, bins[i, f]] += stats[i]
    return out


@pytest.mark.parametrize("n,F,B,K", [(100, 3, 8, 1), (257, 2, 16, 2),
                                     (1000, 4, 32, 3)])
def test_histogram_matches_numpy(rng, n, F, B, K):
    bins = rng.integers(0, B, (n, F)).astype(np.uint8)
    stats = rng.normal(0, 1, (n, 3)).astype(np.float32)
    seg = rng.integers(0, K + 1, n).astype(np.int32)  # includes dropped seg K
    got = compute_histograms(jnp.asarray(bins), jnp.asarray(stats),
                             jnp.asarray(seg), K, B)
    want = _numpy_hist(bins, stats, seg, K, B)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


def test_histogram_row_chunking_equivalent(rng):
    n, F, B, K = 700, 3, 16, 2
    bins = rng.integers(0, B, (n, F)).astype(np.uint8)
    stats = rng.normal(0, 1, (n, 2)).astype(np.float32)
    seg = rng.integers(0, K, n).astype(np.int32)
    full = compute_histograms(jnp.asarray(bins), jnp.asarray(stats),
                              jnp.asarray(seg), K, B, row_chunk=10_000)
    chunked = compute_histograms(jnp.asarray(bins), jnp.asarray(stats),
                                 jnp.asarray(seg), K, B, row_chunk=128)
    np.testing.assert_allclose(np.asarray(full), np.asarray(chunked),
                               rtol=1e-5, atol=1e-5)


def test_histogram_zero_stats_rows_contribute_nothing(rng):
    n, F, B = 50, 2, 8
    bins = rng.integers(0, B, (n, F)).astype(np.uint8)
    stats = np.ones((n, 1), np.float32)
    stats[25:] = 0.0
    seg = np.zeros(n, np.int32)
    got = compute_histograms(jnp.asarray(bins), jnp.asarray(stats),
                             jnp.asarray(seg), 1, B)
    # every feature's histogram accumulates all contributing rows once
    assert float(np.asarray(got).sum()) == 25.0 * F


@pytest.mark.parametrize("mode", ["f32", "bf16"])
def test_fused_pallas_matches_numpy(mode):
    # its OWN rng, like the int8 test below: the bf16 tolerance holds for
    # a given draw, and the session rng's draw depends on which tests
    # the worker ran before this one
    from lightgbm_tpu.ops.histogram_pallas import hist_fused_pallas

    rng = np.random.default_rng(7)
    n, F, B, K = 1500, 4, 32, 5
    bins = rng.integers(0, B, (n, F)).astype(np.uint8)
    stats = rng.normal(0, 1, (n, 3)).astype(np.float32)
    seg = rng.integers(-1, K + 1, n).astype(np.int32)  # out-of-range dropped
    got = hist_fused_pallas(jnp.asarray(bins), jnp.asarray(stats),
                            jnp.asarray(seg), K, B, hist_dtype=mode)
    want = _numpy_hist(bins, stats, seg, K, B)
    tol = 2e-2 if mode == "bf16" else 1e-3
    np.testing.assert_allclose(np.asarray(got), want, rtol=tol, atol=tol)


def test_fused_pallas_feature_blocking(rng):
    """Wide-feature shapes split the feature axis into grid blocks (the
    [F, B, K] accumulator must fit VMEM — MSLR has 136 features)."""
    from lightgbm_tpu.ops.histogram_pallas import hist_fused_pallas

    # F=136, B=256, K=42*3 -> a ~17.5 MB accumulator: must split into
    # (at least) two feature blocks to fit the 16 MB VMEM scope
    n, F, B, K = 700, 136, 256, 42
    bins = rng.integers(0, B, (n, F)).astype(np.uint8)
    stats = rng.normal(0, 1, (n, 3)).astype(np.float32)
    seg = rng.integers(0, K, n).astype(np.int32)
    got = hist_fused_pallas(jnp.asarray(bins), jnp.asarray(stats),
                            jnp.asarray(seg), K, B, hist_dtype="f32")
    want = _numpy_hist(bins, stats, seg, K, B)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-3, atol=1e-3)


def test_fused_pallas_int8_quantized():
    """int8 quantized-gradient mode (use_quantized_grad analogue): unbiased
    stochastic rounding, exact int32 accumulation — histogram within ~1%
    of exact, count channel near-exact.  Uses its OWN rng: the stochastic
    tolerance is calibrated to this exact draw (the shared session rng
    makes the bound order-dependent)."""
    from lightgbm_tpu.ops.histogram_pallas import hist_fused_pallas

    rng = np.random.default_rng(1234)
    n, F, B, K = 4000, 4, 32, 5
    bins = rng.integers(0, B, (n, F)).astype(np.uint8)
    stats = np.column_stack([
        rng.normal(0, 1, n), np.abs(rng.normal(0, 1, n)),
        np.ones(n)]).astype(np.float32)
    seg = rng.integers(0, K, n).astype(np.int32)
    got = np.asarray(hist_fused_pallas(
        jnp.asarray(bins), jnp.asarray(stats), jnp.asarray(seg), K, B,
        hist_dtype="int8"))
    want = _numpy_hist(bins, stats, seg, K, B)
    scale = np.abs(stats).max(axis=0) / 127.0
    # per-cell error bound: each row contributes <= scale/... stochastic
    # rounding error < 1 quantum per row; cells hold ~n/(K*B) rows
    tol = scale * 4 * np.sqrt(n / (K * B) + 9)
    err = np.abs(got - want).max(axis=(0, 1, 2))
    assert np.all(err < tol), (err, tol)
    # totals per (segment, channel): each row's rounding error repeats in
    # ALL F feature histograms, so the f-summed error has sigma
    # F * sqrt(rows_per_seg / 12) quanta; allow 4 sigma
    tg, wg = got.sum(axis=(1, 2)), want.sum(axis=(1, 2))
    sigma_q = F * np.sqrt(n / K / 12.0)
    np.testing.assert_allclose(tg, wg, rtol=5e-3,
                               atol=float(scale.max()) * 4 * sigma_q)


def test_split_hi_lo_is_exact_and_hi_is_bfloat16():
    """The two operands of the "f32" mode's passes: they add up to the
    statistics exactly, the first is a bfloat16 value whatever rounds it
    afterwards, the second is under 2**-7 of it."""
    import jax

    from lightgbm_tpu.ops.histogram_pallas import split_hi_lo

    rng = np.random.RandomState(5)
    x = np.concatenate([rng.randn(4096), [0.5003, -0.4997, 0.0, -0.0,
                                          1e-30, -3e38, 2.0 ** -126]]
                       ).astype(np.float32)
    hi, lo = jax.jit(split_hi_lo)(jnp.asarray(x))
    hi, lo = np.asarray(hi), np.asarray(lo)
    np.testing.assert_array_equal(hi + lo, x)
    np.testing.assert_array_equal(
        np.asarray(jnp.asarray(hi).astype(jnp.bfloat16).astype(jnp.float32)),
        hi)
    assert np.all(np.abs(lo) <= np.abs(x) * 2.0 ** -7)
    assert np.any(lo != 0)
