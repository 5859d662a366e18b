"""Per-phase profiling counters (SURVEY.md §5 "Tracing / profiling").

The reference's only instrumentation is ``system.time`` wall clocks
(r/gridsearchCV.R:57,70); LightGBM's C++ has internal chrono counters around
bin construction / histogram / split / partition.  Here the round step is one
fused XLA program, so phases cannot be timed from the host inside a real
round — instead ``profile_training`` times each phase as its own jitted
program on the actual data (same shapes, same dtypes, same kernels), plus
the fused whole-round program, and reports rows/sec/chip.

Timing is host-fetch honest: ``np.asarray`` of a value that depends on the
computation ends every timed region.

``jax.profiler`` integration: pass ``trace_dir`` to wrap the timed section
in ``jax.profiler.trace`` for TensorBoard/XProf inspection.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional

import numpy as np


def _timeit(fn, *args, reps: int = 3) -> float:
    """Median seconds per call, compile excluded, value-fetch honest."""
    import jax

    out = fn(*args)
    np.asarray(jax.tree.leaves(out)[0])  # compile + fetch
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        np.asarray(jax.tree.leaves(out)[0])
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def profile_training(params: Dict[str, Any], X, y,
                     num_boost_round: int = 20,
                     trace_dir: Optional[str] = None) -> Dict[str, Any]:
    """Phase breakdown + throughput for one training configuration.

    Returns a dict with seconds per phase (one execution each):
      bin_construct   host-side quantile binning of X (one-time cost)
      histogram_pass  one (grad,hess,count) histogram over all rows
      split_scan      one full split-gain scan over (segments,features,bins)
      partition       one row->leaf partition update (gather)
      tree_grow       one full tree (all trips/waves)
      round           one boosting round from the fused path
      train_total     num_boost_round rounds via update_many
      rows_per_s      training throughput over train_total
    """
    import jax
    import jax.numpy as jnp

    import lightgbm_tpu as lgb
    from ..models.gbdt import HyperScalars, resolve_hist_dtype, \
        resolve_wave_width
    from ..models.tree import grow_tree
    from ..ops.histogram import batched_histogram_op
    from ..ops.split import find_best_split

    report: Dict[str, Any] = {}

    t0 = time.perf_counter()
    ds = lgb.Dataset(X, label=y)
    ds.construct()
    report["bin_construct_s"] = time.perf_counter() - t0

    p = lgb.config.parse_params(params)
    n_pad = int(ds.row_mask.shape[0])
    hd = resolve_hist_dtype(p, n_pad)
    ww = resolve_wave_width(p, n_pad)
    hyper = HyperScalars.from_params(p)
    stats = jnp.stack([ds.y, jnp.ones_like(ds.y), ds.row_mask], axis=-1)
    # real rows -> segment 0; padding -> out-of-range (contributes nothing)
    seg = jnp.where(ds.row_mask > 0.5, 0, 2).astype(jnp.int32)

    hist_op = batched_histogram_op(2, ds.num_bins,
                                   int(p.extra.get("row_chunk", 131072)),
                                   p.extra.get("hist_impl", "auto"), hd)
    report["histogram_pass_s"] = _timeit(
        jax.jit(lambda b, s, g: hist_op(b, s, g)), ds.X_binned, stats, seg)

    hist = jax.jit(lambda b, s, g: hist_op(b, s, g))(ds.X_binned, stats, seg)
    fmask = jnp.ones(ds.num_feature_, jnp.float32)
    report["split_scan_s"] = _timeit(
        jax.jit(lambda h: jax.vmap(
            find_best_split, in_axes=(0, None, None, None))(
                h, hyper.ctx(), fmask, jnp.bool_(True))), hist)

    col = ds.X_binned[:, 0].astype(jnp.int32)
    report["partition_s"] = _timeit(
        jax.jit(lambda c, rl: jnp.where(
            rl == 0, jnp.where(c <= 17, 1, 2), rl)),
        col, jnp.zeros(n_pad, jnp.int32))

    report["tree_grow_s"] = _timeit(
        jax.jit(lambda b, s: grow_tree(
            b, s, fmask, hyper.ctx(), p.num_leaves, ds.num_bins,
            p.max_depth, hist_dtype=hd, wave_width=ww)),
        ds.X_binned, stats)

    def train_rounds(k):
        b = lgb.Booster(p.copy(), ds)
        b.update_many(k)
        return b

    ctx = None
    if trace_dir:
        import jax.profiler
        ctx = jax.profiler.trace(trace_dir)
        ctx.__enter__()
    b = train_rounds(1)  # compile
    _ = np.asarray(b._pred_train[:4])
    t0 = time.perf_counter()
    b = train_rounds(1)
    _ = np.asarray(b._pred_train[:4])
    report["round_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    b = train_rounds(num_boost_round)
    _ = np.asarray(b._pred_train[:4])
    report["train_total_s"] = time.perf_counter() - t0
    if ctx is not None:
        ctx.__exit__(None, None, None)

    report["num_boost_round"] = num_boost_round
    report["rows"] = ds.num_data_
    report["rows_per_s"] = ds.num_data_ * num_boost_round / \
        report["train_total_s"]
    # "f32x" is the internal explicit-f32 routing token — report the
    # user-facing name
    report["hist_dtype"] = "f32" if hd == "f32x" else hd
    # the tail policy rides in the ENCODING of the static width — surface
    # it as named fields, not the raw encoded int (ADVICE r3); decoded
    # through the single shared helper (code review r5)
    from ..models.tree import decode_wave_width

    w_dec, tail, over = decode_wave_width(ww)
    report["wave_width"] = w_dec
    report["wave_tail"] = tail
    if over is not None:
        report["wave_overgrow_leaves"] = over
    return report
