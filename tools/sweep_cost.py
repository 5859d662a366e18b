"""XLA cost analysis + measured slope of one fused-cv bucket round.

The 108-config sweep is per-op-bound (PERF_HISTORY.md r4 finding 3): ~30-70 ms
per while-loop round for ~0.3 ms of FLOPs.  This tool compiles one
bucket's ``run_segment`` at the exact sweep shape and prints the
compiled program's cost_analysis (bytes accessed, flops) plus a
slope-timed ms/round, so op-count/traffic reduction work has a target.

Usage: python tools/sweep_cost.py [num_leaves] [n_configs]
"""
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp


def main():
    nl = int(sys.argv[1]) if len(sys.argv) > 1 else 31
    n_configs = int(sys.argv[2]) if len(sys.argv) > 2 else 8

    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.datasets import (
        make_synthetic_diamonds, train_test_split_bernoulli)
    from lightgbm_tpu.models.fused import (
        _fused_cv_fn, _fused_wave_width, FusedCVCarry)
    from lightgbm_tpu.models.gbdt import (
        HyperScalars, _objective_static_key, resolve_hist_dtype)
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.config import parse_params

    X, y, _ = make_synthetic_diamonds()
    tr, _te = train_test_split_bernoulli(len(y), 0.85, seed=3928272)
    ds = lgb.Dataset(X[tr], label=y[tr])
    ds.construct()
    n_pad = int(ds.row_mask.shape[0])
    nfold = 5
    batch = n_configs * nfold

    p = parse_params({"objective": "regression", "verbosity": -1,
                      "hist_dtype": "bf16", "num_leaves": nl,
                      "learning_rate": 0.1, "bagging_fraction": 0.8,
                      "bagging_freq": 4})
    hd = resolve_hist_dtype(p, n_pad)
    obj = create_objective(p)
    if hasattr(obj, "prepare"):
        obj.prepare(np.asarray(ds.get_label()), np.ones(ds.num_data()))
    run_segment, init_carry, finalize = _fused_cv_fn(
        _objective_static_key(obj, p), nl, ds.num_bins, "l2", 0.9, 1.5,
        1000, 4, n_configs, nfold, "auto", 131072, hd, None, 1,
        _fused_wave_width(p, n_pad, hd), bynode_off=True)

    rng = np.random.default_rng(1)
    assign = rng.permutation(ds.num_data()) % nfold
    tm = np.zeros((batch, n_pad), np.float32)
    vm = np.zeros((batch, n_pad), np.float32)
    for b in range(batch):
        tm[b, :ds.num_data()] = assign != (b % nfold)
        vm[b, :ds.num_data()] = assign == (b % nfold)
    n_in_fold = tm.sum(axis=1).astype(np.float32)

    rep = lambda v: jnp.full((batch,), v, jnp.float32)
    hyper_b = HyperScalars(
        learning_rate=rep(0.1), lambda_l1=rep(0.0), lambda_l2=rep(0.0),
        min_data_in_leaf=rep(20), min_sum_hessian=rep(1e-3),
        min_gain_to_split=rep(0.0), max_depth=rep(-1).astype(jnp.int32),
        feature_fraction_bynode=rep(1.0), top_rate=rep(0.2),
        other_rate=rep(0.1), max_delta_step=rep(0.0), path_smooth=rep(0.0),
        linear_lambda=rep(0.0))

    carry = init_carry(n_pad, jnp.zeros((batch,), jnp.float32))
    carry = carry._replace(bag=jnp.asarray(tm))
    args = (jnp.asarray(tm), jnp.asarray(vm), hyper_b, rep(0.8), rep(1.0),
            jnp.asarray(n_in_fold), jnp.int32(0),
            jnp.zeros((n_configs,), jnp.float32),   # es_min_delta_c
            jax.random.PRNGKey(0))

    lowered = run_segment.lower(carry, jnp.int32(10), ds.X_binned, ds.y,
                                ds.w, *args)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    flops = ca.get("flops", 0.0)
    bytes_acc = ca.get("bytes accessed", 0.0)
    print(f"nl={nl} E={batch} n_pad={n_pad} wave_width="
          f"{_fused_wave_width(p, n_pad, hd)}")
    print(f"  per-10-round segment: flops={flops/1e9:.2f} G  "
          f"bytes={bytes_acc/1e9:.3f} GB")
    print(f"  implied/round @800GB/s: {bytes_acc/10/800e9*1e3:.2f} ms "
          f"(traffic)  @197T: {flops/10/197e12*1e3:.3f} ms (flops)")
    for k in sorted(ca):
        if k.startswith("bytes accessed") and ca[k] > bytes_acc * 0.02:
            print(f"    {k}: {ca[k]/1e9:.3f} GB")

    # measured slope ms/round
    def run(k):
        c = run_segment(carry, jnp.int32(k), ds.X_binned, ds.y, ds.w, *args)
        np.asarray(c.r)
        return c

    run(2)
    t0 = time.perf_counter(); run(2); t1 = time.perf_counter() - t0
    t0 = time.perf_counter(); run(12); t2 = time.perf_counter() - t0
    print(f"  measured: {(t2-t1)/10*1e3:.2f} ms/round (slope)")


if __name__ == "__main__":
    main()
