"""Serving-runtime benchmark: throughput + compile-cache behavior per bucket.

Measures PredictorRuntime dispatch throughput (rows/sec, warm) at every
power-of-two batch bucket 2^0 .. 2^14, plus the compile-cache hit rate of
a mixed-size workload, and writes the artifact the issue asks for
(``BENCH_SERVE_r06.json``).  Runs on CPU JAX by default so the artifact is
reproducible without an accelerator.

Usage: python tools/bench_serving.py [n_trees] [out.json]
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, ".")

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import lightgbm_tpu as lgb
from lightgbm_tpu.serving import PredictorRuntime, ServingStats, pack_booster

MAX_BUCKET = 1 << 14
REPEATS = 5


def build_model(n_trees: int):
    rng = np.random.default_rng(0)
    n, f = 20_000, 16
    X = rng.normal(size=(n, f))
    y = (2.0 * X[:, 0] + np.sin(3 * X[:, 1]) + 0.5 * X[:, 2] * X[:, 3]
         + 0.1 * rng.normal(size=n))
    booster = lgb.train(
        {"objective": "regression", "num_leaves": 31, "verbosity": -1},
        lgb.Dataset(X, label=y), num_boost_round=n_trees)
    return booster, X


def bench_buckets(runtime, codes):
    """Warm rows/sec per bucket (first dispatch per bucket = the compile)."""
    rows = []
    for bucket in runtime.buckets:
        batch = np.resize(codes, (bucket, codes.shape[1]))
        t0 = time.perf_counter()
        runtime.predict_binned(batch)            # cold: compile + run
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(REPEATS):
            runtime.predict_binned(batch)        # warm: cache hits only
        warm_s = (time.perf_counter() - t0) / REPEATS
        rows.append({
            "bucket": bucket,
            "compile_ms": compile_s * 1e3,
            "warm_ms": warm_s * 1e3,
            "rows_per_sec": bucket / warm_s if warm_s > 0 else None,
        })
        print(f"bucket {bucket:6d}: compile {compile_s*1e3:8.1f} ms  "
              f"warm {warm_s*1e3:8.2f} ms  "
              f"{bucket/warm_s/1e3:9.1f} krows/s", flush=True)
    return rows


def bench_mixed(runtime, codes, n_batches: int = 200):
    """Mixed-size workload: cache hit rate once every bucket is compiled."""
    rng = np.random.default_rng(1)
    sizes = rng.integers(1, 1001, size=n_batches)
    t0 = time.perf_counter()
    total = 0
    for n in sizes:
        runtime.predict_binned(np.resize(codes, (int(n), codes.shape[1])))
        total += int(n)
    elapsed = time.perf_counter() - t0
    snap = runtime.stats.snapshot()
    hits = sum(b["cache_hits"] for b in snap["buckets"])
    misses = sum(b["cache_misses"] for b in snap["buckets"])
    return {
        "batches": n_batches,
        "rows": total,
        "rows_per_sec": total / elapsed,
        "num_compiles": runtime.num_compiles,
        "cache_hits": hits,
        "cache_misses": misses,
        "cache_hit_rate": hits / (hits + misses) if hits + misses else None,
        "padding_waste": (
            sum(b["padded_rows"] for b in snap["buckets"])
            / max(1, sum(b["rows"] + b["padded_rows"]
                         for b in snap["buckets"]))),
    }


def main():
    import jax

    n_trees = int(sys.argv[1]) if len(sys.argv) > 1 else 100
    out_path = sys.argv[2] if len(sys.argv) > 2 else "BENCH_SERVE_r06.json"
    booster, X = build_model(n_trees)
    packed = pack_booster(booster)
    codes = np.asarray(packed.bin_mapper.transform(X))

    runtime = PredictorRuntime(packed, max_bucket=MAX_BUCKET,
                               max_cache_entries=32, stats=ServingStats())
    per_bucket = bench_buckets(runtime, codes)

    mixed_rt = PredictorRuntime(packed, max_bucket=1024,
                                stats=ServingStats())
    mixed = bench_mixed(mixed_rt, codes)
    print(f"mixed workload: {mixed['rows_per_sec']/1e3:.1f} krows/s, "
          f"{mixed['num_compiles']} compiles, "
          f"hit rate {mixed['cache_hit_rate']:.3f}", flush=True)

    artifact = {
        "bench": "serving_runtime",
        "round": 6,
        "backend": jax.default_backend(),
        "model": {"n_trees": packed.num_trees, "num_leaves": 31,
                  "n_features": codes.shape[1],
                  "depth_cap": packed.depth_cap},
        "max_bucket": MAX_BUCKET,
        "repeats": REPEATS,
        "per_bucket": per_bucket,
        "mixed_workload": mixed,
    }
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=2)
        f.write("\n")
    print(f"wrote {out_path}")


if __name__ == "__main__":
    main()
