"""Micro-benchmark: strict vs frontier grower at Higgs-ish scale on TPU.

Usage: python tools/bench_grower.py [n_rows] [rounds]
       python tools/bench_grower.py --artifact [out.json]

The --artifact mode writes the BENCH_SELF_r* self-measurement dict
(kernels_per_round from tools/hlo_counts plus split_iter_ms and the
F=136 partition-fusion round timings) instead of the table.
"""

import json
import sys
import time

import numpy as np

sys.path.insert(0, ".")

import lightgbm_tpu as lgb
from lightgbm_tpu.utils.datasets import make_higgs_like


def run(n, num_leaves, policy, rounds=10, width=None):
    X, y = make_higgs_like(n)
    params = {
        "objective": "binary", "num_leaves": num_leaves,
        "learning_rate": 0.1, "verbosity": -1, "grow_policy": policy,
        "min_data_in_leaf": 20,
    }
    if width:
        params["wave_width"] = width
    ds = lgb.Dataset(X, label=y)
    ds.construct()
    b = lgb.Booster(params, ds)
    b.update()  # compile + run round 0
    t0 = time.perf_counter()
    for _ in range(rounds):
        b.update()
    import jax
    jax.block_until_ready(b._pred_train)
    dt = (time.perf_counter() - t0) / rounds
    return dt


def _time_grow(grow, reps=5):
    import jax
    f = jax.jit(grow)
    jax.block_until_ready(f())    # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = f()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def split_iter_ms(n=50_000, num_leaves=31, num_bins=64, fuse=True):
    """ms per strict split iteration, mega-kernel on/off (grow_tree
    directly — fuse_split is not a Booster param)."""
    import jax.numpy as jnp
    from lightgbm_tpu.models.tree import grow_tree
    from lightgbm_tpu.ops.split import SplitContext

    num_features = 28               # higgs-like width
    rng = np.random.RandomState(0)
    bins = jnp.asarray(rng.randint(0, num_bins, size=(n, num_features))
                       .astype(np.int32))
    g = jnp.asarray(rng.randn(n).astype(np.float32))
    stats = jnp.stack([g, jnp.ones(n, jnp.float32),
                       jnp.ones(n, jnp.float32)], -1)
    fmask = jnp.ones(num_features, jnp.float32)
    ctx = SplitContext(jnp.float32(0.0), jnp.float32(1.0), jnp.float32(20.0),
                       jnp.float32(1e-3), jnp.float32(0.0))
    dt = _time_grow(lambda: grow_tree(bins, stats, fmask, ctx, num_leaves,
                                      num_bins, 0, fuse_split=fuse))
    return dt * 1e3 / (num_leaves - 1)


def mslr_round_ms(n=60_000, num_features=136, num_bins=256, num_leaves=31,
                  fuse_partition=True):
    """ms/round of the frontier grower at the MSLR shape (F=136) — the
    class the r5 single-block partition kernel gated off."""
    import jax.numpy as jnp
    from lightgbm_tpu.models.spec import WaveSchedule
    from lightgbm_tpu.models.tree import grow_tree
    from lightgbm_tpu.ops.split import SplitContext

    rng = np.random.RandomState(0)
    bins = jnp.asarray(rng.randint(0, num_bins, size=(n, num_features))
                       .astype(np.int32))
    g = jnp.asarray(rng.randn(n).astype(np.float32))
    stats = jnp.stack([g, jnp.ones(n, jnp.float32),
                       jnp.ones(n, jnp.float32)], -1)
    fmask = jnp.ones(num_features, jnp.float32)
    ctx = SplitContext(jnp.float32(0.0), jnp.float32(1.0), jnp.float32(20.0),
                       jnp.float32(1e-3), jnp.float32(0.0))
    dt = _time_grow(lambda: grow_tree(
        bins, stats, fmask, ctx, num_leaves, num_bins, -1,
        wave=WaveSchedule(8, "half"), hist_impl="pallas", hist_dtype="bf16",
        fuse_partition=fuse_partition), reps=2)
    return dt * 1e3


def artifact(path):
    from tools.hlo_counts import kernels_per_round_summary

    out = dict(kernels_per_round_summary(e=40))
    out["split_iter_ms_unfused"] = round(split_iter_ms(fuse=False), 3)
    out["split_iter_ms"] = round(split_iter_ms(fuse=True), 3)
    out["mslr_f136_round_ms_unfused_partition"] = round(
        mslr_round_ms(fuse_partition=False), 1)
    out["mslr_f136_round_ms_fused_partition"] = round(
        mslr_round_ms(fuse_partition=True), 1)
    out["note_kernels"] = (
        "kernels/split-iter: r4 TPU-measured baseline 50 (PERF_HISTORY.md '49 "
        "fusions + 1 custom-call'); tpu_model = CPU compile with the "
        "mega-kernel as one custom-call (tools/hlo_counts.py stub); "
        "fused_cpu_inlined is interpret-mode Pallas inlined by XLA:CPU "
        "and NOT a launch count")
    out["note_timing"] = (
        "timings CPU-measured (interpret-mode Pallas inside jit); "
        "split_iter_ms over strict n=50k nl=31 B=64 F=28; "
        "mslr_f136_round_ms over frontier n=60k F=136 B=256 nl=31 "
        "wave_width=8 — relative fused-vs-unfused movement is the "
        "signal, absolute ms is not TPU ms; on CPU the launch-count "
        "win cannot show, so near-parity here just confirms the fused "
        "paths cost no extra FLOPs")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    print(json.dumps(out, indent=1))
    return out


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--artifact":
        artifact(sys.argv[2] if len(sys.argv) > 2 else "BENCH_SELF_r07.json")
        sys.exit(0)
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    rounds = int(sys.argv[2]) if len(sys.argv) > 2 else 10
    for leaves in (31, 127):
        for policy in ("leafwise", "frontier"):
            dt = run(n, leaves, policy, rounds)
            print(f"n={n} leaves={leaves:4d} {policy:9s}: "
                  f"{dt*1e3:8.1f} ms/round  {n/dt/1e6:7.2f} Mrows/s",
                  flush=True)
