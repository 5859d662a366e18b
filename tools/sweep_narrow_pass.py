"""Time one partition-fused wave pass by width and by orientation (PR 32).

    chiprun -- python3 tools/sweep_narrow_pass.py [higgs] [epsilon]

At each benchmark cell's shape (10,500,096 x 28, bfloat16, one kernel call a
pass; 400,128 x 2,000 in 63 feature blocks, hi/lo float32, two calls) the
operands are prepared ONCE at the tree's width 42, as the grower prepares
them, and ``hist_partition_fused_pallas`` is timed at W = 1 .. 42 with its
dot unturned and turned: a loop of 10 calls under one ``jit``, ended by
``block_until_ready``, the median of the repeats, in ms a pass.  The table
decides the kernel's ``TURNED_MAX_K`` and with it the schedule's narrow
width (``models.spec.narrow_width_for``; PERF.md section 6, PR 32).
Prints one JSON line a shape and writes
``chiprun_out/sweep_narrow_pass.json``.  Needs a TPU: a CPU timing is no
device number.
"""
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

import lightgbm_tpu  # noqa: F401  (puts the compile-cache rule in force)
from lightgbm_tpu.ops.histogram_pallas import (_vmem_blocking,
                                               hist_partition_fused_pallas,
                                               prepare_wave_operands)

SHAPES = {
    "higgs": dict(n=10_500_096, f=28, dtype="bf16", repeats=5),
    "epsilon": dict(n=400_128, f=2000, dtype="f32", repeats=3),
}
WIDTHS = (1, 2, 4, 8, 16, 32, 42)
TREE_WIDTH, NUM_BINS, CALLS = 42, 255, 10


def sweep(name, n, f, dtype, repeats):
    key = jax.random.PRNGKey(0)
    kb, ks, kl, kt = jax.random.split(key, 4)
    bins = jax.random.randint(kb, (n, f), 0, NUM_BINS, jnp.int32).astype(
        jnp.uint8)
    stats = jnp.concatenate(
        [jax.random.normal(ks, (n, 2), jnp.float32),
         jnp.ones((n, 1), jnp.float32)], axis=1)
    bins_t, stats_t = jax.jit(
        lambda b, s: prepare_wave_operands(b, s, NUM_BINS, TREE_WIDTH)[:2])(
            bins, stats)
    del bins
    f_blk, _, _, chunk = _vmem_blocking(f, NUM_BINS, 3 * TREE_WIDTH)
    n_pad = bins_t.shape[1]
    leaf = jax.random.randint(kl, (n_pad,), 0, 1 << 20, jnp.int32)
    thr = jax.random.randint(kt, (n_pad,), 0, NUM_BINS, jnp.int32)
    rows = {}
    for w in WIDTHS:
        rank = leaf % w
        zero = jnp.zeros((n_pad,), jnp.float32)
        pv_t = jnp.stack([zero + 1.0, (leaf % f).astype(jnp.float32),
                          thr.astype(jnp.float32),
                          (2 * rank).astype(jnp.float32),
                          (leaf % 2).astype(jnp.float32), zero, zero, zero])
        wfeat = (jnp.arange(w, dtype=jnp.int32) * 7) % f
        for turned in (False, True):

            @jax.jit
            def loop(bins_t, stats_t, pv_t, wfeat, w=w, turned=turned):
                def body(_, acc):
                    # the carry feeds the operand, so no call is hoisted
                    hist, enc = hist_partition_fused_pallas(
                        bins_t, stats_t + acc * 0.0, pv_t, w, NUM_BINS,
                        chunk, hist_dtype=dtype, wfeat=wfeat,
                        num_features=f, f_blk=f_blk, bins_minor=turned,
                        name="lgbtpu_sweep")
                    return hist[0, 0, 0, 0] + enc[0].astype(jnp.float32)
                return lax.fori_loop(0, CALLS, body, jnp.float32(0.0))

            loop(bins_t, stats_t, pv_t, wfeat).block_until_ready()
            times = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                loop(bins_t, stats_t, pv_t, wfeat).block_until_ready()
                times.append((time.perf_counter() - t0) / CALLS * 1e3)
            case = f"W{w}.{'turned' if turned else 'wide'}"
            rows[case] = {"ms_per_pass": statistics.median(times),
                          "min": min(times), "max": max(times)}
            print(name, case, rows[case], file=sys.stderr, flush=True)
    return {"shape": name, "rows": n, "features": f, "hist_dtype": dtype,
            "f_blk": f_blk, "chunk": chunk, "calls_per_loop": CALLS,
            "repeats": repeats, "ms_per_pass": rows}


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("sweep_narrow_pass needs a TPU: a CPU timing is no device "
                 "number")
    names = sys.argv[1:] or list(SHAPES)
    out = {"device": dev.device_kind, "shapes": []}
    for name in names:
        res = sweep(name, **SHAPES[name])
        out["shapes"].append(res)
        print(json.dumps(res), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/sweep_narrow_pass.json", "w") as fh:
        json.dump(out, fh, indent=1)


if __name__ == "__main__":
    main()
