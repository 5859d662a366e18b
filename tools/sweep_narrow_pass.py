"""Time one partition-fused wave pass by width and by orientation (PR 32).

    python3 tools/sweep_narrow_pass.py [higgs] [epsilon] [mslr] [bosch]
                                      [--root <checkout>]    # one TPU chip

At each benchmark cell's shape (10,500,096 x 28, bfloat16, one kernel call a
pass; 400,128 x 2,000 in 63 feature blocks, hi/lo float32, two calls) the
operands are prepared ONCE at the tree's width 42, as the grower prepares
them, and ``hist_partition_fused_pallas`` is timed at W = 1 .. 42 with its
dot unturned and turned: a loop of 10 calls under one ``jit``, ended by
``block_until_ready``, the median of the repeats, in ms a pass.  The table
decides the kernel's ``TURNED_MAX_K`` and with it the schedule's narrow
width (``models.spec.narrow_width_for``; PERF.md section 6, PR 32).

``bosch`` (1,000,000 x 660 columns, 256 bins, bfloat16, 17 feature blocks
of 40: the sparse cell's bundle columns) times a narrow pass (W = 16) and
a root pass with every column's one-hot 16, 32, 64, 128 and 256 bins tall
(``histogram_pallas.feature_layout``; codes drawn below the height): how a
pass's time follows the height decides the rounding of
``histogram_pallas.onehot_heights`` (PERF.md section 6).  Under
``--root`` a checkout without layouts times the full height alone.

``mslr`` (2,270,296 x 136, bfloat16, 5 feature blocks of 32: the last one
holds 8 features and 24 rows of padding) times the three passes a tree
runs, on operands prepared at width 42: the wave pass at W = 42, the
narrow pass at W = 16 and the root pass, at 136 features and at 128 (four
full blocks: the floor a pass over 136 features approaches when the
padding costs nothing).  ``--root`` imports the kernels from another
checkout (a ``git archive`` of a parent commit) to time its passes on the
same chip.

Prints one JSON line a shape and writes
``chiprun_out/sweep_narrow_pass[.<root>].json``.  Needs a TPU: a CPU
timing is no device number.
"""
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARGS = sys.argv[1:]
if "--root" in ARGS:
    at = ARGS.index("--root")
    ROOT = os.path.abspath(ARGS[at + 1])
    del ARGS[at:at + 2]
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
from jax import lax

import lightgbm_tpu  # noqa: F401  (puts the compile-cache rule in force)
from lightgbm_tpu.ops import histogram_pallas
from lightgbm_tpu.ops.histogram_pallas import (_vmem_blocking,
                                               hist_fused_prepared,
                                               hist_partition_fused_pallas,
                                               prepare_wave_operands)

SHAPES = {
    "higgs": dict(n=10_500_096, f=28, dtype="bf16", repeats=5),
    "epsilon": dict(n=400_128, f=2000, dtype="f32", repeats=3),
    "mslr": dict(n=2_270_296, f=136, dtype="bf16", repeats=5, floor_f=128),
    "bosch": dict(n=1_000_000, f=660, dtype="bf16", repeats=5,
                  heights=(16, 32, 64, 128, 256)),
}
WIDTHS = (1, 2, 4, 8, 16, 32, 42)
TREE_WIDTH, NARROW_WIDTH, NUM_BINS, CALLS = 42, 16, 255, 10


def _operands(n, f, seed=0, codes_below=NUM_BINS, num_bins=NUM_BINS):
    """Random codes (below ``codes_below``) and statistics, prepared at the
    tree's width 42 as the grower prepares them: ``(bins_t, stats_t, leaf,
    thr)``."""
    key = jax.random.PRNGKey(seed)
    kb, ks, kl, kt = jax.random.split(key, 4)
    bins = jax.random.randint(kb, (n, f), 0, codes_below, jnp.int32).astype(
        jnp.uint8)
    stats = jnp.concatenate(
        [jax.random.normal(ks, (n, 2), jnp.float32),
         jnp.ones((n, 1), jnp.float32)], axis=1)
    bins_t, stats_t = jax.jit(
        lambda b, s: prepare_wave_operands(b, s, num_bins, TREE_WIDTH)[:2])(
            bins, stats)
    n_pad = bins_t.shape[1]
    leaf = jax.random.randint(kl, (n_pad,), 0, 1 << 20, jnp.int32)
    thr = jax.random.randint(kt, (n_pad,), 0, codes_below, jnp.int32)
    return bins_t, stats_t, leaf, thr


def _pv(leaf, thr, w, f):
    """The per-row node fields of a pass of ``w`` splits: every row in a
    leaf that splits, ranks spread over the wave."""
    rank = leaf % w
    zero = jnp.zeros(leaf.shape, jnp.float32)
    return jnp.stack([zero + 1.0, (leaf % f).astype(jnp.float32),
                      thr.astype(jnp.float32),
                      (2 * rank).astype(jnp.float32),
                      (leaf % 2).astype(jnp.float32), zero, zero, zero])


def _ms_per_call(loop, args, repeats):
    """``loop(*args)`` once to compile, then ``repeats`` timed runs of its
    ``CALLS`` calls: ``{"ms_per_pass": median, "min", "max"}``."""
    loop(*args).block_until_ready()
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        loop(*args).block_until_ready()
        times.append((time.perf_counter() - t0) / CALLS * 1e3)
    return {"ms_per_pass": statistics.median(times), "min": min(times),
            "max": max(times)}


def sweep(name, n, f, dtype, repeats):
    bins_t, stats_t, leaf, thr = _operands(n, f)
    f_blk, _, _, chunk = _vmem_blocking(f, NUM_BINS, 3 * TREE_WIDTH)
    rows = {}
    for w in WIDTHS:
        pv_t = _pv(leaf, thr, w, f)
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

            case = f"W{w}.{'turned' if turned else 'wide'}"
            rows[case] = _ms_per_call(loop, (bins_t, stats_t, pv_t, wfeat),
                                      repeats)
            print(name, case, rows[case], file=sys.stderr, flush=True)
    return {"shape": name, "rows": n, "features": f, "hist_dtype": dtype,
            "f_blk": f_blk, "chunk": chunk, "calls_per_loop": CALLS,
            "repeats": repeats, "ms_per_pass": rows}


def tree_passes(name, n, f, dtype, repeats, floor_f):
    """The wave pass (W = 42), the narrow pass (W = 16) and the root pass
    at ``f`` features and at ``floor_f``, in ms a pass."""
    rows = {}
    for feats in (f, floor_f):
        bins_t, stats_t, leaf, thr = _operands(n, feats)
        f_blk, n_fblk, f_pad, chunk = _vmem_blocking(feats, NUM_BINS,
                                                     3 * TREE_WIDTH)
        n_pad = bins_t.shape[1]
        for role, w in (("wave", TREE_WIDTH), ("narrow", NARROW_WIDTH)):
            pv_t = _pv(leaf, thr, w, feats)
            wfeat = (jnp.arange(w, dtype=jnp.int32) * 7) % feats

            @jax.jit
            def loop(bins_t, stats_t, pv_t, wfeat, w=w, feats=feats,
                     f_blk=f_blk, chunk=chunk):
                def body(_, acc):
                    hist, enc = hist_partition_fused_pallas(
                        bins_t, stats_t + acc * 0.0, pv_t, w, NUM_BINS,
                        chunk, hist_dtype=dtype, wfeat=wfeat,
                        num_features=feats, f_blk=f_blk,
                        name="lgbtpu_sweep")
                    return hist[0, 0, 0, 0] + enc[0].astype(jnp.float32)
                return lax.fori_loop(0, CALLS, body, jnp.float32(0.0))

            rows[f"F{feats}.{role}"] = _ms_per_call(
                loop, (bins_t, stats_t, pv_t, wfeat), repeats)
            print(name, f"F{feats}.{role}", rows[f"F{feats}.{role}"],
                  file=sys.stderr, flush=True)

        @jax.jit
        def root(bins_t, stats_t, feats=feats, f_blk=f_blk, chunk=chunk):
            seg = jnp.zeros((1, n_pad), jnp.int32)

            def body(_, acc):
                hist = hist_fused_prepared(
                    bins_t, stats_t + acc * 0.0, seg, 1, NUM_BINS, chunk,
                    f_blk, feats, hist_dtype=dtype, name="lgbtpu_sweep")
                return hist[0, 0, 0, 0]
            return lax.fori_loop(0, CALLS, body, jnp.float32(0.0))

        rows[f"F{feats}.root"] = _ms_per_call(root, (bins_t, stats_t),
                                              repeats)
        rows[f"F{feats}.blocking"] = {"f_blk": f_blk, "blocks": n_fblk,
                                      "f_pad": f_pad, "chunk": chunk}
        print(name, f"F{feats}.root", rows[f"F{feats}.root"],
              file=sys.stderr, flush=True)
        del bins_t, stats_t
    return {"shape": name, "rows": n, "features": f, "floor_features": floor_f,
            "hist_dtype": dtype, "root": ROOT, "calls_per_loop": CALLS,
            "repeats": repeats, "ms_per_pass": rows}


def height_passes(name, n, f, dtype, repeats, heights, num_bins=256):
    """A narrow pass (W = 16) and a root pass, in ms, with every column's
    one-hot ``h`` bins tall for each ``h`` of ``heights``."""
    layouts = hasattr(histogram_pallas, "feature_layout")
    f_blk, n_fblk, f_pad, chunk = _vmem_blocking(f, num_bins, 3 * TREE_WIDTH)
    rows = {"blocking": {"f_blk": f_blk, "blocks": n_fblk, "f_pad": f_pad,
                         "chunk": chunk}}
    for h in (heights if layouts else (num_bins,)):
        kw = ({"layout": histogram_pallas.feature_layout(
            f, f_blk, num_bins, (h,) * f)} if layouts else {})
        bins_t, stats_t, leaf, thr = _operands(n, f, codes_below=h,
                                               num_bins=num_bins)
        n_pad = bins_t.shape[1]
        pv_t = _pv(leaf, thr, NARROW_WIDTH, f)
        wfeat = (jnp.arange(NARROW_WIDTH, dtype=jnp.int32) * 7) % f

        @jax.jit
        def narrow(bins_t, stats_t, pv_t, wfeat, kw=kw):
            def body(_, acc):
                hist, enc = hist_partition_fused_pallas(
                    bins_t, stats_t + acc * 0.0, pv_t, NARROW_WIDTH,
                    num_bins, chunk, hist_dtype=dtype, wfeat=wfeat,
                    num_features=f, f_blk=f_blk, name="lgbtpu_sweep", **kw)
                return hist[0, 0, 0, 0] + enc[0].astype(jnp.float32)
            return lax.fori_loop(0, CALLS, body, jnp.float32(0.0))

        @jax.jit
        def root(bins_t, stats_t, kw=kw):
            seg = jnp.zeros((1, n_pad), jnp.int32)

            def body(_, acc):
                hist = hist_fused_prepared(
                    bins_t, stats_t + acc * 0.0, seg, 1, num_bins, chunk,
                    f_blk, f, hist_dtype=dtype, name="lgbtpu_sweep", **kw)
                return hist[0, 0, 0, 0]
            return lax.fori_loop(0, CALLS, body, jnp.float32(0.0))

        for role, loop, args in (
                ("narrow", narrow, (bins_t, stats_t, pv_t, wfeat)),
                ("root", root, (bins_t, stats_t))):
            rows[f"H{h}.{role}"] = _ms_per_call(loop, args, repeats)
            print(name, f"H{h}.{role}", rows[f"H{h}.{role}"],
                  file=sys.stderr, flush=True)
        del bins_t, stats_t
    return {"shape": name, "rows": n, "features": f, "num_bins": num_bins,
            "hist_dtype": dtype, "root": ROOT, "calls_per_loop": CALLS,
            "repeats": repeats, "ms_per_pass": rows}


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("sweep_narrow_pass needs a TPU: a CPU timing is no device "
                 "number")
    names = ARGS or list(SHAPES)
    out = {"device": dev.device_kind, "root": ROOT, "shapes": []}
    for name in names:
        shape = SHAPES[name]
        res = (tree_passes(name, **shape) if "floor_f" in shape
               else height_passes(name, **shape) if "heights" in shape
               else sweep(name, **shape))
        out["shapes"].append(res)
        print(json.dumps(res), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    own = ROOT == os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = ("chiprun_out/sweep_narrow_pass.json" if own else
            f"chiprun_out/sweep_narrow_pass.{os.path.basename(ROOT)}.json")
    with open(path, "w") as fh:
        json.dump(out, fh, indent=1)


if __name__ == "__main__":
    main()
