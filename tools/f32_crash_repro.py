"""Minimal repro harness for the f32 (hi/lo) kernel-mode worker crash.

PERF_HISTORY.md "Known issue": the f32 two-pass histogram mode intermittently
crashed the TPU worker (r3-r5 hardware) at the 1M-row Higgs shape after a few
hundred kernel invocations; bf16/int8 have run thousands clean and f32 is
stable at <=200k rows.  VERDICT r3 #7 asks for a shape/pressure bisect and
a checked-in repro.

This script walks a (rows x mode x chunk) grid, hammering each config with
``--reps`` back-to-back kernel invocations in a SUBPROCESS (a crash
poisons the client process, so each cell gets a fresh one), and prints the
survival table.  Run it only when you are prepared to crash the worker
repeatedly — it exists to make the fault reproducible, not to avoid it.

Usage:  python tools/f32_crash_repro.py [--reps 300] [--quick]
"""

import json
import subprocess
import sys
from pathlib import Path

CELL = r"""
import sys, json
import numpy as np, jax, jax.numpy as jnp
sys.path.insert(0, {repo!r})
from lightgbm_tpu.ops.histogram_pallas import hist_fused_pallas

n, mode, chunk, reps = {n}, {mode!r}, {chunk}, {reps}
rng = np.random.default_rng(0)
bins = jnp.asarray(rng.integers(0, 256, (n, 28)).astype(np.uint8))
stats = jnp.asarray(rng.normal(0, 1, (n, 3)).astype(np.float32))
seg = jnp.asarray(rng.integers(0, 42, n).astype(np.int32))

f = jax.jit(lambda b, s, g: hist_fused_pallas(
    b, s, g, 42, 256, chunk=chunk, hist_dtype=mode))
out = f(bins, stats, seg)
out.block_until_ready()
for i in range(reps):
    out = f(bins, stats, seg)
out.block_until_ready()
print("@@OK@@")
"""


def main():
    reps = 300
    if "--reps" in sys.argv:
        reps = int(sys.argv[sys.argv.index("--reps") + 1])
    quick = "--quick" in sys.argv
    rows = [200_000, 500_000, 1_000_000] if not quick else [1_000_000]
    modes = ["bf16", "f32"] if not quick else ["f32"]
    chunks = [None, 1024, 512]
    repo = str(Path(__file__).resolve().parent.parent)

    table = []
    for n in rows:
        for mode in modes:
            for chunk in chunks:
                code = CELL.format(repo=repo, n=n, mode=mode,
                                   chunk=chunk or "None", reps=reps)
                r = subprocess.run([sys.executable, "-c", code],
                                   capture_output=True, text=True,
                                   timeout=1800)
                ok = "@@OK@@" in r.stdout
                err = "" if ok else (r.stderr.strip().splitlines()
                                     or ["?"])[-1][-160:]
                cell = {"n": n, "mode": mode, "chunk": chunk,
                        "reps": reps, "ok": ok, "err": err}
                table.append(cell)
                print(json.dumps(cell), flush=True)
    print(json.dumps({"survival_table": table}))


if __name__ == "__main__":
    main()
