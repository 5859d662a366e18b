"""The readings that a cell's limits are set from, on the chip, at the
cell's own size, many seeds to a process.

    python3 -m benchmark.readings --workload <name> --seeds 11 12 13 \\
        --modes program control half_batch fewer_leaves [--window 0]

For each seed the inputs are made once and every mode builds its own run
on them through the kind's own set-up, window and check: ``program`` is
the configuration as stated; ``control`` is the configuration with its
``control.params`` switched on (the nearest precision below the stated
one); the others plant a fault in the timed path, given to that run's
``Cell`` alone (the kind's ``TIMED_PATH_FAULTS`` and ``PARAM_FAULTS``).
One JSON line per (seed, mode) with every number compared, ``correct`` by
the limits as committed; ``chiprun_out/readings-<name>.jsonl`` keeps them.  Not part of a benchmark run: nothing here is timed.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time


def variant(config: dict, mode: str):
    """``(config, fault)`` of one mode: ``program`` is the configuration as
    stated, ``control`` has every group of ``config["control"]`` that is a
    dict merged over the group of the same name (``control.params`` over
    ``params``), any other mode names a fault of the kind's ``Cell``."""
    cfg = copy.deepcopy(config)
    if mode == "program":
        return cfg, None
    if mode == "control":
        for key, over in cfg["control"].items():
            if isinstance(over, dict):
                cfg[key] = dict(cfg.get(key, {}), **over)
        return cfg, None
    return cfg, mode


def main(argv=None, *, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.readings")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--modes", nargs="+", default=["program", "control"])
    ap.add_argument("--window", type=float, default=0.0)
    ap.add_argument("--out", default="chiprun_out")
    args = ap.parse_args(argv)

    from . import manifest as mf
    from .device import require_chips

    man = mf.Manifest()
    cell = man.cell(args.workload)
    devices = require_chips(int(cell["chips"]), require_tpu=require_tpu)
    config, traffic = man.config(cell), man.traffic(cell)
    kind = mf.load_kind(traffic["kind"])
    os.makedirs(args.out, exist_ok=True)
    out = open(os.path.join(args.out, f"readings-{cell['name']}.jsonl"), "a")
    for seed in args.seeds:
        base = kind.Cell(config, traffic, seed, devices)
        base.make_inputs()
        for mode in args.modes:
            cfg, fault = variant(config, mode)
            t0 = time.perf_counter()
            run = kind.Cell(cfg, traffic, seed, devices, fault=fault)
            run.share_inputs(base)
            run.build()
            run.window(args.window)
            run.release()
            checks = run.check()
            line = {"workload": cell["name"], "seed": seed, "mode": mode,
                    "kind": devices[0].device_kind,
                    "correct": all(v <= lim for _, v, lim in checks),
                    "checks": {n: v for n, v, _ in checks},
                    "limits": {n: lim for n, _, lim in checks},
                    "counters": {k: v for k, v in run.counters.items()
                                 if isinstance(v, (int, float, list, str))},
                    "memory": {k: v for k, v in
                               (devices[0].memory_stats() or {}).items()
                               if "bytes" in k},
                    "seconds": time.perf_counter() - t0}
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
            out.flush()
            del run
            # each mode is a program of its own with scratch of its own:
            # unload it before the next is compiled
            import jax

            jax.clear_caches()
        del base
    out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
