"""One run of one cell.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for.  Set-up (inputs from the seed, binning or deploy, warm-up,
compilation) is timed from the start of the process to the start of the
window; the window measures for ``--seconds``; then ``memory_peak_bytes``
is read, the program's state is freed and the plain reference decides
``correct``.  The last line of standard output is the result: with
``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, ``device.busy_s`` / ``device.window_s`` and the
``breakdown`` of a profiler trace taken around the window (cut to the
traffic file's ``trace_seconds``).  Each number compared is printed
beside its limit on standard error and under ``checks``, the result's
last key.

Nothing here names a cell, a configuration or a metric: see
``manifest.py`` for how the files are found.
"""

from __future__ import annotations

import time

_IMPORTED_AT = time.time()

import argparse
import json
import os
import shutil
import sys


def _process_started_at() -> float:
    """Wall-clock second at which this process started (Linux), else the
    moment this module was imported."""
    try:
        with open("/proc/self/stat") as f:
            ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            boot = next(float(line.split()[1]) for line in f
                        if line.startswith("btime"))
        started = boot + ticks / os.sysconf("SC_CLK_TCK")
        # btime has whole seconds: trust it only where it is plausible
        if 0.0 <= _IMPORTED_AT - started < 600.0:
            return started
    except (OSError, ValueError, StopIteration, IndexError):
        pass
    return _IMPORTED_AT


def _say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def main(argv=None, *, require_tpu: bool = True, root: str = None,
         fault: str = None) -> int:
    """``require_tpu``, ``root`` and ``fault`` (a fault of the kind's
    ``Cell`` planted in this run) are for the CPU tests: no flag or
    environment variable reaches them."""
    ap = argparse.ArgumentParser(prog="benchmark.run", description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    started_at = _process_started_at()

    from . import manifest as mf
    from .device import memory_parts, peaks_for, require_chips

    man = mf.Manifest(root or mf.ROOT)
    cell = man.cell(args.workload)
    devices = require_chips(int(cell["chips"]), require_tpu=require_tpu)
    reached_at = time.time()
    kind = devices[0].device_kind
    peaks = peaks_for(kind) if devices[0].platform == "tpu" else None
    config, traffic = man.config(cell), man.traffic(cell)
    _say(f"[bench] {cell['name']} seed={args.seed} seconds={args.seconds} "
         f"trace={args.trace} platform={devices[0].platform} kind={kind!r} "
         f"count={len(devices)}")

    run = mf.load_kind(traffic["kind"]).Cell(
        config, traffic, args.seed, devices, fault=fault)
    # where set-up goes before the kind's own phases: the interpreter and
    # this module, then JAX's start and its first contact with the chip
    run.counters.update(start_s=_IMPORTED_AT - started_at,
                        reach_chip_s=reached_at - _IMPORTED_AT)
    run.setup()
    setup_s = time.time() - started_at

    seconds, trace = float(args.seconds), None
    trace_dir = os.path.join(man.root, ".benchmark_tmp",
                             "trace-" + cell["name"])
    if args.trace:
        import jax

        seconds = min(seconds, float(traffic.get("trace_seconds", seconds)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
        jax.profiler.start_trace(trace_dir)
        try:
            win = run.window(seconds)
        finally:
            jax.profiler.stop_trace()
        from .reduce import trace as tr

        trace = tr.reduce_dir(trace_dir, len(devices),
                              devices[0].platform)
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        win = run.window(seconds)

    parts = memory_parts(devices)
    peak = sum(parts.values())
    run.counters.update(parts)
    run.release()
    released_at = time.time()
    # a number that is not finite is over any limit, and stays valid JSON
    checks = [(name, value if value == value and abs(value) != float("inf")
               else 1e300, limit) for name, value, limit in run.check()]
    run.counters["check_s"] = time.time() - released_at
    correct = all(value <= limit for _, value, limit in checks)

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": win["attempted"],
              "failed": win["failed"]}
    win["metrics"]["setup_s"] = setup_s
    if args.trace:
        ctx = {"trace": trace, "counters": run.counters, "config": config,
               "traffic": traffic, "peaks": peaks, "window": win,
               "device_kind": kind}
        wanted, metrics = man.metrics_of(cell["name"], "per_layer"), {}
        for m in wanted:
            spec = man.metric_spec(m["name"])
            value = man.metric_reader(m["name"], spec)(ctx, spec)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = trace.breakdown()
    else:
        wanted = man.metrics_of(cell["name"], "end_to_end")
        result["metrics"] = {
            m["name"]: {"value": float(win["metrics"][m["name"]]),
                        "unit": m["unit"]} for m in wanted}
        result["device"] = device
    result["window_s"] = win["window_s"]
    result["counters"] = {k: v for k, v in run.counters.items()
                          if isinstance(v, (int, float, list, str))}
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in checks}
    for name, value, limit in checks:
        _say(f"[check] {name} value={value:.6g} limit={limit:.6g} "
             f"{'ok' if value <= limit else 'OVER'}")
    _say(f"[check] correct={correct}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
