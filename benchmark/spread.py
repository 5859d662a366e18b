"""How the rate's spread across seeds falls with the window's length: the
reading ISSUE 36 asked for, to set ``run_seconds`` and the rate's ``bound``.
What it read, while every seed drew a table of its own, was the seed's
trees (``PERF.md`` section 2); since ``datagen.table`` gives every seed the
configuration's one table, it reads the machine's noise.

    python3 -m benchmark.spread --workload <cell> --seeds 11 12 13 \\
        --seconds 80 --windows 20 40 51 [--plain 2 --plain-seconds 20]
    python3 -m benchmark.spread --read chiprun_out/spread-*.jsonl \\
        --windows 20 40 51

A tool like ``readings.py``, not a run.  One fresh ``python3 -m
benchmark.run --trace 0`` process a seed, as the driver starts them; the
window keeps its clock after every call (counter ``window_call_s``), so
ONE long run says what every shorter window of the same run would have
read: the first call whose time is at least W closes a window of W
seconds, and the rate is the rows x rounds up to that call over its time,
the arithmetic of the kind's ``window()``.  Per W: each seed's rate, the
median, and the spread as the driver takes it (``trimmed_spread``) beside
the spread by quartiles (``quartile_spread``).  ``--plain N`` also makes a
plain ``--plain-seconds`` run on the first N seeds and says how far it
lies from the long run's figure at that W: the window's first rounds are
the same rounds.  ``--read`` reckons again from the lines an earlier call
kept (``chiprun_out/spread-<cell>.jsonl``), every cell it finds, and
applies the rule of ``choose`` over the cells together.

This process never touches JAX: each child needs the chip for itself.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import subprocess
import sys

# the bounds the rule may choose from, and the factor the bound has to
# keep over the spread (the driver's note fires at half the bound)
BOUNDS = (0.02, 0.03, 0.04, 0.05)
ROOM = 2.0


def rates_at(call_s: list, rows: int, rounds_per_call: int,
             windows: list) -> dict:
    """``{W: (rate, rounds, window_s)}`` for every W that the run's clock
    reaches: call k, the first with ``call_s[k-1] >= W``, closes the
    window, and the rate is ``rows * k * rounds_per_call / call_s[k-1]``.
    A W past the run's last call is left out: nothing was read there."""
    out = {}
    for w in windows:
        for k, t in enumerate(call_s, 1):
            if t >= w:
                out[w] = (rows * k * rounds_per_call / t,
                          k * rounds_per_call, t)
                break
    return out


def _without_farthest(values: list, med: float) -> list:
    """The values in order, the one farthest from ``med`` left out."""
    kept = sorted(values)
    if len(kept) > 2:
        kept.remove(max(kept, key=lambda v: abs(v - med)))
    return kept


def trimmed_spread(values: list) -> float:
    """(max - min) over the median of all the values, with the value
    farthest from that median left out where that narrows it."""
    med = statistics.median(values)
    kept = _without_farthest(values, med)
    return (kept[-1] - kept[0]) / med


def quartile_spread(values: list, trim: bool = False) -> float:
    """The distance between the first and the third quartile
    (``statistics.quantiles(values, n=4)``) as a share of the median;
    ``trim`` leaves out the value farthest from the median first."""
    med = statistics.median(values)
    kept = _without_farthest(values, med) if trim else values
    q1, _, q3 = statistics.quantiles(kept, n=4)
    return (q3 - q1) / med


def bound_for(spread: float):
    """The smallest of ``BOUNDS`` that is at least ``ROOM`` x the spread,
    or ``None`` where the largest is too small."""
    return next((b for b in BOUNDS if b >= ROOM * spread), None)


def choose(spread_by_window: dict):
    """``(W, bound)`` from ``{W: S(W)}``, S the larger of the cells'
    spreads: the W with the smallest admissible bound, the shorter W on a
    tie; where no W reaches the largest of ``BOUNDS``, the longest W and
    ``ROOM`` x its spread rounded up to a whole percent."""
    best = None
    for w in sorted(spread_by_window):
        b = bound_for(spread_by_window[w])
        if b is not None and (best is None or b < best[1]):
            best = (w, b)
    if best is None:
        w = max(spread_by_window)
        best = (w, math.ceil(ROOM * spread_by_window[w] * 100) / 100)
    return best


def run_once(workload: str, seed: int, seconds: float) -> dict:
    """One run of the command in a process of its own; its result line
    with ``seed`` and ``seconds`` added, or ``{"rc": n}`` where it failed."""
    cmd = [sys.executable, "-m", "benchmark.run", "--workload", workload,
           "--seed", str(seed), "--seconds", str(seconds), "--trace", "0"]
    done = subprocess.run(cmd, stdout=subprocess.PIPE, text=True)
    lines = [ln for ln in done.stdout.splitlines() if ln.strip()]
    if done.returncode != 0 or not lines:
        return {"rc": done.returncode, "workload": workload, "seed": seed,
                "seconds": seconds}
    return dict(json.loads(lines[-1]), workload=workload, seed=seed,
                seconds=seconds, rc=0)


def table(runs: list, windows: list) -> dict:
    """The reckoning over one cell's long runs: per W the seeds' rates,
    their median and spreads; per seed what the run itself says."""
    per_w = {w: [] for w in windows}
    seeds = []
    for r in runs:
        c = r["counters"]
        got = rates_at(c["window_call_s"], c["rows"], c["rounds_per_call"],
                       windows)
        for w, reading in got.items():
            per_w[w].append(reading[0])
        seeds.append({
            "seed": r["seed"], "seconds": r["seconds"],
            "correct": r["correct"], "failed": r["failed"],
            "compiles_in_window": r["checks"]["compiles_in_window"]["value"],
            "window_s": r["window_s"], "rounds": c["window_rounds"],
            "setup_s": r["metrics"]["setup_s"]["value"],
            "reach_chip_s": c.get("reach_chip_s"),
            "rounds_at": {w: reading[1] for w, reading in got.items()}})
    out = {"seeds": seeds, "windows": {}}
    for w, rates in per_w.items():
        if len(rates) < 2:
            continue
        out["windows"][w] = {
            "rates": rates, "median": statistics.median(rates),
            "spread": trimmed_spread(rates),
            "spread_all": (max(rates) - min(rates))
            / statistics.median(rates),
            "quartile_spread": quartile_spread(rates),
            "quartile_spread_trimmed": quartile_spread(rates, trim=True)}
    return out


def say(workload: str, tab: dict) -> None:
    print(f"== {workload}")
    for s in tab["seeds"]:
        print(f"seed {s['seed']}: correct={s['correct']} failed={s['failed']} "
              f"compiles_in_window={s['compiles_in_window']:g} "
              f"window_s={s['window_s']:.3f} rounds={s['rounds']} "
              f"rounds_at={s['rounds_at']} setup_s={s['setup_s']:.3f} "
              f"reach_chip_s={s['reach_chip_s']}")
    for w, t in tab["windows"].items():
        print(f"W={w}: median={t['median']:.1f} spread={t['spread']:.5f} "
              f"(all runs {t['spread_all']:.5f}; quartiles "
              f"{t['quartile_spread']:.5f}, trimmed "
              f"{t['quartile_spread_trimmed']:.5f}) "
              f"bound_for={bound_for(t['spread'])} rates="
              + " ".join(f"{r:.1f}" for r in t["rates"]))


def say_plain(plain: dict, long_run: dict) -> None:
    """A plain shorter run against the long run's figure at its length."""
    c, w = long_run["counters"], plain["seconds"]
    at = rates_at(c["window_call_s"], c["rows"], c["rounds_per_call"], [w])
    if not at:
        return
    own = plain["metrics"]["train_rows_rounds_per_s"]["value"]
    print(f"plain {w:g} s run, seed {plain['seed']}: {own:.1f} (rounds "
          f"{plain['counters']['window_rounds']}, correct="
          f"{plain['correct']}) against {at[w][0]:.1f} at W={w:g} of the "
          f"long run: {100 * (own / at[w][0] - 1):+.4f} %")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.spread")
    ap.add_argument("--workload")
    ap.add_argument("--seeds", type=int, nargs="+", default=[])
    ap.add_argument("--seconds", type=float, default=80.0)
    ap.add_argument("--windows", type=float, nargs="+",
                    default=[20.0, 40.0, 51.0])
    ap.add_argument("--plain", type=int, default=0)
    ap.add_argument("--plain-seconds", type=float, default=20.0)
    ap.add_argument("--read", nargs="+", default=[])
    ap.add_argument("--out", default="chiprun_out")
    args = ap.parse_args(argv)
    windows = [int(w) if float(w).is_integer() else w for w in args.windows]

    lines = []
    for path in args.read:
        with open(path) as f:
            lines += [json.loads(ln) for ln in f if ln.strip()]
    if args.workload:
        os.makedirs(args.out, exist_ok=True)
        todo = [(s, args.seconds) for s in args.seeds]
        todo += [(s, args.plain_seconds) for s in args.seeds[:args.plain]]
        with open(os.path.join(
                args.out, f"spread-{args.workload}.jsonl"), "a") as keep:
            for seed, seconds in todo:
                line = run_once(args.workload, seed, seconds)
                keep.write(json.dumps(line) + "\n")
                keep.flush()
                lines.append(line)

    bad = [ln for ln in lines if ln["rc"] != 0]
    for ln in bad:
        print(f"FAILED rc={ln['rc']} {ln['workload']} seed={ln['seed']} "
              f"seconds={ln['seconds']}")
    cells = sorted({ln["workload"] for ln in lines if ln["rc"] == 0})
    spreads = {}
    for workload in cells:
        mine = [ln for ln in lines
                if ln["workload"] == workload and ln["rc"] == 0]
        longest = max(ln["seconds"] for ln in mine)
        long_runs = {ln["seed"]: ln for ln in mine
                     if ln["seconds"] == longest}
        tab = table(list(long_runs.values()), windows)
        say(workload, tab)
        for w, t in tab["windows"].items():
            spreads.setdefault(w, []).append(t["spread"])
        for ln in mine:
            if ln["seconds"] < longest and ln["seed"] in long_runs:
                say_plain(ln, long_runs[ln["seed"]])
    worst = {w: max(s) for w, s in spreads.items() if len(s) == len(cells)}
    if worst:
        w, b = choose(worst)
        print("S(W), the larger of the cells' spreads: " + ", ".join(
            f"W={k}: {v:.5f} -> {bound_for(v)}" for k, v in worst.items()))
        print(f"rule over {len(cells)} cell(s): run_seconds={w:g} bound={b}")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
