"""Are the bin edges of this checkout those of another one, byte for byte?

    python3 tools/edges_parity.py --other <checkout> [--seed N] \\
        [--configs higgs-10m5 epsilon-400k mslr-web30k] [--out chiprun_out]

Makes each benchmark configuration's table as its cell does (the
configuration's ``table_seed``, the columns in the order of ``--seed``) and
runs ``BinMapper.fit`` on it twice, each in a process of its own: once from
this checkout, once from ``--other`` (a ``git archive`` of the commit to
compare with).  Compared per column: ``upper_bounds``, ``nan_bin``,
``n_bins``, ``is_categorical``.  Printed per configuration: the columns
compared, those that differ, and each side's seconds in ``fit`` (a host
number: it is the chip machine's only when run there).  Exit code 1 where a
column differs.  Host work only; the children run with ``JAX_PLATFORMS=cpu``
so that neither holds a chip.
"""
import argparse
import hashlib
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def fit_digests(root: str, configs, seed: int) -> dict:
    """In a child: per configuration the digest of each column's edges by
    ``root``'s ``BinMapper.fit``, and the seconds it took."""
    sys.path.insert(0, root)
    import numpy as np

    from benchmark import datagen, datagen_rank
    from benchmark.kinds.train_window_rank import docs_range
    from lightgbm_tpu.dataset import BinMapper

    out = {}
    for name in configs:
        with open(os.path.join(HERE, "benchmark", "configs",
                               name + ".json")) as f:
            config = json.load(f)
        rows, features = int(config["rows"]), int(config["features"])
        if "queries" in config:
            X = datagen_rank.mslr_like(
                rows, features, int(config["queries"]),
                int(config["table_seed"]), *docs_range(config))[0]
        else:
            X = datagen.higgs_like(rows, features,
                                   int(config["table_seed"]))[0]
        datagen.reorder_columns(X, seed)
        t0 = time.perf_counter()
        mapper = BinMapper.fit(X, max_bin=int(config["params"]["max_bin"]))
        fit_s = time.perf_counter() - t0
        digests = [hashlib.sha256(
            np.asarray(ub, np.float64).tobytes()
            + np.asarray([mapper.nan_bin[f], mapper.n_bins[f],
                          mapper.is_categorical[f]], np.int64).tobytes()
        ).hexdigest() for f, ub in enumerate(mapper.upper_bounds)]
        out[name] = {"shape": list(X.shape), "dtype": str(X.dtype),
                     "fit_s": fit_s, "digests": digests,
                     "fit_counts": getattr(mapper, "fit_counts", None)}
        del X
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--other")
    ap.add_argument("--seed", type=int, default=2138000001)
    ap.add_argument("--configs", nargs="+", default=[
        "higgs-10m5", "epsilon-400k", "mslr-web30k"])
    ap.add_argument("--out", default="chiprun_out")
    ap.add_argument("--child-root", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child_root:
        json.dump(fit_digests(args.child_root, args.configs, args.seed),
                  sys.stdout)
        return 0
    if not args.other:
        ap.error("--other is required")

    sides = {}
    for side, root in (("other", os.path.abspath(args.other)),
                       ("this", HERE)):
        done = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child-root", root,
             "--seed", str(args.seed), "--configs", *args.configs],
            env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=root,
            stdout=subprocess.PIPE, check=True)
        sides[side] = json.loads(done.stdout)
    report, differ = {}, 0
    for name in args.configs:
        this, other = sides["this"][name], sides["other"][name]
        unequal = [f for f, (a, b) in enumerate(
            zip(this["digests"], other["digests"])) if a != b]
        assert len(this["digests"]) == len(other["digests"])
        differ += len(unequal)
        report[name] = {
            "shape": this["shape"], "dtype": this["dtype"],
            "columns_compared": len(this["digests"]),
            "columns_that_differ": unequal,
            "fit_s_other": other["fit_s"], "fit_s_this": this["fit_s"],
            "fit_counts": this["fit_counts"]}
        print(name, json.dumps(report[name]), flush=True)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "edges_parity.json"), "w") as f:
        json.dump({"seed": args.seed, "cells": report}, f, indent=1)
    print("edges byte-identical:" if not differ else "EDGES DIFFER:",
          sum(r["columns_compared"] for r in report.values()) - differ,
          "of", sum(r["columns_compared"] for r in report.values()),
          "columns")
    return 1 if differ else 0


if __name__ == "__main__":
    sys.exit(main())
