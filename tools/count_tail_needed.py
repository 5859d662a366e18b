"""Count what the exact tail's certification passes have to expand (PR 35).

    JAX_PLATFORMS=cpu python tools/count_tail_needed.py --seed 11 \\
        [--cell higgs-10m5] [--rows 400000] [--features 28] [--rounds 17] \\
        [--min-hessian 100]

Grows ``--rounds`` trees with a benchmark cell's ``params`` on rows from
``benchmark/datagen.py`` with the program's own grower, on the CPU (a tool
to size a schedule, not a cell's code: nothing here is a device number).
Every time the wave loop asks ``tree._replay_certified`` whether to go on,
a wrapper this tool puts around it also reads, from the same node table:
the expanded nodes, the leaves with a candidate split, ``needed``
(``tree._replay_needed``: the leaves the replay still needs expanded) and
the certificate.  Nothing in the package prints.  Per round: the passes
the tree ran, how many of them ran after it held ``num_leaves - 1`` splits
(the certification passes) and ``needed`` before each of those.  A
certification pass with ``needed`` at most the schedule's narrow width
(16) is one the partition-fused path runs narrow; ``q`` is their share.
On the CPU the grower runs full-width passes only; the needed leaves at
each pass are the same under either width (they are expanded by both).
"""
import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from benchmark.datagen import higgs_like
from lightgbm_tpu.models import tree
from lightgbm_tpu.models.spec import resolve_wave


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="higgs-10m5")
    ap.add_argument("--rows", type=int, default=400_000)
    ap.add_argument("--features", type=int, default=28)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--rounds", type=int, default=17)
    ap.add_argument("--min-hessian", type=float, default=None,
                    help="min_sum_hessian_in_leaf, to scale the cell's with "
                         "the rows (default: the cell's)")
    args = ap.parse_args()

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           args.cell + ".json")) as fh:
        params = dict(json.load(fh)["params"])
    if args.min_hessian is not None:
        params["min_sum_hessian_in_leaf"] = args.min_hessian

    seen = []           # (expanded, candidates, needed, certified) per ask
    certified = tree._replay_certified

    def asked(P, num_leaves):
        fires = certified(P, num_leaves)
        jax.debug.callback(
            lambda *row: seen.append(tuple(int(x) for x in row)),
            jnp.sum(P[:, tree._PK.LEFT] >= 0),
            jnp.sum(tree._has_candidate(P)),
            tree._replay_needed(P, num_leaves), fires)
        return fires

    tree._replay_certified = asked

    X, y = higgs_like(args.rows, args.features, args.seed)
    booster = lgb.Booster(params, lgb.Dataset(X, label=y))
    wave = resolve_wave(booster.params, args.rows)
    narrow = wave.narrow_width
    splits = params["num_leaves"] - 1
    print(json.dumps({"cell": args.cell, "rows": args.rows,
                      "features": args.features, "seed": args.seed,
                      "params": params, "wave": [wave.width, wave.tail,
                                                 wave.cap_leaves, narrow]}))
    totals = {"certification": 0, "narrow": 0}
    for rnd in range(1, args.rounds + 1):
        del seen[:]
        booster.update()
        jax.effects_barrier()
        # one ask before every pass and one after the last; a tree's
        # expanded nodes only grow, which orders the asks
        asks = sorted(seen)
        passes = asks[:-1]
        tail = [a for a in passes if a[0] >= splits]
        totals["certification"] += len(tail)
        totals["narrow"] += sum(0 < a[2] <= narrow for a in tail)
        print(json.dumps({
            "round": rnd, "passes": len(passes),
            "certification_passes": len(tail),
            "needed": [a[2] for a in tail],
            "candidates": [a[1] for a in tail],
            "expanded": [a[0] for a in tail],
            "ended_certified": bool(asks[-1][3]),
            "expanded_at_end": asks[-1][0]}), flush=True)
    q = totals["narrow"] / max(totals["certification"], 1)
    print(json.dumps(dict(totals, rounds=args.rounds, q=round(q, 4))))


if __name__ == "__main__":
    main()
