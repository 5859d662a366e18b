"""Virtual-mesh, CPU only: training chaos bench — kill/resume parity,
fault absorption, overhead (the CPU platform is pinned below, so nothing
here is a chip number).

Drives the r13 fault-tolerant training stack through the failure menu
the issue gates on and writes ``BENCH_CHAOS_r13.json`` with the
``acceptance_r13`` rollup:

* **kill-at-round-k x resume parity sweep** — for every config in
  {strict, wave, in-memory, streamed multi-block, dryrun multi-chip
  (8 virtual CPU devices)} and EVERY kill round k, resuming the
  checkpoint and training the remaining rounds reproduces the
  uninterrupted forest bit for bit (``np.array_equal`` on every tree
  buffer and on train predictions);
* **SIGTERM drain** — a real signal mid-run finishes the in-flight
  round, checkpoints, and the follow-up invocation completes to the
  same forest;
* **transient block-read fault** — absorbed by the bounded retry with
  ZERO lost rounds (forest unchanged vs the clean run);
* **corrupt checkpoint** — the torn newest artifact is rejected at
  load while the prior generation stays loadable, and the resumed run
  still matches;
* **checkpoint overhead** — the ``CKPT_BUDGETS`` time model holds the
  <=5% bar at ``checkpoint_rounds=10`` and a measured wall-clock
  overhead on a real training loop confirms it.

Deterministic by construction: faults fire on exact hit counts
(``lightgbm_tpu.faults``), never on wall-clock; only the overhead
measurement reads real timers.

Usage: python tools/bench_chaos.py [out.json]
"""

import json
import os
import signal
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import numpy as np

sys.path.insert(0, ".")

import jax

jax.config.update("jax_platforms", "cpu")

import lightgbm_tpu as lgb
from lightgbm_tpu.analysis.budgets import check_ckpt_budgets, ckpt_overhead_time
from lightgbm_tpu.dataset import Dataset
from lightgbm_tpu.faults import FaultInjector, FaultSpec
from lightgbm_tpu.training import (CorruptCheckpointError, latest_checkpoint,
                                   list_checkpoints, load_checkpoint,
                                   load_latest, resume_booster,
                                   save_checkpoint, train_resumable)

ROUNDS = 5


def _problem(n=1200, f=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(0, 1, (n, f)).astype(np.float32)
    w = rng.normal(0, 1, f)
    y = (rng.random(n) < 1 / (1 + np.exp(-(X @ w)))).astype(np.float32)
    return X, y


def _base_params():
    return dict(objective="binary", num_leaves=15, learning_rate=0.2,
                max_bin=63, min_data_in_leaf=5, verbose=-1, seed=7)


def _configs():
    """name -> (params, fresh-Dataset factory); the sweep grid."""
    X, y = _problem()
    out = {}

    def mem(name, **extra):
        p = dict(_base_params(), **extra)
        out[name] = (p, lambda p=p: Dataset(X, label=y, params=dict(p)))

    mem("strict_inmem", bagging_fraction=0.8, bagging_freq=1,
        feature_fraction=0.8)
    mem("wave_inmem", wave_width=4)
    mem("dp_mesh_8dev", tree_learner="data")

    p = dict(_base_params(), stream_block_rows=256)
    blocks = [(X[lo:lo + 256], y[lo:lo + 256])
              for lo in range(0, len(X), 256)]
    out["streamed_multiblock"] = (
        p, lambda p=p: Dataset.from_blocks(blocks, params=dict(p)))
    p2 = dict(_base_params(), stream_block_rows=256, boosting="goss",
              top_rate=0.3, other_rate=0.2)
    out["streamed_goss"] = (
        p2, lambda p=p2: Dataset.from_blocks(blocks, params=dict(p2)))
    return out


def _trees_equal(a, b):
    if len(a.trees) != len(b.trees):
        return False
    for ta, tb in zip(a.trees, b.trees):
        for field in ("split_feature", "split_bin", "left", "right",
                      "leaf_value", "is_leaf"):
            if not np.array_equal(np.asarray(getattr(ta, field)),
                                  np.asarray(getattr(tb, field))):
                return False
    return True


def _same_run(ref, got):
    return (_trees_equal(ref, got)
            and np.array_equal(np.asarray(ref._pred_train),
                               np.asarray(got._pred_train)))


def _reference(p, make_ds, rounds=ROUNDS):
    b = lgb.Booster(dict(p), make_ds())
    for _ in range(rounds):
        b.update()
    return b


def sweep_kill_resume():
    """Kill at every round k of every config; resume must be bit-identical."""
    results = {}
    for name, (p, make_ds) in _configs().items():
        ref = _reference(p, make_ds)
        with tempfile.TemporaryDirectory() as d:
            res = train_resumable(dict(p), make_ds(), ROUNDS,
                                  checkpoint_dir=d, checkpoint_rounds=1,
                                  keep_last=ROUNDS + 1, resume=False)
            paths = list_checkpoints(d)
            kills = []
            for path in paths[:-1]:
                k = load_checkpoint(path)[1]["iter"]
                b = resume_booster(path, make_ds())
                for _ in range(ROUNDS - k):
                    b.update()
                kills.append({"kill_round": int(k),
                              "bit_identical": _same_run(ref, b)})
            results[name] = {
                "rounds": ROUNDS,
                "uninterrupted_matches": _same_run(ref, res.booster),
                "kills": kills,
                "all_bit_identical": (_same_run(ref, res.booster)
                                      and all(x["bit_identical"]
                                              for x in kills)
                                      and len(kills) == ROUNDS - 1),
            }
    return results


def scenario_sigterm():
    cfgs = _configs()
    p, make_ds = cfgs["strict_inmem"]
    ref = _reference(p, make_ds)
    with tempfile.TemporaryDirectory() as d:
        def kill_at(booster, i):
            if i == 2:
                os.kill(os.getpid(), signal.SIGTERM)

        r1 = train_resumable(dict(p), make_ds(), ROUNDS, checkpoint_dir=d,
                             checkpoint_rounds=10, resume=False,
                             round_callbacks=[kill_at])
        r2 = train_resumable(dict(p), make_ds(), ROUNDS, checkpoint_dir=d,
                             checkpoint_rounds=10, resume=True)
        return {
            "preempted": bool(r1.preempted),
            "rounds_at_drain": r1.rounds_done,
            "resumed_from": os.path.basename(r2.resumed_from or ""),
            "completed": bool(r2.completed),
            "bit_identical": _same_run(ref, r2.booster),
        }


def scenario_block_read_fault():
    cfgs = _configs()
    p, make_ds = cfgs["streamed_multiblock"]
    ref = _reference(p, make_ds)

    ds = make_ds()
    store = ds.block_store
    store._sleep = lambda s: None
    inj = FaultInjector([FaultSpec("block_read", after=2, times=2,
                                   message="transient host read")])
    store.fault_injector = inj
    b = lgb.Booster(dict(p), ds)
    for _ in range(ROUNDS):
        b.update()
    return {
        "faults_fired": inj.fired["block_read"],
        "retries_absorbed": store.read_retries,
        "quarantined_blocks": sorted(store.quarantined),
        "rounds_completed": int(b._iter),
        "lost_rounds": ROUNDS - int(b._iter),
        "bit_identical": _same_run(ref, b),
        "absorbed": (inj.fired["block_read"] == 2
                     and int(b._iter) == ROUNDS and _same_run(ref, b)),
    }


def scenario_corrupt_checkpoint():
    cfgs = _configs()
    p, make_ds = cfgs["strict_inmem"]
    ref = _reference(p, make_ds)
    with tempfile.TemporaryDirectory() as d:
        b = lgb.Booster(dict(p), make_ds())
        b.update()
        save_checkpoint(b, d)
        b.update()
        newest = save_checkpoint(b, d)
        blob = bytearray(open(newest, "rb").read())
        blob[len(blob) // 2] ^= 0xFF           # bit-rot mid-payload
        open(newest, "wb").write(bytes(blob))

        try:
            load_checkpoint(newest)
            rejected = False
        except CorruptCheckpointError:
            rejected = True
        path, found = load_latest(d)
        prior_ok = path is not None and found["meta"]["iter"] == 1

        import warnings as _w
        with _w.catch_warnings():
            _w.simplefilter("ignore")
            res = train_resumable(dict(p), make_ds(), ROUNDS,
                                  checkpoint_dir=d, checkpoint_rounds=10,
                                  resume=True)
        return {
            "corrupt_rejected": rejected,
            "prior_generation_loadable": bool(prior_ok),
            "fallback_path": os.path.basename(path or ""),
            "resumed_bit_identical": _same_run(ref, res.booster),
        }


def scenario_ckpt_overhead():
    """Model check (the lint-gated CKPT_BUDGETS) + a measured wall-clock
    CHECKPOINT overhead at checkpoint_rounds=10: the same resumable loop
    with and without mid-run checkpoints, so the delta isolates exactly
    what the budget models (write + digest cost amortized over the
    cadence) rather than loop/screen fixed costs, which are reported
    separately as ``loop_overhead_frac``."""
    budgets = check_ckpt_budgets()
    model_ok = all(r["ok"] for r in budgets)
    ref_model = ckpt_overhead_time()

    X, y = _problem(n=20_000, f=16, seed=3)
    p = dict(_base_params(), num_leaves=31, max_bin=63)
    rounds = 30

    def run(checkpoint_rounds):
        ds = Dataset(X, label=y, params=dict(p))
        with tempfile.TemporaryDirectory() as d:
            t0 = time.perf_counter()
            train_resumable(dict(p), ds, rounds, checkpoint_dir=d,
                            checkpoint_rounds=checkpoint_rounds,
                            resume=False)
            return time.perf_counter() - t0

    def run_plain():
        ds = Dataset(X, label=y, params=dict(p))
        t0 = time.perf_counter()
        b = lgb.Booster(dict(p), ds)
        for _ in range(rounds):
            b.update()
        return time.perf_counter() - t0

    run(rounds + 1)                            # warm the jit caches
    t_none = min(run(rounds + 1) for _ in range(2))   # final ckpt only
    t_ckpt = min(run(10) for _ in range(2))           # every 10 rounds
    t_plain = min(run_plain() for _ in range(2))      # bare update loop
    overhead = max(t_ckpt - t_none, 0.0) / t_none
    loop_overhead = max(t_none - t_plain, 0.0) / t_plain
    return {
        "budget_entries": budgets,
        "model_overhead_frac_ref": ref_model["overhead_frac"],
        "model_ok": model_ok,
        "measured": {
            "rounds": rounds, "n_rows": len(X),
            "checkpoint_rounds": 10,
            "no_mid_ckpt_s": round(t_none, 4),
            "with_ckpt_s": round(t_ckpt, 4),
            "plain_loop_s": round(t_plain, 4),
            "overhead_frac": round(overhead, 4),
            "loop_overhead_frac": round(loop_overhead, 4),
        },
        "measured_le_5pct": overhead <= 0.05,
    }


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    out_path = argv[0] if argv else "BENCH_CHAOS_r13.json"

    print(f"devices: {len(jax.devices())} ({jax.devices()[0].platform})")
    t0 = time.time()
    sweep = sweep_kill_resume()
    print(f"kill/resume sweep done in {time.time() - t0:.1f}s")
    sig = scenario_sigterm()
    blk = scenario_block_read_fault()
    cor = scenario_corrupt_checkpoint()
    ovh = scenario_ckpt_overhead()

    acceptance = {
        "resume_bit_identical_all_configs": all(
            v["all_bit_identical"] for v in sweep.values()),
        "sigterm_drain_resume_bit_identical": (
            sig["preempted"] and sig["completed"] and sig["bit_identical"]),
        "block_read_fault_absorbed_zero_lost_rounds": blk["absorbed"],
        "corrupt_checkpoint_rejected_prior_loadable": (
            cor["corrupt_rejected"] and cor["prior_generation_loadable"]
            and cor["resumed_bit_identical"]),
        "ckpt_overhead_budgets_ok": ovh["model_ok"],
        "ckpt_overhead_measured_le_5pct": ovh["measured_le_5pct"],
    }
    acceptance["all_green"] = all(acceptance.values())

    doc = {
        "bench": "training_chaos",
        "round": 13,
        "backend": jax.devices()[0].platform,
        "n_devices": len(jax.devices()),
        "kill_resume_sweep": sweep,
        "sigterm_drain": sig,
        "block_read_fault": blk,
        "corrupt_checkpoint": cor,
        "ckpt_overhead": ovh,
        "acceptance_r13": acceptance,
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    status = "ALL GREEN" if acceptance["all_green"] else "RED"
    print(f"wrote {out_path}; acceptance_r13 {status}")
    for k, v in acceptance.items():
        print(f"  {'ok ' if v else 'FAIL'} {k}")
    return 0 if acceptance["all_green"] else 1


if __name__ == "__main__":
    sys.exit(main())
