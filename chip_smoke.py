#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system starts on the chip.

Drives the main path once, in ONE process, through the entry points a
user calls: bin -> train -> predict -> pack -> serve -> cv, at the full
width of the Higgs cell (1M rows x 28 features, 127 leaves, 255 bins;
depth cut to 10 rounds), and checks every phase by the repo's own means
(numpy histograms, the host tree walk, the serving oracle).  Data comes
from ``--seed``; nothing is read but tracked files, nothing needs a
network, no process is started.

    python chip_smoke.py             # one chip, every phase
    python chip_smoke.py --chips 4   # ONLY the cross-chip path and what
                                     # it is compared with

It fails at once, printing no result, unless JAX's default backend is a
TPU.  Any failed check exits non-zero.  The LAST line of stdout is the
device line and nothing else:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Everything else (timings, compile seconds, memory, errors vs the
references) goes on earlier ``[phase] key=value`` lines.  These are
smoke observations of one run, not benchmark results.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile
import time

import numpy as np

# stated floors and tolerances (CPU rehearsal at 200k rows: AUC 0.827 at
# 10 rounds, 0.801 / 0.802 default / parity at 3; device-vs-host-walk
# raw-score difference 2.4e-7)
AUC_FLOOR_DEFAULT_10 = 0.80
AUC_FLOOR_PARITY_3 = 0.78
AUC_PARITY_VS_DEFAULT_3 = 0.02
HOST_WALK_TOL = 1e-5
TREE_VALUE_RTOL = 1e-5
SERVE_TOL = 1e-5
HIST_REL_TOL = {"f32": 1e-4, "bf16": 5e-3, "int8": 5e-2}

HIGGS_ROWS = 1_000_000
HELD_OUT_ROWS = 100_000
N_REQUESTS = 300


def say(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(
        f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
        for k, v in kv.items()), flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED — {what}")


class CompileMeter:
    """What JAX compiled inside a ``with`` block, from JAX's own monitoring
    events: ``seconds`` spent in backend compiles (XLA + Mosaic, or the
    read of a persistent-cache entry; tracing and lowering are not in
    it), ``programs`` compiled or fetched, persistent-cache ``hits`` and
    ``writes`` (a write is a miss that was worth keeping)."""

    def __init__(self):
        import jax.monitoring as mon

        self._on = False
        self._reset()
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _reset(self):
        self.seconds, self.programs = 0.0, 0
        self.cache_hits = self.cache_writes = 0

    def _duration(self, event, secs, **_):
        if self._on and event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _event(self, event, **_):
        if self._on:
            self.cache_hits += event == "/jax/compilation_cache/cache_hits"
            self.cache_writes += (
                event == "/jax/compilation_cache/cache_misses")

    @contextlib.contextmanager
    def measure(self):
        self._reset()
        self._on = True
        try:
            yield self
        finally:
            self._on = False


def dispatch_round_trip_ms() -> float:
    """Median host -> device -> host round trip of a trivial jitted op."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1.0)
    x = jnp.zeros(8)
    np.asarray(f(x))
    times = []
    for _ in range(50):
        t0 = time.perf_counter()
        np.asarray(f(x))
        times.append(time.perf_counter() - t0)
    return float(np.median(times) * 1e3)


# ---------------------------------------------------------------------------
# phase 1 — kernel numerics on the chip vs numpy
# ---------------------------------------------------------------------------
def phase_hist_kernels(rng) -> None:
    import jax.numpy as jnp

    from lightgbm_tpu.ops.histogram_pallas import hist_fused_pallas

    n, f, b, w = 40_000, 28, 256, 8
    bins = rng.integers(0, b, (n, f)).astype(np.uint8)
    stats = rng.normal(size=(n, 3)).astype(np.float32)
    seg = rng.integers(0, w, n).astype(np.int32)
    ref = np.zeros((w, f, b, 3))
    np.add.at(ref, (seg[:, None], np.arange(f)[None, :], bins),
              stats[:, None, :])
    for mode, tol in HIST_REL_TOL.items():
        got = np.asarray(hist_fused_pallas(
            jnp.asarray(bins), jnp.asarray(stats), jnp.asarray(seg), w, b,
            hist_dtype=mode, interpret=False))
        err = float(np.max(np.abs(got - ref)) / np.abs(ref).max())
        say("kernel", name=f"hist_fused_pallas[{mode}]", shape=f"{n}x{f}x{b}",
            rel_err=err, tol=tol)
        check(np.all(np.isfinite(got)) and err < tol,
              f"hist_fused_pallas {mode} rel err {err} >= {tol}")


def _random_forest(rng, trees: int, slots: int, features: int, bins: int):
    """Random ragged trees as [T, M] node arrays (grower conventions:
    -1 children at leaves, garbage left in unreachable slots) and the
    traversal depth that reaches every leaf."""
    depth = np.zeros((trees, slots), np.int64)
    feat = np.zeros((trees, slots), np.int32)
    thr = np.zeros((trees, slots), np.int32)
    left = -np.ones((trees, slots), np.int32)
    right = -np.ones((trees, slots), np.int32)
    leaf = np.full((trees, slots), 777.0, np.float32)
    is_leaf = np.zeros((trees, slots), bool)
    for t in range(trees):
        n_nodes, frontier = 1, [0]
        while frontier and n_nodes + 2 <= slots:
            i = frontier.pop(int(rng.integers(len(frontier))))
            if i and rng.random() < 0.15:
                is_leaf[t, i], leaf[t, i] = True, rng.normal()
                continue
            feat[t, i] = rng.integers(features)
            thr[t, i] = rng.integers(bins)
            left[t, i], right[t, i] = n_nodes, n_nodes + 1
            depth[t, n_nodes:n_nodes + 2] = depth[t, i] + 1
            frontier += [n_nodes, n_nodes + 1]
            n_nodes += 2
        for i in frontier:
            is_leaf[t, i], leaf[t, i] = True, rng.normal()
    return (feat, thr, left, right, leaf, is_leaf), int(depth.max()) + 1


def _walk(feat, thr, left, right, leaf, is_leaf, codes):
    """Plain numpy tree walk: sum of leaf values per row, in f64."""
    out = np.zeros(codes.shape[0])
    rows = np.arange(codes.shape[0])
    for t in range(feat.shape[0]):
        node = np.zeros(codes.shape[0], np.int64)
        while not is_leaf[t, node].all():
            go_left = codes[rows, feat[t, node]] <= thr[t, node]
            nxt = np.where(go_left, left[t, node], right[t, node])
            node = np.where(is_leaf[t, node], node, nxt)
        out += leaf[t, node]
    return out


def phase_predict_kernel(rng) -> None:
    import jax.numpy as jnp

    from lightgbm_tpu.ops.predict import (pack_forest_soa,
                                          predict_forest_pallas)

    trees, slots, features, bins, rows = 40, 509, 28, 255, 1000
    (feat, thr, left, right, leaf, is_leaf), depth = _random_forest(
        rng, trees, slots, features, bins)
    codes = rng.integers(0, bins, (rows, features)).astype(np.uint8)
    for precision in ("f32", "bf16", "int8"):
        scale = None
        if precision == "f32":
            stored, ref_leaf = leaf, leaf
        elif precision == "bf16":
            stored = np.asarray(jnp.asarray(leaf, jnp.bfloat16), np.float32)
            ref_leaf = stored
        else:
            scale = (np.abs(np.where(is_leaf, leaf, 0)).max(axis=1)
                     / 127.0).astype(np.float32)
            stored = np.clip(np.round(leaf / scale[:, None]),
                             -127, 127).astype(np.int8)
            ref_leaf = stored.astype(np.float32) * scale[:, None]
        narrow = precision != "f32"
        soa = pack_forest_soa(
            feat.astype(np.int16) if narrow else feat,
            thr.astype(np.uint8) if narrow else thr,
            left.astype(np.int16) if narrow else left,
            right.astype(np.int16) if narrow else right,
            stored, is_leaf, precision=precision, leaf_scale=scale)
        got = np.asarray(predict_forest_pallas(
            soa, jnp.asarray(codes), 1.0, 0.0, jnp.int32(trees), depth,
            interpret=False))
        want = _walk(feat, thr, left, right, ref_leaf, is_leaf, codes)
        err = float(np.max(np.abs(got - want)))
        say("kernel", name=f"predict_forest_pallas[{precision}]",
            forest=f"{trees}x{slots}", depth=depth, max_abs_err=err,
            tol=1e-4)
        check(np.all(np.isfinite(got)) and err < 1e-4,
              f"predict_forest_pallas {precision} differs from the numpy "
              f"walk by {err}")


# ---------------------------------------------------------------------------
# phase 2 — bin + train at Higgs-1M (default config, then preset=parity)
# ---------------------------------------------------------------------------
def _train(lgb, params, ds, rounds, meter):
    """``lgb.train`` to the end of its last round; the booster, and the
    wall seconds with what JAX compiled meanwhile."""
    import jax

    with meter.measure():
        t0 = time.perf_counter()
        booster = lgb.train(params, ds, num_boost_round=rounds)
        jax.block_until_ready(booster._pred_train)
        wall = time.perf_counter() - t0
    return booster, dict(wall=wall, compile_s=meter.seconds,
                         programs=meter.programs, hits=meter.cache_hits,
                         writes=meter.cache_writes)


def _kernels_in_round(booster, n_rounds: int) -> int:
    """tpu_custom_call count in the lowered ``n_rounds`` program — the one
    ``update_many`` just ran (same jitted function, same operands)."""
    fn, args = booster._fused_segment(n_rounds)
    return fn.lower(*args).as_text().count("tpu_custom_call")


def split_iter_vs_xla(ds, hyper, num_leaves: int) -> None:
    """``split_iter_pallas`` on the chip against the XLA split path it
    replaced.  The chip's kernel is not the code the CPU tests run (its
    prefix sums are roll-and-add on padded lanes; interpret mode keeps
    ``jnp.cumsum``), so tree parity is checked HERE: one strict tree
    grown twice from the first-round gradients of all the rows,
    ``grow_tree(fuse_split=True / False)`` with the parity preset's
    histograms.  The trees must be the same tree — every split, child
    link and row assignment equal, no tolerance — and the f32 values
    stored in them within ``TREE_VALUE_RTOL`` (the summation orders
    differ)."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.models.tree import grow_tree

    mask = ds.row_mask
    p = jnp.sum(ds.y * mask) / jnp.sum(mask)
    stats = jnp.stack([(p - ds.y) * mask, p * (1 - p) * mask, mask], axis=-1)
    fmask = jnp.ones(ds.X_binned.shape[1], jnp.float32)

    def grow(fuse_split):
        fn = jax.jit(lambda bins, stats: grow_tree(
            bins, stats, fmask, hyper.ctx(), num_leaves, ds.num_bins,
            hyper.max_depth, hist_impl="jnp", hist_dtype="f32",
            fuse_split=fuse_split))
        kernels = fn.lower(ds.X_binned, stats).as_text().count(
            "tpu_custom_call")
        tree, row_leaf = fn(ds.X_binned, stats)
        return tree._replace(**{
            f: np.asarray(getattr(tree, f)) for f in (
                "split_feature", "split_bin", "left", "right", "is_leaf",
                "leaf_value", "split_gain")}), np.asarray(row_leaf), kernels

    fused, fused_rows, fused_kernels = grow(True)
    plain, plain_rows, plain_kernels = grow(False)
    internal = plain.left >= 0
    differing = internal & ((fused.split_feature != plain.split_feature)
                            | (fused.split_bin != plain.split_bin))
    same = bool(np.array_equal(fused.is_leaf, plain.is_leaf)
                and np.array_equal(fused.left, plain.left)
                and np.array_equal(fused.right, plain.right)
                and not differing.any()
                and np.array_equal(fused_rows, plain_rows))

    def rel(field, where):
        a, b = getattr(fused, field)[where], getattr(plain, field)[where]
        return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))

    leaf_err = rel("leaf_value", plain.is_leaf)
    gain_err = rel("split_gain", internal)
    say("split-iter", rows=HIGGS_ROWS, leaves=int(plain.is_leaf.sum()),
        fused_kernels=fused_kernels, xla_path_kernels=plain_kernels,
        tree_identical=same, differing_splits=int(differing.sum()),
        leaf_value_rel_err=leaf_err, split_gain_rel_err=gain_err,
        tol=TREE_VALUE_RTOL)
    check(fused_kernels > 0 and plain_kernels == 0,
          "fuse_split did not switch split_iter_pallas on and off")
    if differing.any():
        i = int(np.argmax(differing))
        say("split-iter", first_differing_node=i,
            fused=f"f{fused.split_feature[i]}<=b{fused.split_bin[i]}"
                  f"(gain {fused.split_gain[i]:.9g})",
            xla=f"f{plain.split_feature[i]}<=b{plain.split_bin[i]}"
                f"(gain {plain.split_gain[i]:.9g})")
    check(same, "split_iter_pallas grew another tree than the XLA split "
          "path from the same gradients")
    check(int(plain.is_leaf.sum()) == num_leaves,
          "the comparison tree did not use its leaf budget")
    check(leaf_err < TREE_VALUE_RTOL and gain_err < TREE_VALUE_RTOL,
          f"stored leaf values / gains differ: {leaf_err} {gain_err}")


def phase_train(lgb, seed: int, meter, device):
    from sklearn.metrics import roc_auc_score

    from lightgbm_tpu.utils.datasets import make_higgs_like

    t0 = time.perf_counter()
    X, y = make_higgs_like(HIGGS_ROWS, seed=seed)
    Xv, yv = make_higgs_like(HELD_OUT_ROWS, seed=seed + 9)
    gen_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    ds = lgb.Dataset(X, label=y)
    ds.construct()
    import jax

    jax.block_until_ready(ds.X_binned)
    binning_s = time.perf_counter() - t0
    # a float32 table of this size is coded on the chip: the same bytes as
    # the host's loop gives
    from lightgbm_tpu.utils import profiling

    host_codes = ds.bin_mapper._transform_unbundled(X)
    same = np.asarray(ds.X_binned)[:HIGGS_ROWS].tobytes() == host_codes.tobytes()
    path = profiling.snapshot()["facts"]["dataset.codes_path"]
    say("bin", rows=HIGGS_ROWS, features=X.shape[1], num_bins=ds.num_bins,
        datagen_s=gen_s, binning_s=binning_s, codes_path=path,
        bit_identical_to_host=same)
    check(path == "device" and same,
          "bin codes assigned on the chip are the host's bytes")

    params = {"objective": "binary", "num_leaves": 127, "verbosity": -1}
    rounds = 10
    # cold: after whatever this process traced before.  warm: jax has
    # forgotten every executable, so the same shape, traced anew from
    # another line, has to come back from the persistent cache.  steady:
    # nothing compiles.
    _, cold = _train(lgb, params, ds, rounds, meter)
    jax.clear_caches()
    _, warm = _train(lgb, params, ds, rounds, meter)
    booster, steady = _train(lgb, params, ds, rounds, meter)
    kernels = _kernels_in_round(booster, 4)
    peak = device.memory_stats()["peak_bytes_in_use"]
    say("train", config="default(exact-tail waves, bf16 Pallas, fused "
        "partition)", rounds=rounds, compile_cold_s=cold["compile_s"],
        compile_warm_s=warm["compile_s"], cold_programs=cold["programs"],
        cold_cache_hits=cold["hits"], cold_cache_writes=cold["writes"],
        warm_cache_hits=warm["hits"], warm_cache_writes=warm["writes"],
        first_train_wall_s=cold["wall"], second_train_wall_s=warm["wall"],
        s_per_round=steady["wall"] / rounds,
        rows_rounds_per_s=HIGGS_ROWS * rounds / steady["wall"],
        tpu_custom_calls_in_round=kernels, peak_hbm_bytes=peak)
    check(steady["programs"] == 0, "a steady-state train compiled something")
    check(kernels > 0, "the default round holds no Pallas kernel")
    check(warm["hits"] > 0 and warm["writes"] == 0,
          "the second train of the same shape did not come from the "
          "persistent cache")
    if cold["writes"]:
        check(warm["compile_s"] < cold["compile_s"] / 2,
              f"warm compile {warm['compile_s']:.1f}s is not markedly "
              f"shorter than the cold one {cold['compile_s']:.1f}s")
    else:
        say("train", note="the first train was itself served by a "
            "persistent cache that came with the machine")

    p_rounds = 3
    parity, p_first = _train(lgb, dict(params, preset="parity"), ds,
                             p_rounds, meter)
    _, p_steady = _train(lgb, dict(params, preset="parity"), ds, p_rounds,
                         meter)
    p_kernels = _kernels_in_round(parity, p_rounds)
    auc_p = float(roc_auc_score(yv, parity.predict(Xv)))
    auc_d3 = float(roc_auc_score(yv, booster.predict(Xv, num_iteration=3)))
    say("train", config="preset=parity(strict, f32 XLA histograms, "
        "split_iter_pallas)", rounds=p_rounds,
        compile_s=p_first["compile_s"], first_train_wall_s=p_first["wall"],
        s_per_round=p_steady["wall"] / p_rounds,
        tpu_custom_calls_in_round=p_kernels, auc=auc_p,
        auc_default_3_rounds=auc_d3,
        peak_hbm_bytes=device.memory_stats()["peak_bytes_in_use"])
    check(p_kernels > 0, "the strict round holds no Pallas kernel")
    check(auc_p > AUC_FLOOR_PARITY_3,
          f"parity AUC {auc_p} under its floor {AUC_FLOOR_PARITY_3}")
    check(abs(auc_p - auc_d3) < AUC_PARITY_VS_DEFAULT_3,
          f"parity AUC {auc_p} vs default {auc_d3} at 3 rounds")
    split_iter_vs_xla(ds, parity._hyper, params["num_leaves"])
    return booster, Xv, yv


# ---------------------------------------------------------------------------
# phase 3 — Booster.predict vs the host tree walk
# ---------------------------------------------------------------------------
def phase_predict(booster, Xv, yv):
    from sklearn.metrics import roc_auc_score

    from lightgbm_tpu.serving import pack_booster

    t0 = time.perf_counter()
    raw = np.asarray(booster.predict(Xv, raw_score=True))
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    prob = np.asarray(booster.predict(Xv))
    second_s = time.perf_counter() - t0
    packed = pack_booster(booster)
    codes = packed.bin_mapper.transform(np.asarray(Xv, np.float64))
    host = packed.predict_numpy(codes, raw_score=True)
    err = float(np.max(np.abs(raw - host)))
    auc = float(roc_auc_score(yv, prob))
    say("predict", rows=len(yv), first_call_s=first_s, second_call_s=second_s,
        auc=auc, auc_floor=AUC_FLOOR_DEFAULT_10,
        max_abs_vs_host_walk=err, tol=HOST_WALK_TOL)
    check(np.all(np.isfinite(prob)) and prob.shape == (len(yv),),
          "Booster.predict returned non-finite values or a wrong shape")
    check(auc > AUC_FLOOR_DEFAULT_10, f"AUC {auc} under its floor")
    check(err <= HOST_WALK_TOL,
          f"chip predictions differ from the host walk by {err}")
    return packed, codes


# ---------------------------------------------------------------------------
# phase 4 — save -> ModelBank deploy (the serve CLI's path) -> MicroBatcher
# ---------------------------------------------------------------------------
def phase_serve(booster, packed, codes, Xv, meter, workdir: str) -> None:
    from lightgbm_tpu.serving import ModelBank

    path = os.path.join(workdir, "higgs.npz")
    booster.save_model(path)
    exact = packed.predict_numpy(codes[:N_REQUESTS], raw_score=False)
    for precision in ("f32", "int8"):
        bank = ModelBank(warm_on_deploy=True, forest_precision=precision)
        with meter.measure():
            t0 = time.perf_counter()
            rep = bank.deploy("higgs", path)
            deploy_s = time.perf_counter() - t0
        warm_compile_s = meter.seconds
        rt = bank.runtime("higgs")
        check(rep["ok"] and rep["canary"]["max_abs_err"] <= bank.canary_tol,
              f"{precision} deploy or canary failed: {rep}")
        compiles_warm = rt.num_compiles
        batcher = bank.batcher("higgs", max_batch=128, max_delay_ms=2.0,
                               fallback_unbatched=False)
        with meter.measure():
            t0 = time.perf_counter()
            handles = []
            for row in Xv[:N_REQUESTS]:
                handles.append(batcher.submit(row))
                batcher.pump()
            batcher.flush()
            got = np.array([h.result() for h in handles])
            traffic_s = time.perf_counter() - t0
        snap = bank.snapshot()["models"]["higgs"]["stats"]
        err = float(np.max(np.abs(got - exact)))
        bound = rt.quant_error_bound + SERVE_TOL
        say("serve", forest_precision=precision, deploy_s=deploy_s,
            warmed_programs=rep["warmed"], warm_compile_s=warm_compile_s,
            canary_max_abs_err=rep["canary"]["max_abs_err"],
            requests=N_REQUESTS, dispatches=snap["batched_dispatches"],
            traffic_s=traffic_s, max_abs_vs_oracle=err,
            quant_error_bound=rt.quant_error_bound,
            fused_path=snap["compile_cache"]["fused_path"],
            fallbacks=snap["fallbacks"],
            program_builds_after_warm=rt.num_compiles - compiles_warm,
            xla_compiles_in_traffic=meter.programs)
        check(np.all(np.isfinite(got)) and err <= bound,
              f"{precision} answers differ from the oracle by {err} "
              f"> {bound}")
        check(snap["compile_cache"]["fused_path"] is True
              and snap["fused_path"]["legacy_dispatches"] == 0,
              "serving did not take the fused kernel path")
        check(snap["fallbacks"] == 0, "requests were answered from the host")
        check(rt.num_compiles == compiles_warm and meter.programs == 0,
              "traffic compiled a program after warm-up")


# ---------------------------------------------------------------------------
# phase 5 — fused CV: lgb.cv (1 config x 5 folds) and a 4-config sweep
# ---------------------------------------------------------------------------
def phase_cv(lgb, meter) -> None:
    from lightgbm_tpu.utils.datasets import (make_synthetic_diamonds,
                                             train_test_split_bernoulli)
    from lightgbm_tpu.utils.sweep import expand_grid, run_grid_search

    X, y, _ = make_synthetic_diamonds()
    tr, _ = train_test_split_bernoulli(len(y), 0.85, seed=3928272)
    ds = lgb.Dataset(X[tr], label=y[tr])
    rounds = 5
    base = {"objective": "regression", "verbosity": -1, "num_leaves": 31,
            "learning_rate": 0.1}
    with meter.measure():
        t0 = time.perf_counter()
        fit = lgb.cv(base, ds, num_boost_round=rounds, nfold=5,
                     metrics="rmse", stratified=False, seed=3928272)
        cv_s = time.perf_counter() - t0
    curve = fit["valid rmse-mean"]
    say("cv", entry="lgb.cv", shape=f"{len(tr)}x{X.shape[1]}", configs=1,
        folds=5, rounds=rounds, wall_s=cv_s, compile_s=meter.seconds,
        rmse_first=curve[0], rmse_last=curve[-1])
    check(len(curve) == rounds and np.all(np.isfinite(curve))
          and curve[-1] < curve[0] < float(np.std(y)),
          f"lgb.cv curve is not a finite falling RMSE: {curve}")

    grid = expand_grid(min_data_in_leaf=[20, 40],
                       bagging_fraction=[0.6, 0.8], bagging_freq=[4])
    with meter.measure():
        t0 = time.perf_counter()
        ledger = run_grid_search(grid, ds, base_params=base,
                                 num_boost_round=rounds, nfold=5,
                                 early_stopping_rounds=5, seed=3928272,
                                 verbose=False)
        sweep_s = time.perf_counter() - t0
    scores = [float(r["score"]) for r in ledger.leaderboard()]
    say("cv", entry="run_grid_search", configs=len(grid), folds=5,
        rounds=rounds, wall_s=sweep_s, compile_s=meter.seconds,
        best_score=max(scores), worst_score=min(scores))
    check(len(scores) == len(grid) and np.all(np.isfinite(scores))
          and min(scores) > -float(np.std(y)),
          f"the 4-config fused sweep did not score every config: {scores}")


# ---------------------------------------------------------------------------
# --chips 4 — the cross-chip path and what it is compared with, only
# ---------------------------------------------------------------------------
def _on_distinct_chips(array, n: int, what: str) -> None:
    shards = array.addressable_shards
    devices = {s.device for s in shards}
    check(len(shards) == n and len(devices) == n
          and all(d.platform == "tpu" for d in devices)
          and all(s.data.size > 0 for s in shards),
          f"{what}: not one non-empty shard on each of {n} TPU devices "
          f"({[(s.device, s.data.shape) for s in shards]})")


def phase_four_chips(lgb, seed: int, meter) -> None:
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.serving import PredictorRuntime, pack_booster
    from lightgbm_tpu.utils.datasets import make_higgs_like

    chips = 4
    X, y = make_higgs_like(HIGGS_ROWS, seed=seed)
    Xv, _ = make_higgs_like(4096, seed=seed + 9)
    ds = lgb.Dataset(X, label=y)
    ds.construct()
    params = {"objective": "binary", "num_leaves": 127, "verbosity": -1}
    rounds = 3

    dp, dp_first = _train(lgb, dict(params, tree_learner="data"), ds, rounds,
                          meter)
    mesh = dp._dp_mesh
    check(mesh is not None and mesh.devices.size == chips
          and all(d.platform == "tpu" for d in mesh.devices.flat),
          f"tree_learner=data did not build a {chips}-TPU mesh: {mesh}")
    _on_distinct_chips(dp._dp_bins, chips, "dp training bins")
    _on_distinct_chips(dp._pred_train, chips, "dp training predictions")
    t0 = time.perf_counter()
    dp.update()
    jax.block_until_ready(dp._pred_train)
    dp_round = time.perf_counter() - t0

    serial, serial_first = _train(lgb, params, ds, rounds, meter)
    _, serial_steady = _train(lgb, params, ds, rounds, meter)
    serial_round = serial_steady["wall"] / rounds

    same = True
    leaf_err = 0.0
    for i in range(rounds):
        a, b = dp.trees[i], serial.trees[i]
        same &= all(np.array_equal(np.asarray(getattr(a, f)),
                                   np.asarray(getattr(b, f)))
                    for f in ("split_feature", "split_bin", "is_leaf"))
        # leaves only: slots the exact tail pruned away keep whatever
        # value they had, and no row can reach them
        leaves = np.asarray(a.is_leaf)
        leaf_err = max(leaf_err, float(np.max(np.abs(
            np.asarray(a.leaf_value) - np.asarray(b.leaf_value))[leaves])))
    pred_err = float(np.max(np.abs(
        np.asarray(dp.predict(Xv, num_iteration=rounds, raw_score=True))
        - np.asarray(serial.predict(Xv, num_iteration=rounds,
                                    raw_score=True)))))
    say("dp-train", chips=chips, rows=HIGGS_ROWS, rounds=rounds,
        merge="reduce_scatter_pipelined", dp_compile_s=dp_first["compile_s"],
        serial_compile_s=serial_first["compile_s"],
        dp_first_train_wall_s=dp_first["wall"],
        serial_first_train_wall_s=serial_first["wall"],
        dp_s_per_round=dp_round,
        serial_s_per_round=serial_round, trees_identical=same,
        max_leaf_value_diff=leaf_err, max_raw_pred_diff=pred_err)
    failures = []
    if not same:
        failures.append("dp trees differ from serial trees (structure)")
    if not (leaf_err < 1e-4 and pred_err < 1e-4):
        failures.append(f"dp leaf values / predictions differ from serial: "
                        f"{leaf_err} {pred_err}")

    packed = pack_booster(serial, num_iteration=rounds)
    single = PredictorRuntime(packed)
    sharded = PredictorRuntime(packed, mesh_devices=chips,
                               shard_policy="dp")
    check(all(d.platform == "tpu" for d in sharded.mesh.mesh.devices.flat)
          and sharded.mesh.mesh.devices.size == chips,
          "the serving mesh is not four TPU devices")
    codes = packed.bin_mapper.transform(np.asarray(Xv, np.float64))
    route = sharded.route_for(len(codes))
    check(route == "dp", f"bucket {len(codes)} routed {route}, not dp")
    out1 = single.predict_binned(codes)
    out4 = sharded.predict_binned(codes)
    on_mesh = sharded._get_fn(len(codes), False, route)(
        jnp.asarray(codes), jnp.ones(len(codes), jnp.float32),
        jnp.int32(rounds))
    _on_distinct_chips(on_mesh, chips, "dp serving output")
    say("dp-serve", chips=chips, rows=len(codes), route=route,
        bit_identical_f32=bool(np.array_equal(out1, out4)),
        fused_path=sharded.cache_info()["fused_path"])
    if not np.array_equal(out1, out4):
        failures.append("dp serving is not bit-identical to single-device "
                        "at f32")
    check(not failures, "; ".join(failures))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX's default backend is "
                 f"{devices[0].platform!r}, not a TPU — nothing was run")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
                 f"devices, JAX sees {len(devices)}")

    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.compile_cache import compile_cache_dir

    say("device", platform=devices[0].platform, kind=devices[0].device_kind,
        count=len(devices), jax=jax.__version__,
        compile_cache=compile_cache_dir(),
        dispatch_round_trip_ms=dispatch_round_trip_ms())
    meter = CompileMeter()
    rng = np.random.default_rng(args.seed)
    t_start = time.perf_counter()
    if args.chips == 4:
        phase_four_chips(lgb, args.seed, meter)
    else:
        phase_hist_kernels(rng)
        phase_predict_kernel(rng)
        booster, Xv, yv = phase_train(lgb, args.seed, meter, devices[0])
        packed, codes = phase_predict(booster, Xv, yv)
        with tempfile.TemporaryDirectory() as workdir:
            phase_serve(booster, packed, codes, Xv, meter, workdir)
        phase_cv(lgb, meter)
    say("done", wall_s=time.perf_counter() - t_start)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
