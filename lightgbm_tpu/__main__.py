"""Config-file CLI (LightGBM's original interface, ``lightgbm config=...``).

Upstream LightGBM ships a C++ CLI driven by ``key=value`` config files with
``task=train|predict`` (src/main.cpp + io/config.cpp).  The snippets repo
never uses it, but it is the library's historical front door, so the same
contract is exposed here over the TPU engine:

    python -m lightgbm_tpu config=train.conf
    python -m lightgbm_tpu task=train data=train.csv valid=valid.csv \
        objective=regression num_trees=100 output_model=model.txt
    python -m lightgbm_tpu task=predict data=test.csv \
        input_model=model.txt output_result=preds.txt

Config format (upstream io/config semantics): one ``key = value`` per line,
``#`` comments; command-line ``key=value`` pairs override file entries.
Data files are CSV/TSV (auto-sniffed) with ``label_column=<int>`` (default
0, upstream default) or ``label_column=name:<col>``; ``header=true|false``
(default false, matching upstream).

``task=serve`` (alias ``predict-server``) is the serving front end: it
loads a model (JSON text or packed ``.npz``), builds the compiled
PredictorRuntime + micro-batching queue (lightgbm_tpu.serving), and
serves newline-delimited requests from stdin to stdout — one CSV row (or
JSON array) of features in, one prediction out, no network dependency:

    python -m lightgbm_tpu task=serve input_model=model.npz \
        max_batch=256 max_delay_ms=2 < requests.csv > preds.txt

Keys: ``output_format=csv|json`` (csv), ``raw_score=true|false`` (false),
``num_iteration`` (staged truncation), ``request_timeout_ms`` (per-request
queue deadline), ``show_stats=true`` (serving counters as JSON on stderr
at shutdown), ``max_bucket``/``max_cache_entries`` (runtime knobs),
``warm_buckets=true`` (precompile the bucket ladder before the first
request so no size class pays its compile on live traffic).

r12 resilience keys (validated at startup; unknown keys are rejected):
``max_queue_depth`` (admission-control bound on live queued requests;
default ``none`` = unbounded), ``shed_policy=off|depth|deadline``
(default ``deadline``: reject requests predicted to miss their deadline
with a typed ``Overloaded`` error instead of letting p99 blow out),
``canary_rows`` (post-swap canary batch size, default 8),
``compile_cache_dir`` (reported only: jax's persistent compilation
cache, which lets restarts serve warm, lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``<checkout>/.jaxcache``).
The model is ModelBank-backed: ``!swap <model.npz>`` /
``!rollback`` / ``!stats`` request lines are control commands (acks on
stderr), and SIGTERM drains gracefully — stop admitting, flush
in-flight, final stats snapshot on stderr.

r14 pod-scale serving keys: ``mesh_devices`` (power of two; shard
dispatches across a device mesh, default 1), ``shard_policy=auto|dp|tp``
(data-parallel row sharding — bit-identical to single-device at f32 —
vs tree-parallel psum splitting vs the automatic batch-size x
forest-depth chooser; default ``auto``), ``forest_precision=f32|bf16|
int8`` (quantized resident forest with per-tree scales — ~2.3x models
per HBM byte at int8; structural fields must narrow exactly or the
deploy is rejected, and the canary gates quantization drift against its
arithmetic bound).  Swaps stay mesh-wide atomic: one runtime owns all
mesh programs, so ``!swap``/``!rollback`` remain one attribute flip.

r13 fault-tolerant training keys (``task=train``): ``checkpoint_dir=``
turns on the resumable loop — atomic checkpoints every
``checkpoint_rounds`` (default 10), ``checkpoint_keep`` generations
retained (default 2), and ``resume=true|false`` (default true: pick up
the newest valid checkpoint, bit-identical continuation).  SIGTERM
finishes the in-flight round, checkpoints, and exits 0, so a preempted
job resumes by rerunning the same command line.

``task=refresh`` (r15) runs the freshness pipeline: watch a directory
for ``*.npz`` row-block files (``X`` + ``y`` arrays), continue training
the live model ``refresh_rounds`` rounds per generation, and push each
versioned artifact through canary + atomic hot swap, reporting the
measured model staleness per flip:

    python -m lightgbm_tpu task=refresh watch_dir=blocks/ \
        state_dir=state/ refresh_rounds=5 staleness_slo_ms=60000 \
        objective=binary num_leaves=31

Keys (validated up front; unknown keys are rejected like ``serve``):
``watch_dir``/``state_dir`` (required), ``refresh_rounds`` (default 5),
``initial_rounds`` (generation 1; defaults to refresh_rounds),
``checkpoint_rounds`` (default 5), ``canary_rows`` (default 8),
``staleness_slo_ms`` (optional SLO; breaches are reported on stderr),
``model_name`` (default "model"), ``max_ticks`` (default 64 — the CLI
drains the watch directory and exits; schedulers rerun it).  Remaining
keys are LightGBM training params, checked against the known parameter
vocabulary.  r17 adds the closed tune->serve loop: ``sweep_grid=<json>``
+ ``sweep_every=N`` makes every Nth data-bearing generation sweep the
grid first and promote the winning config through the same
canary->flip path (``sweep_rounds``/``sweep_nfold``/
``sweep_early_stopping``/``sweep_devices`` bound the sweep).

``task=sweep`` (r17) runs a standalone distributed sweep over a
CSV/TSV training file: the grid (JSON — ``{"axes": {...}}`` expands the
cartesian product, ``{"rows": [...]}`` or a bare list is explicit)
shards into fused-CV hyper-batches over a configs x devices mesh, every
hyper-batch checkpoints between segments, and the ledger is crash-safe
and resumable — a preempted sweep exits 0 and the SAME command line
resumes bit-identically:

    python -m lightgbm_tpu task=sweep data=train.csv \
        sweep_grid=grid.json ledger=sweep.json \
        sweep_checkpoint_dir=ck/ sweep_devices=8 num_trees=500

Keys (typed validation, unknown keys rejected): ``sweep_grid``
(required), ``ledger`` (path; ``.RData`` suffix selects the reference's
codec), ``sweep_checkpoint_dir``, ``sweep_devices``/
``sweep_group_size`` (mesh shape), ``nfold`` (default 5),
``early_stopping_rounds`` (5), ``hyper_batch`` (36),
``engine=auto|fused|host``, ``seed``, ``top`` (leaderboard rows
printed, default 10).  Remaining keys are the shared base params.
"""

from __future__ import annotations

import sys
from typing import Dict, List, Optional, Tuple

import numpy as np


def parse_config_text(text: str) -> Dict[str, str]:
    out: Dict[str, str] = {}
    for line in text.splitlines():
        line = line.split("#", 1)[0].strip()
        if not line:
            continue
        if "=" not in line:
            raise ValueError(f"config line without '=': {line!r}")
        k, v = line.split("=", 1)
        out[k.strip()] = v.strip()
    return out


def parse_argv(argv: List[str]) -> Dict[str, str]:
    """``key=value`` pairs; a ``config=`` file loads first, CLI overrides."""
    pairs: Dict[str, str] = {}
    for a in argv:
        if "=" not in a:
            raise ValueError(f"expected key=value, got {a!r}")
        k, v = a.split("=", 1)
        pairs[k.strip()] = v.strip()
    cfg: Dict[str, str] = {}
    if "config" in pairs:
        with open(pairs.pop("config")) as f:
            cfg = parse_config_text(f.read())
    cfg.update(pairs)
    return cfg


def _load_table(path: str, header: bool) -> Tuple[np.ndarray, List[str]]:
    import csv

    with open(path) as f:
        sample = f.read(4096)
        f.seek(0)
        delim = "\t" if "\t" in sample.split("\n", 1)[0] else ","
        rows = list(csv.reader(f, delimiter=delim))
    names: List[str] = []
    if header:
        names = rows[0]
        rows = rows[1:]
    data = np.asarray(
        [[np.nan if c in ("", "NA", "na", "NaN") else float(c) for c in r]
         for r in rows if r], dtype=np.float64)
    return data, names


def _split_label(data: np.ndarray, names: List[str],
                 label_spec: str) -> Tuple[np.ndarray, np.ndarray]:
    if label_spec.startswith("name:"):
        col = names.index(label_spec[5:])
    else:
        col = int(label_spec)
    y = data[:, col]
    X = np.delete(data, col, axis=1)
    return X, y


def main(argv: Optional[List[str]] = None) -> int:
    raw = list(sys.argv[1:] if argv is None else argv)
    if raw and raw[0] == "lint":
        # graftlint front end — flag-style argv, not key=value config
        from .analysis.cli import main as lint_main

        return lint_main(raw[1:])
    try:
        cfg = parse_argv(raw)
    except (ValueError, OSError) as e:
        # `python -m lightgbm_tpu refresh --help`-style misuse: a typed
        # usage error, never a traceback
        raise SystemExit(
            f"lightgbm_tpu: {e}\nusage: python -m lightgbm_tpu "
            "task=train|predict|serve|refresh|sweep key=value ... "
            "(or config=<file>; see module docs)") from None
    task = cfg.pop("task", "train")
    header = cfg.pop("header", "false").lower() in ("true", "1", "yes")
    label_spec = cfg.pop("label_column", "0")
    data_path = cfg.pop("data", None)
    valid_path = cfg.pop("valid", cfg.pop("valid_data", None))
    output_model = cfg.pop("output_model", "LightGBM_model.txt")
    input_model = cfg.pop("input_model", None)
    output_result = cfg.pop("output_result", "LightGBM_predict_result.txt")

    import lightgbm_tpu as lgb

    if task == "train":
        if data_path is None:
            raise SystemExit("task=train requires data=<file>")
        data, names = _load_table(data_path, header)
        X, y = _split_label(data, names, label_spec)
        ckpt_dir = cfg.pop("checkpoint_dir", None)
        params = dict(cfg)  # remaining keys ARE the LightGBM params;
        # train() resolves every num-rounds alias from them itself
        dtrain = lgb.Dataset(X, label=y)
        if ckpt_dir:
            # fault-tolerant path (r13): auto-checkpoint + SIGTERM drain
            # + resume; a preempted run exits 0 with the checkpoint noted
            # so schedulers can simply relaunch the same command line
            from .engine import _resolve_num_rounds
            from .training import train_resumable

            ckpt_rounds = int(params.pop("checkpoint_rounds", 10))
            keep_last = int(params.pop("checkpoint_keep", 2))
            resume = str(params.pop("resume", "true")).lower() \
                in ("true", "1", "yes")
            rounds = _resolve_num_rounds(params, 100)
            result = train_resumable(
                params, dtrain, rounds, checkpoint_dir=ckpt_dir,
                checkpoint_rounds=ckpt_rounds, keep_last=keep_last,
                resume=resume)
            booster = result.booster
            if result.resumed_from:
                print(f"[lightgbm_tpu] resumed from "
                      f"{result.resumed_from}")
            if result.preempted:
                print(f"[lightgbm_tpu] preempted at round "
                      f"{result.rounds_done}/{rounds}; state -> "
                      f"{result.last_checkpoint} (rerun to resume)")
                return 0
        else:
            valid_sets = None
            if valid_path:
                valid_sets = []
                for vp in valid_path.split(","):  # upstream: comma-sep
                    vdata, vnames = _load_table(vp.strip(), header)
                    Xv, yv = _split_label(vdata, vnames, label_spec)
                    valid_sets.append(dtrain.create_valid(Xv, label=yv))
            booster = lgb.train(params, dtrain, valid_sets=valid_sets)
        booster.save_model(output_model)
        print(f"[lightgbm_tpu] finished training; model -> {output_model}")
        return 0
    if task == "predict":
        if data_path is None or input_model is None:
            raise SystemExit(
                "task=predict requires data=<file> input_model=<model>")
        data, names = _load_table(data_path, header)
        booster = lgb.Booster(model_file=input_model)
        if data.shape[1] == booster.num_feature() + 1:
            # labelled file: drop the label column like upstream predict
            X, _ = _split_label(data, names, label_spec)
        else:
            X = data
        pred = booster.predict(X)
        np.savetxt(output_result, pred, fmt="%.10g")
        print(f"[lightgbm_tpu] predictions -> {output_result}")
        return 0
    if task in ("serve", "predict-server"):
        if input_model is None:
            raise SystemExit(
                "task=serve requires input_model=<model.txt|model.npz>")
        return _serve(input_model, cfg)
    if task == "refresh":
        return _refresh(cfg)
    if task == "sweep":
        return _sweep(cfg, data_path, header, label_spec)
    raise SystemExit(
        f"unknown task {task!r} (train|predict|serve|refresh|sweep)")


def _parse_request_line(line: str) -> Optional[np.ndarray]:
    """One request: CSV floats or a JSON array; blank/comment -> None."""
    line = line.strip()
    if not line or line.startswith("#"):
        return None
    if line.startswith("["):
        import json

        return np.asarray(json.loads(line), dtype=np.float64)
    return np.asarray(
        [np.nan if c.strip() in ("", "NA", "na", "NaN") else float(c)
         for c in line.split(",")], dtype=np.float64)


_SERVE_MODEL = "default"        # single-tenant CLI name in the ModelBank


def _serve(input_model: str, cfg: Dict[str, str],
           stdin=None, stdout=None, stderr=None) -> int:
    """Micro-batched stdin/stdout serving loop (no network dependency).

    Reads one request per line, coalesces through MicroBatcher, answers
    in submission order.  Separated from main() with injectable streams
    so the loop is Tier-1-testable in-process.

    The model lives in a ModelBank, so lines starting with ``!`` are
    control commands (acks on stderr, so the prediction stream stays
    clean): ``!swap <model.npz>`` hot-swaps to a new artifact
    (validate -> warm -> canary -> atomic flip; a rejected swap leaves
    the current version serving), ``!rollback`` flips back to the
    previous resident version, ``!stats`` prints a stats snapshot.

    SIGTERM drains gracefully: stop admitting, flush in-flight requests,
    emit a final stats snapshot on stderr.
    """
    import json
    import signal

    from .serving import SHED_POLICIES, ModelBank, SwapRejected
    from .serving.packed import pack_booster

    stdin = sys.stdin if stdin is None else stdin
    stdout = sys.stdout if stdout is None else stdout
    stderr = sys.stderr if stderr is None else stderr

    def flag(key: str, default: bool = False) -> bool:
        return cfg.pop(key, str(default)).lower() in ("true", "1", "yes")

    def die(msg: str) -> "SystemExit":
        return SystemExit(f"task=serve: {msg}")

    max_batch = int(cfg.pop("max_batch", "128"))
    max_delay_ms = float(cfg.pop("max_delay_ms", "2"))
    max_bucket = int(cfg.pop("max_bucket", "16384"))
    max_cache = int(cfg.pop("max_cache_entries", "12"))
    out_format = cfg.pop("output_format", "csv")
    raw_score = flag("raw_score")
    show_stats = flag("show_stats")
    warm_buckets = flag("warm_buckets")
    tmo = cfg.pop("request_timeout_ms", None)
    timeout_ms = None if tmo is None else float(tmo)
    num_it = cfg.pop("num_iteration", None)
    num_iteration = None if num_it is None else int(num_it)
    # -- r12 resilience knobs, validated up front (a typo'd operating
    # -- point must fail the process at startup, not at 3am under load)
    depth_s = cfg.pop("max_queue_depth", "none").lower()
    try:
        max_queue_depth = None if depth_s in ("none", "") else int(depth_s)
    except ValueError:
        raise die(f"max_queue_depth must be an integer or 'none', "
                  f"got {depth_s!r}") from None
    if max_queue_depth is not None and max_queue_depth < 1:
        raise die(f"max_queue_depth must be >= 1, got {max_queue_depth}")
    shed_policy = cfg.pop("shed_policy", "deadline")
    if shed_policy not in SHED_POLICIES:
        raise die(f"shed_policy must be one of {'|'.join(SHED_POLICIES)},"
                  f" got {shed_policy!r}")
    try:
        canary_rows = int(cfg.pop("canary_rows", "8"))
    except ValueError:
        raise die("canary_rows must be an integer") from None
    if canary_rows < 0:
        raise die(f"canary_rows must be >= 0, got {canary_rows}")
    cache_dir = cfg.pop("compile_cache_dir", None)
    # -- r14 pod-scale knobs, validated up front like the r12 set
    from .serving import FOREST_PRECISIONS, SHARD_POLICIES
    try:
        mesh_devices = int(cfg.pop("mesh_devices", "1"))
    except ValueError:
        raise die("mesh_devices must be an integer") from None
    if mesh_devices < 1 or (mesh_devices & (mesh_devices - 1)):
        raise die(f"mesh_devices must be a power of two >= 1, "
                  f"got {mesh_devices}")
    shard_policy = cfg.pop("shard_policy", "auto")
    if shard_policy not in SHARD_POLICIES:
        raise die(f"shard_policy must be one of "
                  f"{'|'.join(SHARD_POLICIES)}, got {shard_policy!r}")
    forest_precision = cfg.pop("forest_precision", "f32")
    if forest_precision not in FOREST_PRECISIONS:
        raise die(f"forest_precision must be one of "
                  f"{'|'.join(FOREST_PRECISIONS)}, got "
                  f"{forest_precision!r}")
    if cfg:
        raise die(f"unknown key(s): {', '.join(sorted(cfg))}")

    bank = ModelBank(max_bucket=max_bucket, max_cache_entries=max_cache,
                     warm_on_deploy=warm_buckets, canary_rows=canary_rows,
                     cache_dir=cache_dir, mesh_devices=mesh_devices,
                     shard_policy=shard_policy,
                     forest_precision=forest_precision)

    def deploy(path: str) -> dict:
        if path.endswith(".npz"):
            return bank.deploy(_SERVE_MODEL, path, raw_score=raw_score)
        import lightgbm_tpu as lgb

        packed = pack_booster(lgb.Booster(model_file=path))
        return bank.deploy(_SERVE_MODEL, packed, raw_score=raw_score)

    if cache_dir is not None:
        stderr.write(f"[lightgbm_tpu] compile cache in force: "
                     f"{bank.cache_dir}\n")
    try:
        rep = deploy(input_model)
    except SwapRejected as e:
        raise die(f"input_model rejected: {e}") from None
    if warm_buckets:
        # the ladder precompiled inside deploy(), before the first
        # request — each size class pays dispatch, not compile
        stderr.write(f"[lightgbm_tpu] warmed {rep['warmed']} bucket "
                     f"programs\n")
        stderr.flush()
    batcher = bank.batcher(_SERVE_MODEL, max_batch=max_batch,
                           max_delay_ms=max_delay_ms,
                           timeout_ms=timeout_ms, raw_score=raw_score,
                           max_queue_depth=max_queue_depth,
                           shed_policy=shed_policy)
    stats = batcher.stats

    def emit(pending) -> None:
        try:
            v = pending.result()
        except Exception as e:                    # noqa: BLE001
            stdout.write(f"ERROR: {type(e).__name__}: {e}\n")
            return
        v = np.atleast_1d(np.asarray(v, np.float64))
        if out_format == "json":
            stdout.write(json.dumps(
                v.tolist() if v.size > 1 else float(v[0])) + "\n")
        else:
            stdout.write(",".join(f"{x:.10g}" for x in v) + "\n")

    def control(line: str) -> None:
        parts = line[1:].split()
        cmd = parts[0] if parts else ""
        try:
            if cmd == "swap" and len(parts) == 2:
                r = deploy(parts[1])
                stderr.write(f"[lightgbm_tpu] swapped {_SERVE_MODEL} -> "
                             f"{r['version']}\n")
            elif cmd == "rollback":
                r = bank.rollback(_SERVE_MODEL)
                stderr.write(f"[lightgbm_tpu] rolled back {_SERVE_MODEL} "
                             f"-> {r['version']}\n")
            elif cmd == "stats":
                stderr.write(json.dumps(stats.snapshot()) + "\n")
            else:
                stderr.write(f"[lightgbm_tpu] unknown control "
                             f"{line.strip()!r} (!swap <path> | "
                             f"!rollback | !stats)\n")
        except SwapRejected as e:
            # the old version never stopped serving
            stderr.write(f"[lightgbm_tpu] {e}\n")
        stderr.flush()

    draining = False

    def _on_term(signum, frame):                   # noqa: ARG001
        nonlocal draining
        draining = True

    try:
        prev_handler = signal.signal(signal.SIGTERM, _on_term)
    except ValueError:                             # not the main thread
        prev_handler = None

    pendings = []
    try:
        for line in stdin:
            if draining:
                break                              # stop admitting
            if line.lstrip().startswith("!"):
                control(line)
                continue
            try:
                row = _parse_request_line(line)
            except (ValueError, json.JSONDecodeError) as e:
                pendings.append(_failed_pending(e))
                continue
            if row is None:
                continue
            pendings.append(batcher.submit(row,
                                           num_iteration=num_iteration))
            batcher.pump()
            # stream out everything already resolved, in order
            while pendings and pendings[0].done:
                emit(pendings.pop(0))
        # graceful drain (SIGTERM or EOF): flush in-flight, answer all
        batcher.flush()
        for p in pendings:
            emit(p)
        stdout.flush()
    finally:
        if prev_handler is not None:
            signal.signal(signal.SIGTERM, prev_handler)
    if draining:
        stderr.write(f"[lightgbm_tpu] drained on SIGTERM "
                     f"({len(pendings)} in-flight flushed)\n")
    if show_stats or draining:
        stderr.write(json.dumps(stats.snapshot()) + "\n")
        stderr.flush()
    return 0


def _refresh(cfg: Dict[str, str], stdout=None, stderr=None) -> int:
    """``task=refresh``: drive the r15 freshness pipeline over a watch
    directory.  Every refresh key is validated up front and unknown
    keys are rejected (the r12 ``serve`` contract) — a typo'd operating
    point fails at startup, not mid-refresh; the keys left over after
    the refresh set must belong to the known LightGBM/TPU parameter
    vocabulary.  One invocation drains the watch directory (bounded by
    ``max_ticks``) and exits; schedulers keep the loop alive by
    rerunning the same command line — the daemon re-anchors on the
    newest completed artifact in ``state_dir``."""
    import json

    from .config import _ALIASES, _FRAMEWORK_KEYS
    from .pipeline import DirectoryFeed, RefreshDaemon

    stdout = sys.stdout if stdout is None else stdout
    stderr = sys.stderr if stderr is None else stderr

    def die(msg: str) -> "SystemExit":
        return SystemExit(f"task=refresh: {msg}")

    def intkey(key: str, default: str, minimum: int):
        raw_v = cfg.pop(key, default)
        if raw_v is None:
            return None
        try:
            v = int(raw_v)
        except ValueError:
            raise die(f"{key} must be an integer, got {raw_v!r}") \
                from None
        if v < minimum:
            raise die(f"{key} must be >= {minimum}, got {v}")
        return v

    watch_dir = cfg.pop("watch_dir", None)
    if not watch_dir:
        raise die("requires watch_dir=<directory of X/y .npz blocks>")
    state_dir = cfg.pop("state_dir", None)
    if not state_dir:
        raise die("requires state_dir=<directory for models/checkpoints>")
    refresh_rounds = intkey("refresh_rounds", "5", 1)
    initial_rounds = intkey("initial_rounds", None, 1)
    checkpoint_rounds = intkey("checkpoint_rounds", "5", 1)
    canary_rows = intkey("canary_rows", "8", 0)
    max_ticks = intkey("max_ticks", "64", 1)
    model_name = cfg.pop("model_name", "model")
    # r17 closed tune->serve loop: every sweep_every'th data-bearing
    # generation sweeps the grid and promotes the winner
    grid_path = cfg.pop("sweep_grid", None)
    sweep_grid = None
    if grid_path is not None:
        sweep_grid = _load_grid(grid_path, die)
    sweep_every = intkey("sweep_every", "0", 0)
    if sweep_every > 0 and sweep_grid is None:
        raise die("sweep_every > 0 requires sweep_grid=<grid.json>")
    sweep_rounds = intkey("sweep_rounds", "50", 1)
    sweep_nfold = intkey("sweep_nfold", "3", 2)
    sweep_early_stopping = intkey("sweep_early_stopping", "5", 0)
    sweep_devices = intkey("sweep_devices", "1", 1)
    slo_s = cfg.pop("staleness_slo_ms", None)
    staleness_slo_ms = None
    if slo_s is not None:
        try:
            staleness_slo_ms = float(slo_s)
        except ValueError:
            raise die(f"staleness_slo_ms must be a number, got "
                      f"{slo_s!r}") from None
        if staleness_slo_ms <= 0:
            raise die(f"staleness_slo_ms must be > 0, got "
                      f"{staleness_slo_ms}")
    unknown = sorted(k for k in cfg
                     if k.lower() not in _ALIASES
                     and k.lower() not in _FRAMEWORK_KEYS)
    if unknown:
        raise die(f"unknown key(s): {', '.join(unknown)}")

    daemon = RefreshDaemon(
        dict(cfg), state_dir, feed=DirectoryFeed(watch_dir),
        model_name=model_name, refresh_rounds=refresh_rounds,
        initial_rounds=initial_rounds,
        checkpoint_rounds=checkpoint_rounds,
        staleness_slo_ms=staleness_slo_ms, canary_rows=canary_rows,
        sweep_grid=sweep_grid, sweep_every=sweep_every,
        sweep_rounds=sweep_rounds, sweep_nfold=sweep_nfold,
        sweep_early_stopping=sweep_early_stopping,
        sweep_devices=sweep_devices)
    events = daemon.run_until_idle(max_ticks=max_ticks)
    for ev in events:
        doc = {k: v for k, v in ev.items() if k != "report"}
        stdout.write(json.dumps(doc) + "\n")
    snap = daemon.tracker.snapshot()
    stderr.write(json.dumps({
        "generation": daemon.snapshot()["generation"],
        "served": snap["served"],
        "worst_staleness_ms": snap["worst_staleness_ms"],
        "breaches": snap["breaches"],
    }) + "\n")
    stdout.flush()
    stderr.flush()
    return 0


def _load_grid(path: str, die) -> list:
    """Load a sweep grid from a JSON file: ``{"axes": {...}}`` expands
    the cartesian product (R ``expand.grid`` order), ``{"rows": [...]}``
    or a bare list of objects is the explicit row set.  Every misuse is
    a typed one-line error through ``die``."""
    import json

    from .sweep import expand_grid

    try:
        with open(path) as f:
            doc = json.load(f)
    except OSError as e:
        raise die(f"sweep_grid file unreadable: {e}") from None
    except json.JSONDecodeError as e:
        raise die(f"sweep_grid is not valid JSON: {e}") from None
    if isinstance(doc, dict) and "axes" in doc:
        axes = doc["axes"]
        if not isinstance(axes, dict) or not axes or \
                not all(isinstance(v, list) and v for v in axes.values()):
            raise die('sweep_grid "axes" must map param names to '
                      "non-empty lists of values")
        return expand_grid(**axes)
    rows = doc.get("rows") if isinstance(doc, dict) else doc
    if not isinstance(rows, list) or not rows or \
            not all(isinstance(r, dict) for r in rows):
        raise die('sweep_grid must be {"axes": {...}}, {"rows": [...]}, '
                  "or a JSON list of config objects")
    return [dict(r) for r in rows]


def _sweep(cfg: Dict[str, str], data_path: Optional[str], header: bool,
           label_spec: str, stdout=None, stderr=None) -> int:
    """``task=sweep``: run (or resume) a standalone hyperparameter sweep
    over a CSV/TSV training file through the r17 ``SweepService`` —
    scheduled hyper-batches on the fused-CV engine, per-hyper-batch
    checkpoints, a crash-safe resumable ledger, and a leaderboard on
    stdout.  Validation follows the ``serve``/``refresh`` contract:
    every sweep key is checked up front with typed one-line errors,
    unknown keys are rejected against the parameter vocabulary, and a
    preemption exits 0 with the resume instruction — schedulers just
    rerun the same command line."""
    import json

    from .config import _ALIASES, _FRAMEWORK_KEYS
    from .engine import _resolve_num_rounds

    stdout = sys.stdout if stdout is None else stdout
    stderr = sys.stderr if stderr is None else stderr

    def die(msg: str) -> "SystemExit":
        return SystemExit(f"task=sweep: {msg}")

    def intkey(key: str, default, minimum: int):
        raw_v = cfg.pop(key, default)
        if raw_v is None:
            return None
        try:
            v = int(raw_v)
        except ValueError:
            raise die(f"{key} must be an integer, got {raw_v!r}") \
                from None
        if v < minimum:
            raise die(f"{key} must be >= {minimum}, got {v}")
        return v

    if data_path is None:
        raise die("requires data=<train file>")
    grid_path = cfg.pop("sweep_grid", None)
    if not grid_path:
        raise die('requires sweep_grid=<grid.json> ({"axes": {...}}, '
                  '{"rows": [...]}, or a list of config objects)')
    grid = _load_grid(grid_path, die)
    sweep_devices = intkey("sweep_devices", "1", 1)
    sweep_group_size = intkey("sweep_group_size", "1", 1)
    if sweep_devices % sweep_group_size:
        raise die(f"sweep_group_size must divide sweep_devices (got "
                  f"group_size={sweep_group_size}, "
                  f"devices={sweep_devices})")
    ckpt_dir = cfg.pop("sweep_checkpoint_dir", None)
    if ckpt_dir is not None and not str(ckpt_dir).strip():
        raise die("sweep_checkpoint_dir must be a directory path")
    ledger_path = cfg.pop("ledger", None)
    nfold = intkey("nfold", "5", 2)
    early_stopping = intkey("early_stopping_rounds", "5", 0)
    hyper_batch = intkey("hyper_batch", "36", 1)
    seed = intkey("seed", "0", 0)
    top = intkey("top", "10", 1)
    engine = cfg.pop("engine", "auto")
    if engine not in ("auto", "fused", "host"):
        raise die(f"engine must be auto|fused|host, got {engine!r}")
    unknown = sorted(k for k in cfg
                     if k.lower() not in _ALIASES
                     and k.lower() not in _FRAMEWORK_KEYS)
    if unknown:
        raise die(f"unknown key(s): {', '.join(unknown)}")
    params = dict(cfg)
    rounds = _resolve_num_rounds(params, 100)

    import lightgbm_tpu as lgb

    from .sweep import SweepService

    data, names = _load_table(data_path, header)
    X, y = _split_label(data, names, label_spec)
    service = SweepService(
        grid, lgb.Dataset(X, label=y), base_params=params,
        num_boost_round=rounds, nfold=nfold,
        early_stopping_rounds=early_stopping, seed=seed, engine=engine,
        ledger_path=ledger_path, checkpoint_dir=ckpt_dir,
        n_devices=sweep_devices, group_size=sweep_group_size,
        hyper_batch=hyper_batch, verbose=True)
    result = service.run()
    if result.preempted:
        pend = len(result.ledger.pending())
        stderr.write(f"[lightgbm_tpu] sweep preempted ({result.error}); "
                     f"{pend}/{len(grid)} configs pending — rerun the "
                     f"same command line to resume\n")
        stderr.flush()
        return 0
    for row in result.ledger.leaderboard()[:top]:
        stdout.write(json.dumps(row) + "\n")
    stderr.write(json.dumps({
        "engine": result.engine, "units": result.units_total,
        "resumed_units": result.resumed_units,
        "configs": len(grid),
        "rounds_total": result.stats.get("rounds_total", 0),
    }) + "\n")
    stdout.flush()
    stderr.flush()
    return 0


def _failed_pending(e: Exception):
    from .serving import PendingPrediction

    p = PendingPrediction()
    p._set(error=e)
    return p


if __name__ == "__main__":
    sys.exit(main())
