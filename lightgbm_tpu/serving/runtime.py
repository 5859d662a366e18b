"""PredictorRuntime — compiled batch inference over a PackedForest.

The training-side predictor (Booster.predict) retraces for every new batch
shape: a traffic mix of 1000 distinct batch sizes means 1000 XLA compiles.
The serving runtime instead:

* rounds every incoming batch UP to a power-of-two bucket and pads with
  masked rows, so the whole size range [1, max_bucket] shares
  ``log2(max_bucket) + 1`` compiled programs;
* keeps the compiled predict callables in a bounded LRU keyed by
  ``(bucket, raw_score)`` — the ``ntree_limit`` truncation mask is a
  TRACED argument of every program (the repo's staged-predict contract),
  so changing it never recompiles and never grows the key space;
* performs the raw->binned transform on the edge with the packed bin
  bounds (the same dataset.BinMapper search the trainer used, so serving
  and training binning can never diverge);
* batches larger than ``max_bucket`` stream through in full-bucket chunks.

r14 adds the pod-scale knobs (see :mod:`serving.mesh` and
:mod:`ops.quantize`):

* ``mesh_devices``/``shard_policy`` — shard dispatches across a device
  mesh: data-parallel row sharding (bit-identical to single-device at
  f32), tree-parallel ``psum`` splitting, or an automatic chooser.  The
  route is a third compile-cache key component and ``warm()`` warms the
  chosen route per bucket, so sharded traffic pays zero traffic-path
  compiles after a warm deploy.
* ``forest_precision`` — keep the resident forest quantized (int8/bf16
  leaf values with per-tree scales, uint8 thresholds, int16 indices).
  ``runtime.oracle`` is a PackedForest carrying the DEQUANTIZED leaf
  values — the numpy reference for the canary and the queue's fallback
  path, so device-vs-oracle stays tight at any precision — and
  ``quant_error_bound`` is the worst-case |quantized - exact| served
  margin (arithmetic from ``ops.quantize``, not an estimate).

r18 makes the FUSED mega-kernel the default device path (ROADMAP item
3): every non-categorical forest packs into per-class
``ops.predict.ForestSoA`` tables — depth-major, lane-padded, in the
COMPACT storage dtypes — and every bucket program is one
``predict_forest_pallas`` launch per class instead of the chunked
scan-of-scans.  Quantized forests are traversed directly in quantized
space: thresholds compare as stored uint8 bin codes and the per-tree
scale folds into the traced round mask, so no f32 (or i32) node table
is ever materialized in HBM — not resident, not transiently per
dispatch.  The oracle's f32 leaf table is built LAZILY on first
canary/fallback access and cached, never eagerly at ingest.
Categorical forests keep the legacy widen-in-program path
(``fused_predict`` is False there) with identical external semantics.

Per-bucket counters (requests, dispatches, cache hits/misses, padding
waste, latency quantiles) land in :class:`serving.stats.ServingStats`,
which r18 extends with live ``predict_kernel_launches`` / ``fused_path``
counters.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from collections import OrderedDict
from typing import Optional

import numpy as np

from ..ops.quantize import (FOREST_PRECISIONS, packed_model_bytes,
                            quantize_forest, to_device_tree, widen_tree)
from .mesh import SHARD_POLICIES, ServingMesh, choose_route
from .packed import PackedForest
from .stats import ServingStats

DEFAULT_MAX_BUCKET = 1 << 14          # 16384-row dispatches
DEFAULT_CACHE_ENTRIES = 12


def bucket_for(n: int, max_bucket: int) -> int:
    """Smallest power-of-two >= n, capped at max_bucket."""
    if n <= 1:
        return 1
    return min(1 << (int(n - 1).bit_length()), max_bucket)


def enable_persistent_cache(cache_dir: Optional[str] = None) -> str:
    """Cache EVERY program in jax's persistent compilation cache and
    return the cache directory in force.

    The directory is not chosen here: ``utils.compile_cache`` holds the
    one rule (``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/
    .jaxcache``), and a ``cache_dir`` that differs from it is ignored
    with a warning.  What serving adds is the thresholds: zeroed, so
    even the small bucket programs are cached and a restarted process
    that re-warms the same ladder deserializes executables instead of
    recompiling them.
    """
    import jax

    from ..utils.compile_cache import compile_cache_dir

    in_force = compile_cache_dir()
    if cache_dir and os.path.abspath(str(cache_dir)) != \
            os.path.abspath(in_force):
        import warnings

        warnings.warn(
            f"compile cache directory {str(cache_dir)!r} ignored: the "
            f"cache in force is {in_force!r} (set JAX_COMPILATION_CACHE_DIR "
            "to move it)", stacklevel=2)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return in_force


class DeviceProgramError(RuntimeError):
    """A bucket program could not be BUILT for this device.

    Every program is traced, lowered and compiled ahead of its first
    run (``PredictorRuntime._get_fn``), and a failure there (a kernel
    the chip's compiler refuses, a shape error) is deterministic: the
    same program fails the same way for every later batch.  It is
    therefore never degraded to the host oracle — ``warm()``/deploy
    raise it, the canary refuses the swap, and ``MicroBatcher`` fails
    the requests with it.  An error from RUNNING a compiled program is
    not this error, whether it is the program's first run or not: the
    queue treats it as a transient device fault."""


class PredictorRuntime:
    """Serve a packed forest at fixed shapes with a bounded compile cache.

    Args:
      packed: a validated PackedForest (``PackedForest.load`` validates).
      max_bucket: largest single-dispatch row count (power of two);
        bigger batches are chunked.
      max_cache_entries: LRU bound on live compiled programs.  Eviction
        drops the executable, so a re-used evicted bucket recompiles.
      faults: optional serving.faults.FaultInjector consulted at the
        ``device_predict`` site before every compiled dispatch — the
        deterministic stand-in for a device error mid-predict.
      mesh_devices: shard dispatches across this many devices (power of
        two; 1 = the r12 single-device behavior, unchanged).
      shard_policy: ``auto`` | ``dp`` | ``tp`` — see
        :func:`serving.mesh.choose_route`.
      forest_precision: ``f32`` | ``bf16`` | ``int8`` resident forest
        (module docstring).  Raises ``ops.quantize.ThresholdBoundError``
        when a structural field cannot be narrowed EXACTLY.
    """

    def __init__(self, packed: PackedForest,
                 max_bucket: int = DEFAULT_MAX_BUCKET,
                 max_cache_entries: int = DEFAULT_CACHE_ENTRIES,
                 stats: Optional[ServingStats] = None,
                 faults=None,
                 mesh_devices: int = 1,
                 shard_policy: str = "auto",
                 forest_precision: str = "f32",
                 clock=time.perf_counter):
        if max_bucket < 1 or (max_bucket & (max_bucket - 1)):
            raise ValueError(f"max_bucket must be a power of two, got "
                             f"{max_bucket}")
        if shard_policy not in SHARD_POLICIES:
            raise ValueError(f"shard_policy must be one of "
                             f"{SHARD_POLICIES}, got {shard_policy!r}")
        if forest_precision not in FOREST_PRECISIONS:
            raise ValueError(f"forest_precision must be one of "
                             f"{FOREST_PRECISIONS}, got "
                             f"{forest_precision!r}")
        self.packed = packed
        self.max_bucket = int(max_bucket)
        self.max_cache_entries = int(max_cache_entries)
        self.stats = stats if stats is not None else ServingStats()
        self.faults = faults
        # injectable latency source (r12 clock contract) — pass
        # ``faults.wrap_clock(...)`` here to skew it deterministically
        self.clock = clock
        self.shard_policy = shard_policy
        self.forest_precision = forest_precision
        self.mesh = (ServingMesh(mesh_devices) if int(mesh_devices) > 1
                     else None)
        # r18: the fused SoA mega-kernel is the default device path;
        # categorical subset splits keep the legacy chunked-scan path
        # (the SoA traversal has no cat-mask lane yet)
        self.fused_predict = packed.is_cat_split is None
        self._q = None
        if forest_precision == "f32":
            self.quant_error_bound = 0.0
        else:
            self._q = quantize_forest(
                packed.split_feature, packed.split_bin, packed.left,
                packed.right, packed.leaf_value, packed.is_leaf,
                forest_precision, is_cat_split=packed.is_cat_split,
                cat_mask=packed.cat_mask)
            # served margins scale the raw tree sum by shrink; multiply
            # the raw bound through so callers compare against outputs
            self.quant_error_bound = (self._q.error_bound
                                      * abs(packed.shrink))
        # the numpy oracle (and its f32 leaf table, for quantized
        # forests) is built lazily on first canary/fallback access —
        # never eagerly at ingest, never rebuilt per swap
        self._oracle = None
        self._oracle_lock = threading.Lock()
        self._forest = None
        self._leaf_scale = None
        self._soa = None                # per-class ForestSoA (fused path)
        if self.fused_predict:
            self._soa = self._build_soa()
        elif forest_precision == "f32":
            self._forest = packed.to_tree()       # device-resident once
        else:
            self._forest, self._leaf_scale = to_device_tree(self._q)
        self.forest_nbytes = packed_model_bytes(
            packed.num_trees, packed.capacity, packed.num_class,
            forest_precision)
        # mega-kernel launches one compiled dispatch costs (per class;
        # 0 on the legacy path) — mirrored into every record_dispatch
        self.kernel_launches_per_dispatch = (
            packed.num_class if self.fused_predict else 0)
        self._tp_padded = None          # lazily built (forest, scale, t/D)
        self._tp_soa = None             # lazily built ([soa/class], t/D)
        self._obj = packed._objective()
        self._cache: "OrderedDict[tuple, object]" = OrderedDict()
        self.num_compiles = 0                      # lifetime program builds
        self.warmed_buckets = 0                    # precompiled via warm()
        self.warmed_keys: set = set()   # full (bucket, raw, route) keys
        self.buckets = [1 << i
                        for i in range(self.max_bucket.bit_length())]
        # compile-cache counters ride along in every stats snapshot (the
        # serve CLI prints ONE dict on shutdown; tools embed the same)
        self.stats.attach_cache(self.cache_info)

    @property
    def oracle(self) -> PackedForest:
        """Numpy reference forest for the canary gates and the queue's
        graceful-degradation fallback.

        Built LAZILY on first access and cached for the runtime's
        lifetime: the f32 leaf table a quantized runtime's oracle
        carries exists only here — never in device HBM (the fused
        kernel reads the int8/bf16 arrays directly) and never eagerly
        at ingest, so a hot swap whose canary is skipped and whose
        fallback never fires pays zero dequantize cost (r18 satellite
        of the quantized-space mega-kernel)."""
        if self._oracle is None:
            with self._oracle_lock:
                if self._oracle is None:
                    self._oracle = (
                        self.packed if self._q is None
                        else dataclasses.replace(
                            self.packed,
                            leaf_value=self._q.dequantized_leaf_values()))
        return self._oracle

    def _build_soa(self):
        """Per-class ``ForestSoA`` residency tables for the fused kernel.

        Quantized forests pack their COMPACT arrays straight through —
        uint8 thresholds and int8/bf16 leaves go to the device in
        storage dtype, per-tree scales ride as the f32 sidecar the
        kernel folds into the round mask.  f32 forests pack i32/f32
        (their contract dtypes)."""
        from ..ops.predict import pack_forest_soa

        p, q = self.packed, self._q
        nc = p.num_class
        soas = []
        for c in range(nc):
            ci = c if nc > 1 else None
            if q is None:
                pick = (lambda a: np.asarray(a)) if ci is None else (
                    lambda a: np.asarray(a)[:, ci])
                feat, thr = pick(p.split_feature), pick(p.split_bin)
                left, right = pick(p.left), pick(p.right)
                leaf, isl = (pick(p.leaf_value).astype(np.float32),
                             pick(p.is_leaf))
                scale = None
            else:
                feat, thr, left, right, leaf, isl, scale = \
                    q.class_arrays(ci)
            soas.append(pack_forest_soa(
                feat, thr, left, right, leaf, isl,
                precision=self.forest_precision, leaf_scale=scale))
        return soas

    # -- public API ----------------------------------------------------------
    def predict(self, data, num_iteration: Optional[int] = None,
                raw_score: bool = False) -> np.ndarray:
        """Predict on RAW features (binned on the edge, then dispatched)."""
        from ..dataset import _to_2d_float_array

        X = _to_2d_float_array(data)
        codes = self.packed.bin_mapper._transform_unbundled(X)
        return self.predict_binned(codes, num_iteration=num_iteration,
                                   raw_score=raw_score)

    def predict_binned(self, codes: np.ndarray,
                       num_iteration: Optional[int] = None,
                       raw_score: bool = False) -> np.ndarray:
        """Predict on pre-binned codes (uint8/int [n, F])."""
        k = self.packed._resolve_k(num_iteration)
        n = codes.shape[0]
        if n == 0:
            width = (self.packed.num_class,) if self.packed.num_class > 1 \
                else ()
            return np.zeros((0,) + width, np.float32)
        outs = []
        for lo in range(0, n, self.max_bucket):
            outs.append(self._dispatch(codes[lo:lo + self.max_bucket], k,
                                       raw_score))
        return outs[0] if len(outs) == 1 else np.concatenate(outs)

    def cache_info(self) -> dict:
        # counters only — this runs inside every stats snapshot, so no
        # per-call rebuild of a stringified key list
        return {
            "entries": len(self._cache),
            "max_entries": self.max_cache_entries,
            "num_compiles": self.num_compiles,
            "warmed_buckets": self.warmed_buckets,
            "buckets_live": sorted({k[0] for k in self._cache}),
            # r14: shard programs are first-class cache citizens — the
            # warm-coverage test pins that these counters see them
            "mesh_devices": (self.mesh.devices if self.mesh else 1),
            "forest_precision": self.forest_precision,
            "shard_programs": sum(1 for k in self._cache
                                  if k[2] != "single"),
            "routes_live": sorted({k[2] for k in self._cache}),
            # r18: which device path this runtime serves on, and what
            # one dispatch costs in mega-kernel launches (0 = legacy)
            "fused_path": bool(self.fused_predict),
            "kernel_launches_per_dispatch":
                self.kernel_launches_per_dispatch,
            "warmed_keys": len(self.warmed_keys),
        }

    def route_for(self, bucket: int) -> str:
        """The dispatch route this bucket resolves to — deterministic,
        shared verbatim by ``_dispatch`` and ``warm()`` (which is what
        makes warm coverage of shard programs provable)."""
        if self.mesh is None:
            return "single"
        return choose_route(self.shard_policy, bucket,
                            self.packed.num_trees, self.mesh.devices)

    def warm(self, raw_score: bool = False, buckets=None) -> int:
        """Precompile the bucket ladder before traffic arrives.

        Dispatches one fully-masked all-zeros batch per bucket so each
        size class's compile cost lands at startup instead of on its
        first real request.  Warm batches use the same uint8 codes dtype
        the edge transform produces, so the compiled programs are
        exactly the ones traffic will hit.  With a mesh active each
        bucket warms the ROUTE the deterministic chooser will dispatch
        it to (dp/tp shard programs included), so the first sharded
        batch after a swap pays zero traffic-path compiles.  The sweep
        is keyed on the FULL compile key ``(bucket, raw_score, route)``
        — precision is a per-runtime constant baked into every program
        — and the warmed key set is recorded verbatim in
        ``warmed_keys``, so "the first quantized dp request pays no
        traffic-path compile" is checkable (the
        ``serving_recompile_*`` lint specs sweep exactly this
        contract).  When the ladder exceeds the LRU bound only the
        LARGEST ``max_cache_entries`` buckets are warmed — warming more
        would evict programs just built.  Returns the number of
        programs compiled.  Raises :class:`DeviceProgramError` when a
        program cannot be built for this device.
        """
        import jax
        import jax.numpy as jnp

        todo = list(buckets) if buckets is not None else list(self.buckets)
        if len(todo) > self.max_cache_entries:
            todo = todo[-self.max_cache_entries:]
        before = self.num_compiles
        for b in todo:
            key = (b, bool(raw_score), self.route_for(b))
            codes, mask, _ = self._program_operands(b)
            jax.block_until_ready(self._get_fn(*key)(
                jnp.zeros(codes.shape, codes.dtype),
                jnp.zeros(mask.shape, mask.dtype), jnp.int32(1)))
            self.warmed_keys.add(key)
        self.warmed_buckets += len(todo)
        return self.num_compiles - before

    # -- internals -----------------------------------------------------------
    def _dispatch(self, codes: np.ndarray, k: int,
                  raw_score: bool) -> np.ndarray:
        import jax.numpy as jnp

        if self.faults is not None:
            self.faults.check("device_predict")   # may raise FaultError
        t0 = self.clock()
        n = codes.shape[0]
        bucket = bucket_for(n, self.max_bucket)
        pad = bucket - n
        if pad:
            codes = np.concatenate(
                [codes, np.zeros((pad, codes.shape[1]), codes.dtype)])
        mask = np.zeros(bucket, np.float32)
        mask[:n] = 1.0
        route = self.route_for(bucket)
        fn = self._get_fn(bucket, raw_score, route)
        out = np.asarray(fn(jnp.asarray(codes, jnp.uint8),
                            jnp.asarray(mask), jnp.int32(k)))
        self.stats.record_dispatch(
            bucket, rows=n, padded=pad,
            latency_s=self.clock() - t0, route=route,
            kernel_launches=self.kernel_launches_per_dispatch,
            fused=self.fused_predict)
        return out[:n]

    def _program_operands(self, bucket: int) -> tuple:
        """What every program of ``bucket`` takes: the uint8 codes the
        edge transform produces, the f32 row mask, the round count."""
        import jax

        return (jax.ShapeDtypeStruct((bucket, self.packed.num_feature()),
                                     np.uint8),
                jax.ShapeDtypeStruct((bucket,), np.float32),
                jax.ShapeDtypeStruct((), np.int32))

    def _get_fn(self, bucket: int, raw_score: bool,
                route: str = "single"):
        key = (bucket, bool(raw_score), route)
        fn = self._cache.get(key)
        if fn is not None:
            self._cache.move_to_end(key)
            self.stats.record_cache(bucket, hit=True)
            return fn
        self.stats.record_cache(bucket, hit=False)
        # compiled HERE, ahead of the run, so that "cannot be built for
        # this device" and "failed while running" stay two errors
        try:
            fn = self._build_fn(raw_score, route).lower(
                *self._program_operands(bucket)).compile()
        except Exception as e:
            raise DeviceProgramError(
                f"bucket program {key} cannot be built for this device "
                f"— {type(e).__name__}: {e}") from e
        self.num_compiles += 1
        self._cache[key] = fn
        while len(self._cache) > self.max_cache_entries:
            self._cache.popitem(last=False)        # evict LRU
        return fn

    def _tp_parts(self):
        """Tree-axis-padded (forest, leaf_scale, trees_per_device) —
        built once, shared by every tp bucket program (legacy path)."""
        if self._tp_padded is None:
            from .mesh import pad_forest_for_tp

            self._tp_padded = pad_forest_for_tp(
                self._forest, self._leaf_scale, self.mesh.devices)
        return self._tp_padded

    def _tp_soa_parts(self):
        """Tree-axis-padded per-class SoAs + trees_per_device for the
        fused tp route — built once, shared by every tp bucket program.
        Padding goes to a multiple of (sublane chunk x devices) so each
        shard's slice is itself a legal kernel operand."""
        if self._tp_soa is None:
            from .mesh import pad_soa_for_tp

            padded = [pad_soa_for_tp(s, self.mesh.devices)
                      for s in self._soa]
            self._tp_soa = ([p[0] for p in padded], padded[0][1])
        return self._tp_soa

    def _build_fn(self, raw_score: bool, route: str = "single"):
        """One jitted fixed-shape predict program.

        ``num_iteration`` is traced (the forest replay masks rounds on
        device), so every staged-prediction variant shares this program.
        Padded rows are valid bin codes (zeros) that traverse normally;
        the row mask zeroes their outputs so no padding garbage escapes,
        and for probability transforms the masked rows are neutralized
        BEFORE the transform would see them downstream.

        Routes (see :mod:`serving.mesh`): ``single`` is the r12 program;
        ``dp`` wraps the IDENTICAL body in a row-sharding ``shard_map``
        (bit-identical outputs at f32); ``tp`` shards the forest's tree
        axis and ``psum``s raw margins, applying init/rf/transform/mask
        on the replicated result.

        r18: on the default fused path the body is ONE
        ``predict_forest_pallas`` launch per class over the resident
        SoA — quantized forests traverse in quantized space, nothing
        widens, not even transiently.  Categorical forests fall back to
        the legacy body, which widens inside the program (per shard for
        tp) so compute is f32 while residency stays compact.
        """
        import jax
        import jax.numpy as jnp
        from ..ops.predict import (predict_forest_binned,
                                   predict_forest_pallas)

        packed = self.packed
        forest = self._forest
        leaf_scale = self._leaf_scale
        quantized = self.forest_precision != "f32"
        fused = self.fused_predict
        soas = self._soa
        obj = self._obj
        nc = packed.num_class
        shrink = jnp.float32(packed.shrink)
        inits = np.asarray(packed.init_score, np.float32)
        depth_cap = packed.depth_cap
        is_rf = packed.params.get("boosting") == "rf"

        def finalize(raw, mask, num_it):
            if is_rf:
                if nc > 1:
                    raw = ((raw - inits[None, :])
                           / jnp.maximum(num_it, 1) + inits[None, :])
                else:
                    raw = ((raw - inits[0]) / jnp.maximum(num_it, 1)
                           + inits[0])
            out = raw if raw_score else obj.transform(raw)
            return out * (mask[:, None] if nc > 1 else mask)

        if route == "tp":
            if fused:
                from .mesh import tp_raw_margins_fused

                tp_soas, t_loc = self._tp_soa_parts()
                raw_fn = tp_raw_margins_fused(
                    self.mesh, tp_soas, t_loc, shrink, depth_cap,
                    num_class=nc)
            else:
                from .mesh import tp_raw_margins

                tp_forest, tp_scale, t_loc = self._tp_parts()
                raw_fn = tp_raw_margins(
                    self.mesh, tp_forest, tp_scale, t_loc, shrink,
                    depth_cap, num_class=nc, widen=quantized)

            def fn(bins, mask, num_it):
                raw = raw_fn(bins, num_it) + (
                    inits[None, :] if nc > 1 else inits[0])
                return finalize(raw, mask, num_it)
        else:
            if fused:
                def fn(bins, mask, num_it):
                    cols = [predict_forest_pallas(
                        soas[c], bins, shrink, float(inits[c]), num_it,
                        depth_cap) for c in range(nc)]
                    raw = (jnp.stack(cols, axis=1) if nc > 1
                           else cols[0])
                    return finalize(raw, mask, num_it)
            else:
                def fn(bins, mask, num_it):
                    f = widen_tree(forest, leaf_scale) if quantized \
                        else forest
                    if nc > 1:
                        cols = [predict_forest_binned(
                            jax.tree.map(lambda a, c=c: a[:, c], f),
                            bins, shrink, float(inits[c]), num_it,
                            depth_cap) for c in range(nc)]
                        raw = jnp.stack(cols, axis=1)            # [n, K]
                    else:
                        raw = predict_forest_binned(
                            f, bins, shrink, float(inits[0]), num_it,
                            depth_cap)
                    return finalize(raw, mask, num_it)

            if route == "dp":
                from .mesh import dp_shard

                fn = dp_shard(self.mesh, fn, check_vma=not fused)

        return jax.jit(fn)
