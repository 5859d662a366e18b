"""lightgbm_tpu.serving — compiled batch-inference runtime.

Turns a trained/loaded Booster into a standalone serving artifact and
drives it at high throughput:

    from lightgbm_tpu.serving import pack_booster, PredictorRuntime

    packed = pack_booster(booster)            # SoA tensor stack + bin bounds
    packed.save("model.npz")                  # versioned serving artifact

    rt = PredictorRuntime(PackedForest.load("model.npz"))
    preds = rt.predict(X)                     # bucketed, compile-cached

    batcher = MicroBatcher(rt, max_batch=256, max_delay_ms=2.0)
    handle = batcher.submit(row); batcher.pump(); handle.result()

Multi-model tenancy and resilience ride on top:

    bank = ModelBank(warm_on_deploy=True)
    bank.deploy("fraud", "model_v1.npz")      # validate -> warm -> canary -> flip
    mb = bank.batcher("fraud", max_queue_depth=512)   # sheds with Overloaded
    bank.deploy("fraud", "model_v2.npz")      # zero-downtime hot swap
    bank.rollback("fraud")                    # instant, bit-identical

See packed.py (format + ingest validation), runtime.py (shape-bucketed
compile cache), queue.py (micro-batching + admission control), bank.py
(tenancy/hot swap/rollback), faults.py (deterministic fault injection),
stats.py (counters).  The CLI front end is ``python -m lightgbm_tpu
task=serve input_model=...``.
"""

from ..ops.quantize import FOREST_PRECISIONS, ThresholdBoundError
from .bank import ModelBank, SwapRejected
from .faults import SITES as FAULT_SITES
from .faults import FaultError, FaultInjector, FaultSpec
from .mesh import SHARD_POLICIES, ServingMesh, choose_route
from .packed import (PACKED_FORMAT_VERSION, PackedForest, PackedForestError,
                     pack_booster)
from .queue import (SHED_POLICIES, MicroBatcher, Overloaded,
                    PendingPrediction, RequestTimeout)
from .runtime import (DeviceProgramError, PredictorRuntime, bucket_for,
                      enable_persistent_cache)
from .stats import ServingStats

__all__ = [
    "DeviceProgramError",
    "FAULT_SITES",
    "FOREST_PRECISIONS",
    "FaultError",
    "FaultInjector",
    "FaultSpec",
    "MicroBatcher",
    "ModelBank",
    "Overloaded",
    "PACKED_FORMAT_VERSION",
    "PackedForest",
    "PackedForestError",
    "PendingPrediction",
    "PredictorRuntime",
    "RequestTimeout",
    "SHARD_POLICIES",
    "SHED_POLICIES",
    "ServingMesh",
    "ServingStats",
    "SwapRejected",
    "ThresholdBoundError",
    "bucket_for",
    "choose_route",
    "enable_persistent_cache",
    "pack_booster",
]
