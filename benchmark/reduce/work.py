"""Operations and bytes of a call, from its shapes alone.

Each function returns ``{"ops": ..., "bytes": ..., "peak": <key of
peaks.json the ops run against>}`` for ONE call (or one round); a
roofline reader divides the larger of ``ops / peak`` and ``bytes /
hbm_bytes_per_s`` by the measured time.  ``shapes`` is the metric file's
``shapes`` entry resolved against the run's counters and configuration.
"""

from __future__ import annotations


def hbm_floor_round(shapes: dict) -> dict:
    """What ANY implementation of one boosting round must stream from HBM
    at least once per tree: the rows' bin codes and their gradient and
    hessian (4 bytes each).  No operation count: this floor is bytes."""
    rows, features = int(shapes["rows"]), int(shapes["features"])
    code = int(shapes.get("code_bytes", 1))
    return {"ops": 0.0, "bytes": float(rows * features * code + rows * 8),
            "peak": "bf16_flops_per_s"}


def hist_onehot_call(shapes: dict) -> dict:
    """One histogram pass of the one-hot formulation over ``rows`` rows:
    for every feature a ``[bins, rows] x [rows, 3 * segments]`` matmul
    (gradient, hessian and count of each of the wave's ``segments``
    leaves), so ``2 * rows * features * bins * 3 * segments`` operations.
    This is the work the kernel's formulation does, not the algorithm's
    need (3 adds per row and feature): ``hbm_floor_round`` is that bound.
    Bytes: codes, three float32 statistics and a segment id per row in,
    the float32 histograms out."""
    rows, features = int(shapes["rows"]), int(shapes["features"])
    bins, segments = int(shapes["bins"]), int(shapes["segments"])
    code = int(shapes.get("code_bytes", 1))
    peak = {"bf16": "bf16_flops_per_s", "int8": "int8_ops_per_s"}[
        shapes.get("dtype", "bf16")]
    return {
        "ops": 2.0 * rows * features * bins * 3 * segments,
        "bytes": float(rows * (features * code + 12 + 4)
                       + segments * features * bins * 3 * 4),
        "peak": peak,
    }


def least_seconds(work: dict, peaks: dict):
    """``(seconds, bound)``: the least time the chip could take for
    ``work`` and which of the two bounds it (``"ops"`` or ``"bytes"``)."""
    by_ops = work["ops"] / peaks[work["peak"]]
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (by_ops, "ops") if by_ops >= by_bytes else (by_bytes, "bytes")
