"""The device a run is on: the look for a chip, the table of peaks, the
memory reading and the compile meter (copied from ``chip_smoke.py``)."""

from __future__ import annotations

import contextlib
import os

from .manifest import HERE, load_json


def require_chips(chips: int, require_tpu: bool = True):
    """JAX's devices, or ``SystemExit`` with no result printed: a run that
    finds no TPU, or fewer chips than the cell asks for, has measured
    nothing.  (``require_tpu=False`` is for the CPU tests of the harness;
    no command-line flag or environment variable reaches it.)"""
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit(
            f"benchmark: JAX's default backend is {devices[0].platform!r}, "
            "not a TPU: nothing was run, no number is printed")
    if len(devices) < chips:
        raise SystemExit(
            f"benchmark: the cell needs {chips} chip(s), JAX sees "
            f"{len(devices)}: nothing was run")
    return devices[:chips]


def peaks_for(device_kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table or device_kind == "source":
        raise SystemExit(
            f"benchmark: no peaks for device kind {device_kind!r} in "
            "benchmark/peaks.json: a share of a peak cannot be computed")
    return table[device_kind]


def memory_parts(devices) -> dict:
    """The fullest chip's two peaks, from ``Device.memory_stats()``: the
    allocator's ``peak_bytes_in_use`` (live arrays) and
    ``peak_bytes_reserved``, the scratch the runtime sets aside for the
    loaded programs' temporaries.  The TPU runtime counts the two apart
    and both are HBM nothing else can use; a run prints them under
    ``counters`` beside the compiled program's own ``memory_analysis``
    (PR 25: 0.93 GB and 3.10 GB at Higgs-10.5M, the second the compiled
    round's ``temp_size_in_bytes``)."""
    best = {"peak_bytes_in_use": 0, "peak_bytes_reserved": 0}
    for d in devices:
        stats = d.memory_stats() or {}
        parts = {k: int(stats.get(k, 0)) for k in best}
        if sum(parts.values()) > sum(best.values()):
            best = parts
    return best


class CompileMeter:
    """What JAX compiled inside a ``with`` block, from JAX's own monitoring
    events: ``seconds`` in backend compiles (or reads of a persistent-cache
    entry), ``programs`` compiled or fetched, persistent-cache ``hits``
    and ``writes``."""

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
