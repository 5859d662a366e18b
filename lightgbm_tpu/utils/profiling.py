"""One in-process recorder of the program's host spans, facts and counts.

``span(name, **fields)`` opens a ``jax.profiler.TraceAnnotation``, so that
under a profiler session the span lies in the ``.xplane.pb`` on the device
trace's clock, and records ``(id, parent, name, start, end, fields)`` in
memory; the parent is the innermost open span of the thread.  Kept: a
bounded ring of the last spans and, per name, ``count``, ``total_s``,
``self_s`` (duration minus what child spans cover), ``max_s``, ``build_s``
and ``builds``: the seconds JAX spent tracing, lowering and compiling (or
reading the persistent cache) while the span was the thread's innermost,
which says which step recompiled.  ``@span(name)`` on a function opens one
per call.  ``note(name, value)`` keeps a fact the
program decided, ``add(name, n)`` a count; ``defer(name, array)`` keeps a
device array the program computed, unread: a counter the device keeps
(the round program's pass log, ``train.passes``), read only when an
operator or the benchmark asks, never on the dispatch path.  Per name the
newest arrays are kept until they hold ``DEFER_ROWS`` rows of their leading
axis (rounds), so that the ring pins a few MB of device memory at most.
``snapshot()`` is all of it as a plain dict, the deferred arrays fetched
there in one ``jax.device_get``; ``reset()`` forgets it.

Always on: it has to see set-up, which no profiler session covers.  With
no session a span costs two clock reads, one locked dict update and an
annotation that does nothing, so spans belong around dispatches and
phases, never around a row or a request.  The open-span stack is per
thread and everything shared is written under one lock.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time

import jax

RING_SPANS = 4096
DEFER_ROWS = 128
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_BUILD_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration",
                 _BACKEND_COMPILE)


class Recorder:
    def __init__(self, clock=time.perf_counter, ring: int = RING_SPANS):
        self._clock = clock
        self._ring_size = int(ring)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._ids = itertools.count(1)
        self._listening = False
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self._ring = collections.deque(maxlen=self._ring_size)
            self._spans, self._facts = {}, {}
            self._counts = collections.Counter()
            self._deferred = {}

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _listen(self) -> None:
        if self._listening:
            return
        with self._lock:
            first, self._listening = not self._listening, True
        if first:
            jax.monitoring.register_event_time_span_listener(self._on_build)

    def _on_build(self, event, start_time, end_time, **_) -> None:
        """JAX's own events, on the thread that builds: an inner ``jit``
        traced inside an outer one reports first and lies inside the
        outer's interval, which then replaces it."""
        stack = getattr(self._local, "stack", None)
        if not stack or event not in _BUILD_EVENTS:
            return
        open_span = stack[-1]
        built = open_span["built"]
        while built and built[-1][0] >= start_time:
            built.pop()
        built.append((start_time, end_time))
        open_span["builds"] += event == _BACKEND_COMPILE

    @contextlib.contextmanager
    def span(self, name: str, **fields):
        self._listen()
        stack = self._stack()
        rec = {"id": next(self._ids), "name": name, "fields": fields,
               "parent": stack[-1]["id"] if stack else None,
               "child_s": 0.0, "built": [], "builds": 0}
        stack.append(rec)
        with jax.profiler.TraceAnnotation(name):
            start = self._clock()
            try:
                yield fields
            finally:
                end = self._clock()
                stack.pop()
                if stack:
                    stack[-1]["child_s"] += end - start
                self._close(rec, start, end)

    def _close(self, rec: dict, start: float, end: float) -> None:
        total = end - start
        build_s = sum(e - s for s, e in rec["built"])
        with self._lock:
            self._ring.append((rec["id"], rec["parent"], rec["name"], start,
                               end, rec["fields"]))
            agg = self._spans.setdefault(rec["name"], {
                "count": 0, "total_s": 0.0, "self_s": 0.0, "max_s": 0.0,
                "build_s": 0.0, "builds": 0})
            agg["count"] += 1
            agg["total_s"] += total
            agg["self_s"] += total - rec["child_s"]
            agg["max_s"] = max(agg["max_s"], total)
            agg["build_s"] += build_s
            agg["builds"] += rec["builds"]

    def note(self, name: str, value) -> None:
        with self._lock:
            self._facts[name] = value

    def add(self, name: str, n=1) -> None:
        with self._lock:
            self._counts[name] += n

    def defer(self, name: str, array) -> None:
        """Keep ``array`` without reading it: no sync, no copy.  The oldest
        arrays of ``name`` go once the newer ones hold ``DEFER_ROWS`` rows
        of the leading axis; the newest is always kept."""
        rows = array.shape[0] if array.ndim else 1
        with self._lock:
            ring = self._deferred.setdefault(name, collections.deque())
            ring.append((rows, array))
            held = sum(r for r, _ in ring)
            while len(ring) > 1 and held - ring[0][0] >= DEFER_ROWS:
                held -= ring.popleft()[0]

    def snapshot(self) -> dict:
        with self._lock:
            snap = {
                "spans": {k: dict(v) for k, v in self._spans.items()},
                "facts": dict(self._facts),
                "counts": dict(self._counts),
                "ring": [dict(zip(("id", "parent", "name", "start", "end",
                                   "fields"), r)) for r in self._ring]}
            deferred = {k: [a for _, a in ring]
                        for k, ring in self._deferred.items()}
        # outside the lock: the fetch waits for the device
        snap["arrays"] = jax.device_get(deferred)
        return snap


_process = Recorder()
span, note, add = _process.span, _process.note, _process.add
defer = _process.defer
snapshot, reset = _process.snapshot, _process.reset
