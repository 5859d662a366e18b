"""Device time or calls of the events the program NAMED, from the trace.

    {"reader": "trace_named", "patterns": ["^%lgbtpu_hist_wave"],
     "take": "calls", "over": "counter:window_rounds"}
    {"reader": "trace_named", "complement": true, "take": "seconds",
     "patterns": ["^%lgbtpu_hist_wave", "^%lgbtpu_hist_root"],
     "over": "busy", "scale": 100}

``take``: the ``seconds`` or the ``calls`` of the events whose name matches
any of ``patterns`` (an event's name starts with its HLO instruction,
``%<name>.<n> = ...``, and a Pallas kernel's instruction carries the name
the program gave it); ``complement``: the device's busy seconds OUTSIDE
them (kernels do not overlap on one core, so busy minus their sum).
``over``: ``busy``, ``window``, a ``counter:<key>`` or nothing.  No event
matches (a program that names nothing, a kernel that left the path):
nothing to read, never 0."""

from __future__ import annotations

from .common import resolve


def read(ctx: dict, spec: dict):
    trace = ctx["trace"]
    if trace is None:
        return None
    seconds, calls = 0.0, 0
    for pattern in spec["patterns"]:
        s, c = trace.matching(pattern)
        seconds, calls = seconds + s, calls + c
    if calls <= 0:
        return None
    value = calls if spec.get("take") == "calls" else seconds
    if spec.get("complement"):
        value = trace.busy_s - seconds
    over = spec.get("over")
    if over is not None:
        den = {"busy": trace.busy_s, "window": trace.window_s}.get(
            over, resolve(over, ctx))
        if not isinstance(den, (int, float)) or den <= 0:
            return None
        value /= den
    return spec.get("scale", 1) * value
