"""A kernel's share of its roofline, in percent, from the device trace.

    {"reader": "trace_roofline", "calls": [
        {"pattern": "<regex of one kind of call's events>",
         "work": "<function of benchmark/reduce/work.py>", "shapes": {...}},
        ...]}

Least time of a call = max(ops / peak, bytes / peak bytes/s) from the
call's shapes; the share is the sum over the matching events of their
least time over the sum of their device time.  No matching event: nothing
to read, and the metric is left out (never 0).  Which bound holds, and the
counts, go to ``ctx["notes"]`` for the result line."""

from __future__ import annotations

from ..reduce import work
from .common import resolve_shapes


def read(ctx: dict, spec: dict):
    trace, peaks = ctx["trace"], ctx["peaks"]
    if trace is None or peaks is None:
        return None
    least_total, seconds_total, notes = 0.0, 0.0, []
    for call in spec["calls"]:
        shapes = resolve_shapes(call["shapes"], ctx)
        if shapes is None:
            continue
        seconds, calls = trace.matching(call["pattern"])
        if calls <= 0 or seconds <= 0:
            continue
        least, bound = work.least_seconds(
            getattr(work, call["work"])(shapes), peaks)
        least_total += calls * least
        seconds_total += seconds
        notes.append({"pattern": call["pattern"], "calls": calls,
                      "device_s": seconds, "least_s_per_call": least,
                      "bound": bound})
    if seconds_total <= 0:
        return None
    ctx.setdefault("notes", {})[spec.get("layer", "kernel")] = notes
    return 100.0 * least_total / seconds_total
