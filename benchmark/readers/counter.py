"""A counter of the run, or a ratio of sums of counters.

    {"reader": "counter", "counter": "binning_s"}
    {"reader": "counter", "num": ["padded_rows"],
     "den": ["rows", "padded_rows"], "scale": 100}
"""

from __future__ import annotations


def read(ctx: dict, spec: dict):
    counters = ctx["counters"]
    if "counter" in spec:
        return counters.get(spec["counter"])
    keys = list(spec["num"]) + list(spec["den"])
    if any(k not in counters for k in keys):
        return None
    den = sum(counters[k] for k in spec["den"])
    if den <= 0:
        return None
    return spec.get("scale", 1) * sum(counters[k] for k in spec["num"]) / den
