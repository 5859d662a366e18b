"""Seconds the PROGRAM's own recorder kept for its host spans
(``lightgbm_tpu.utils.profiling.snapshot()``, read in the process that
ran the window): set-up included, which no profiler session covers.

    {"reader": "program_span", "scale": 1000, "per": "count",
     "last": "counter:window_calls",
     "terms": [{"pattern": "^lgbtpu\\.train\\.update_many$",
                "field": "total_s"}]}

The sum over ``terms`` of ``field`` (``total_s``, ``self_s``, ``build_s``,
``max_s``) of every span name matching ``pattern``; ``per: count`` divides
by the matching spans' calls; ``last`` keeps only the newest N spans of
each name (from the recorder's ring: durations only, so ``total_s``), which
is how a metric of the window leaves set-up's calls out.  A program with
no recorder, or no span that matches: nothing to read (``None``)."""

from __future__ import annotations

import re

from .common import resolve


def snapshot() -> dict:
    """The program's snapshot, or ``{}`` where it has no recorder."""
    try:
        from lightgbm_tpu.utils import profiling
    except ImportError:
        return {}
    take = getattr(profiling, "snapshot", None)
    return take() if callable(take) else {}


def newest(ring: list, last: int) -> dict:
    """Per name, the aggregate of the newest ``last`` spans of the ring."""
    out = {}
    for rec in reversed(ring):
        agg = out.setdefault(rec["name"], {"count": 0, "total_s": 0.0})
        if agg["count"] < last:
            agg["count"] += 1
            agg["total_s"] += rec["end"] - rec["start"]
    return out


def read(ctx: dict, spec: dict):
    snap = snapshot()
    spans = snap.get("spans", {})
    if "last" in spec:
        last = resolve(spec["last"], ctx)
        if not last:
            return None
        spans = newest(snap.get("ring", []), int(last))
    total, count = 0.0, 0
    for term in spec["terms"]:
        rx = re.compile(term["pattern"])
        for name, agg in spans.items():
            if rx.search(name) and term["field"] in agg:
                total += agg[term["field"]]
                count += agg["count"]
    if count <= 0:
        return None
    if spec.get("per") == "count":
        total /= count
    return spec.get("scale", 1) * total
