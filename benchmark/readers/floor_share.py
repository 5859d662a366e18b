"""The whole step's share of a floor that no implementation can beat, in
percent: units of work done in the window (a counter) x the least time
of one unit (from its shapes, against the chip's peaks) over the window's
time.

    {"reader": "floor_share", "count": "counter:window_rounds",
     "work": "hbm_floor_round", "shapes": {...}}
"""

from __future__ import annotations

from ..reduce import work
from .common import resolve, resolve_shapes


def read(ctx: dict, spec: dict):
    peaks = ctx["peaks"]
    count = resolve(spec["count"], ctx)
    shapes = resolve_shapes(spec["shapes"], ctx)
    window_s = ctx["window"]["window_s"]
    if peaks is None or not count or shapes is None or window_s <= 0:
        return None
    least, _ = work.least_seconds(getattr(work, spec["work"])(shapes), peaks)
    return 100.0 * count * least / window_s
