"""Shares of the rows a wave pass streamed that its splits needed, from the
PROGRAM's pass log: ``lightgbm_tpu.utils.profiling.snapshot()["arrays"]
["train.passes"]``, the arrays ``Booster.update_many`` kept unread, one a
segment, each ``[rounds, 5, passes]`` with passes on the minor axis and the
five columns of ``lightgbm_tpu.models.tree._PASS``: role (0 narrow, 1 full
width), splits, rows streamed, rows of the leaves split (what the partition
routes), rows of their smaller children (what the histogram counts).

    {"reader": "pass_log", "role": 1, "rows": "parents",
     "last": "counter:window_rounds"}

100 x the sum of ``rows`` over the sum of rows streamed, over the passes of
``role`` that ran (splits > 0) in the newest ``last`` rounds.  A program
that logs no passes (the commits before PR 39, a path that drops the log),
no such counter, or no pass of that role: nothing to read (``None``)."""

from __future__ import annotations

import numpy as np

from .common import resolve
from .program_span import snapshot

ROLE, SPLITS, STREAMED = 0, 1, 2
ROWS = {"parents": 3, "direct": 4}


def newest_rounds(arrays: list, last: int):
    """``[rounds, 5, passes]``: the newest ``last`` rounds of the log, from
    the newest array back while the arrays have one shape (one booster)."""
    taken, held = [], 0
    for a in reversed(arrays):
        a = np.asarray(a)
        if taken and a.shape[1:] != taken[0].shape[1:]:
            break
        taken.insert(0, a)
        held += a.shape[0]
        if held >= last:
            break
    return np.concatenate(taken)[-last:] if taken else None


def read(ctx: dict, spec: dict):
    last = resolve(spec["last"], ctx)
    arrays = snapshot().get("arrays", {}).get("train.passes")
    if not last or not arrays:
        return None
    rounds = newest_rounds(arrays, int(last))
    if rounds is None:
        return None
    take = (rounds[:, SPLITS] > 0) & (rounds[:, ROLE] == spec["role"])
    streamed = rounds[:, STREAMED][take].astype(np.float64).sum()
    if streamed <= 0:
        return None
    used = rounds[:, ROWS[spec["rows"]]][take].astype(np.float64).sum()
    return 100.0 * used / streamed
