"""A named kernel's share of its roofline, in percent: ``trace_roofline``
with the work counted from what the PROGRAM says it ran.

    {"reader": "named_roofline", "calls": [
        {"pattern": "^%lgbtpu_hist_wave", "work": "hist_onehot_call",
         "shapes": {"segments": "program:train.wave_width",
                    "rows": "program:train.rows_padded", ...}}]}

``"program:<fact>"`` in ``shapes`` is a fact the program noted
(``lightgbm_tpu.utils.profiling.note``): the wave width, the rows and the
precision of the pass that ran, not a constant kept here, so a PR that
changes them does not leave the metric stale.  A fact the program did not
note, or a value the work function has no peak for: that call has nothing
to read."""

from __future__ import annotations

from . import trace_roofline
from .program_span import snapshot


def read(ctx: dict, spec: dict):
    facts = snapshot().get("facts", {})
    calls = []
    for call in spec["calls"]:
        shapes = {k: (facts.get(v.split(":", 1)[1])
                      if isinstance(v, str) and v.startswith("program:")
                      else v) for k, v in call["shapes"].items()}
        if all(v is not None for v in shapes.values()):
            calls.append(dict(call, shapes=shapes))
    if not calls:
        return None
    try:
        return trace_roofline.read(ctx, dict(spec, calls=calls))
    except KeyError:
        return None
