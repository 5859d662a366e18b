"""Shared by the readers: resolving a metric file's ``shapes`` entry."""

from __future__ import annotations


def resolve(value, ctx: dict):
    """``"counter:<key>"``, ``"config:<key>"`` and ``"traffic:<key>"`` name
    a number of the run; anything else is itself.  A missing key is
    ``None``: the reader then has nothing to read."""
    if isinstance(value, str) and ":" in value:
        where, key = value.split(":", 1)
        source = {"counter": ctx["counters"], "config": ctx["config"],
                  "traffic": ctx["traffic"]}.get(where)
        if source is not None:
            return source.get(key)
    return value


def resolve_shapes(shapes: dict, ctx: dict):
    out = {k: resolve(v, ctx) for k, v in shapes.items()}
    return None if any(v is None for v in out.values()) else out
