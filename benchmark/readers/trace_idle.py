"""Idle share of the device in the traced window, in percent: 1 - the
union of the device operations' intervals over the window."""

from __future__ import annotations


def read(ctx: dict, spec: dict):
    trace = ctx["trace"]
    share = None if trace is None else trace.idle_share()
    return None if share is None else 100.0 * share
