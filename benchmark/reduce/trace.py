"""From a profiler trace to numbers: one reduction for every cell.

``reduce_dir`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote (with
``jax.profiler.ProfileData``, nothing but JAX) into plain interval lists;
everything after that is arithmetic on ``(name, start, end)`` tuples, which
the CPU tests drive with hand-built traces:

* busy time of a device: the union of the intervals in which an operation
  ran on it; averaged over the chips used;
* the window: the harness's ``bench.window`` host span where the trace has
  it, else first device start to last device end;
* per-pattern sums: device time of the events whose name matches a regular
  expression kept in the metric's data file;
* idle gaps: the holes of the busy union inside the window, each named by
  the harness's or program's host span that covers most of it.

Which planes and lines hold device operations and host spans is data:
``trace_layout.json`` beside this file, one entry per platform (the
``cpu`` entry lets the CPU tests drive the traced path; its numbers are
never printed under a device's name by the command, which refuses to run
without a TPU).
"""

from __future__ import annotations

import glob
import json
import os
import re
from collections import defaultdict

HERE = os.path.dirname(os.path.abspath(__file__))
NS = 1e-9


def union_length(intervals) -> float:
    """Total length covered by ``[(start, end), ...]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_of(intervals, start: float, end: float):
    """The holes ``[(s, e), ...]`` the union of ``intervals`` leaves in
    ``[start, end]``."""
    out, at = [], start
    for s, e in sorted(intervals):
        if e <= start:
            continue
        if s >= end:
            break
        if s > at:
            out.append((at, min(s, end)))
        at = max(at, e)
    if at < end:
        out.append((at, end))
    return out


class Trace:
    """``device_ops``: ``{device: [(name, start_s, end_s), ...]}`` of the
    operations that ran on each chip; ``host_spans``: ``[(name, start_s,
    end_s), ...]`` of named host work on the same clock."""

    def __init__(self, device_ops: dict, host_spans: list, chips: int,
                 window_span: str = "bench.window"):
        self.device_ops, self.host_spans = device_ops, host_spans
        self.chips = max(int(chips), 1)
        win = [(s, e) for n, s, e in host_spans if n == window_span]
        every = [(s, e) for ops in device_ops.values() for _, s, e in ops]
        if win:
            self.start = min(s for s, _ in win)
            self.end = max(e for _, e in win)
        elif every:
            self.start = min(s for s, _ in every)
            self.end = max(e for _, e in every)
        else:
            self.start = self.end = 0.0
        self.window_s = self.end - self.start
        self.busy_s = sum(
            union_length([(s, e) for _, s, e in
                          clip_named(ops, self.start, self.end)])
            for ops in device_ops.values()) / self.chips

    def idle_share(self):
        if self.window_s <= 0 or not self.device_ops:
            return None
        return 1.0 - self.busy_s / self.window_s

    def matching(self, pattern: str):
        """``(seconds, calls)`` of device events whose name matches
        ``pattern``, averaged over the chips, inside the window."""
        rx = re.compile(pattern)
        seconds, calls = 0.0, 0
        for ops in self.device_ops.values():
            for name, s, e in clip_named(ops, self.start, self.end):
                if rx.search(name):
                    seconds += e - s
                    calls += 1
        return seconds / self.chips, calls / self.chips

    def top_ops(self, n: int = 10):
        """Device operations by SELF time (an operation that holds others,
        a ``while`` or a ``call``, is charged only what its children leave),
        under short labels, averaged over the chips."""
        total = defaultdict(float)
        for ops in self.device_ops.values():
            for name, seconds in self_times(
                    clip_named(ops, self.start, self.end)):
                total[short_label(name)] += seconds
        rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[name, seconds / self.chips] for name, seconds in rows]

    def idle_gaps(self, n: int = 10):
        """Idle seconds of the fullest-traced device, summed by the host
        span that covers most of each gap."""
        if not self.device_ops:
            return []
        ops = max(self.device_ops.values(), key=len)
        total = defaultdict(float)
        spans = [sp for sp in self.host_spans if sp[0] != "bench.window"]
        for gs, ge in gaps_of([(s, e) for _, s, e in ops],
                              self.start, self.end):
            best, cover = "host:unnamed", 0.0
            for name, s, e in spans:
                c = min(e, ge) - max(s, gs)
                if c > cover:
                    best, cover = name, c
            total[best] += ge - gs
        rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[name, seconds] for name, seconds in rows]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def clip_named(ops, start: float, end: float):
    return [(n, max(s, start), min(e, end)) for n, s, e in ops
            if e > start and s < end]


def self_times(ops):
    """``[(name, self_seconds), ...]``: each event's duration minus what
    the events nested inside it cover.  Events of one line nest properly
    (a child lies inside its parent) or do not overlap."""
    out, stack = [], []          # stack of [name, end, self_seconds]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and s >= stack[-1][1]:
            top = stack.pop()
            out.append((top[0], top[2]))
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    out.extend((name, seconds) for name, _, seconds in stack)
    return out


_HLO = re.compile(r"^(%[^ ]+) = (.*?)\s?([a-z][a-z\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_label(name: str) -> str:
    """An HLO instruction's text cut to ``opcode[:target] %name shape``;
    any other event name is kept (to 80 characters)."""
    m = _HLO.match(name)
    if not m:
        return name[:80]
    inst, shape, opcode = m.groups()
    target = _TARGET.search(name)
    if target:
        opcode += ":" + target.group(1)
    shape = re.sub(r"\{[^}]*\}", "", shape)
    return f"{opcode} {inst} {shape}"[:80]


def layout() -> dict:
    with open(os.path.join(HERE, "trace_layout.json")) as f:
        return json.load(f)


def read_xplane(path: str, lay: dict = None):
    """``(device_ops, host_spans)`` of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    lay = lay or layout()["tpu"]
    dev_rx = re.compile(lay["device_plane"])
    op_lines = [re.compile(p) for p in lay["device_op_lines"]]
    host_rx = re.compile(lay["host_plane"])
    span_rx = re.compile(lay["host_span"])
    device_ops, host_spans = {}, []
    data = ProfileData.from_file(path)
    for plane in data.planes:
        if dev_rx.search(plane.name):
            ops = []
            for line in plane.lines:
                if any(p.search(line.name) for p in op_lines):
                    for ev in line.events:
                        s = ev.start_ns * NS
                        ops.append((ev.name, s, s + ev.duration_ns * NS))
            device_ops[plane.name] = ops
        if host_rx.search(plane.name):
            for line in plane.lines:
                for ev in line.events:
                    if span_rx.search(ev.name):
                        s = ev.start_ns * NS
                        host_spans.append(
                            (ev.name, s, s + ev.duration_ns * NS))
    return device_ops, host_spans


def reduce_dir(trace_dir: str, chips: int, platform: str = "tpu") -> Trace:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise SystemExit(f"benchmark: the profiler wrote no trace under "
                         f"{trace_dir}")
    device_ops, host_spans = read_xplane(files[-1], layout()[platform])
    if not any(device_ops.values()):
        raise SystemExit("benchmark: the trace holds no device operation: "
                         "nothing ran on the device in the traced window")
    return Trace(device_ops, host_spans, chips)
