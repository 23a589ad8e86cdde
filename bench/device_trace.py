"""Reduction of a JAX profiler trace (``*.xplane.pb``) to the numbers the
per-layer metrics read.

- The window is the host span named by the caller (a
  ``jax.profiler.TraceAnnotation``), on the host's clock, which the device
  events share.
- Busy time is the union of the intervals in which an operation ran on a
  chip (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), clipped to
  the window and averaged over the chips.  Idle gaps are the rest of the
  window.
- The breakdown lists the ten device ops that took most time (ops that
  enclose others on the line, such as a loop, are left out: their children
  are listed), named by their HLO instruction, its shape and opcode; and the
  idle
  time grouped by what the host was doing then: the shortest host event
  covering at least half of the gap.  Gaps under ``SHORT_GAP_NS`` (the
  device moving from one op to the next) are summed under one name.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SHORT_GAP_NS = 20_000
SHORT_GAP = "gaps under 20 us"


def merge(intervals):
    """Union of (start, end) intervals as a sorted list of disjoint ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy, lo, hi):
    """The parts of [lo, hi) that ``busy`` (disjoint, sorted) leaves free."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def leaves(events):
    """The (start, end, name) events that enclose no other event."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    out = []
    for i, (s, e, name) in enumerate(events):
        nxt = events[i + 1] if i + 1 < len(events) else None
        if nxt is None or not (nxt[0] < e and nxt[1] <= e):
            out.append((s, e, name))
    return out


def op_label(hlo: str) -> str:
    """``%fusion.3 = (f32[8]{0}, bf16[2,4]{1,0}) fusion(...), ...`` ->
    ``fusion.3 (f32[8], bf16[2,4]) fusion``: name, shape without layouts,
    opcode."""
    name, _, rest = hlo.partition(" = ")
    prev = None
    while prev != rest:
        prev, rest = rest, re.sub(r"\{[^{}]*\}", "", rest)
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        shape, rest = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
    opcode = rest.split("(", 1)[0]
    return f"{name.lstrip('%')} {shape} {opcode}".strip()[:200]


def xplane_file(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one xplane file under {trace_dir}, "
                           f"found {files}")
    return files[0]


def reduce(trace_dir_or_file: str, window_span: str) -> dict:
    from jax.profiler import ProfileData
    path = trace_dir_or_file
    if os.path.isdir(path):
        path = xplane_file(path)
    pd = ProfileData.from_file(path)
    host_events, device_lines = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            device_lines += [ln for ln in plane.lines if ln.name == OPS_LINE]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host_events += [(e.name, e.start_ns, e.end_ns)
                                for e in ln.events]
    host_events.sort(key=lambda x: x[1])
    spans = [(s, e) for n, s, e in host_events if n == window_span]
    if not spans:
        raise RuntimeError(f"no host span {window_span!r} in {path}")
    lo, hi = spans[0]
    if not device_lines:
        raise RuntimeError(f"no {OPS_LINE!r} line on a {DEVICE_PREFIX} plane "
                           f"in {path}")

    busy_total = 0.0
    op_ns = collections.Counter()
    idle = collections.Counter()
    for line in device_lines:
        evs = [(ev.start_ns, ev.end_ns, ev.name) for ev in line.events
               if ev.end_ns > lo and ev.start_ns < hi]
        for s, e, name in leaves(evs):
            op_ns[op_label(name)] += min(e, hi) - max(s, lo)
        busy = merge(clip([(s, e) for s, e, _ in evs], lo, hi))
        busy_total += sum(e - s for s, e in busy)
        starts = [x[1] for x in host_events]
        for gs, ge in gaps(busy, lo, hi):
            label = (SHORT_GAP if ge - gs < SHORT_GAP_NS else
                     _host_label(host_events, starts, gs, ge, window_span))
            idle[label] += ge - gs
    n = len(device_lines)
    to_s = lambda ns: ns / 1e9
    return {
        "window_s": to_s(hi - lo),
        "busy_s": to_s(busy_total / n),
        "breakdown": {
            "device_ops": [[k, to_s(v / n)] for k, v in op_ns.most_common(10)],
            "idle_gaps": [[k, to_s(v / n)] for k, v in idle.most_common(10)],
        },
    }


def _host_label(host_events, starts, gs, ge, window_span, look_back=500):
    """The shortest host event that covers at least half of [gs, ge); host
    events are sorted by start, and only the ``look_back`` that start last
    before ``ge`` are looked at."""
    need = (ge - gs) / 2
    best = None
    i = bisect.bisect_left(starts, ge)
    for name, s, e in host_events[max(0, i - look_back):i]:
        if name == window_span or min(e, ge) - max(s, gs) < need:
            continue
        if best is None or e - s < best[1]:
            best = (name, e - s)
    return best[0] if best else "no host event"
