"""The predictor's error and query cost split by what the program does, from
a profiler trace.

Device side.  Each leaf device op of a traced window (the ``XLA Ops`` line,
as ``device_trace`` reads it) is an HLO instruction of the window's
program, named at the head of the event (``%fusion.252 = ...``).  The
program's optimized HLO text (``compiled.as_text()``) says what each
instruction is, and ``instruction_kinds`` sorts it into the predictor's
three op families:

- **attention** if its ``op_name`` metadata has the model's ``attention``
  name scope (``models/attention.SCOPE``) as a path component;
- otherwise **matmul** if it is a ``dot`` or ``convolution``, or a fusion
  whose body (nested fusions included) holds one;
- otherwise **memory**.

``kind_times`` sums each op's own time per family inside the window: all of
a leaf op's, and the part of an enclosing op's (a loop's) that no op
nested in it covers, so the families partition the busy time.  Ops of
other modules, and instructions the text does not name, are ``unmapped``.

Query side.  ``LatencyService`` opens a span ``latency.<endpoint>`` around
each answer, and ``opgraph._snippet_features`` a span
``predict.snippet_compile`` around each compile of a memory snippet.
``query_spans`` reads them inside the window span of the query phase.

``split.py`` runs a cell with both phases traced and prints the metrics
these give.
"""
from __future__ import annotations

import bisect
import collections
import os
import re

import device_trace as dt

from repro.models.attention import SCOPE

KINDS = ("matmul", "attention", "memory")
ANSWER_PREFIX = "latency."
COMPILE_SPAN = "predict.snippet_compile"
MODULES_LINE = "XLA Modules"

_INSTR = re.compile(r"^\s+(?:ROOT )?%?([^\s=]+) = (.*)$")
_HEAD = re.compile(r"^(ENTRY )?%?([^\s(]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([^\s,}]+)")
_MODULE = re.compile(r"^HloModule ([^\s,]+)")


def _opcode(rest: str) -> str:
    """The opcode of an instruction's right-hand side: the word after its
    shape (a tuple shape is one balanced group of parentheses)."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        rest = rest[i + 1:].lstrip()
    else:
        rest = rest.partition(" ")[2]
    return rest.split("(", 1)[0]


def parse_hlo(text: str) -> tuple[str, dict]:
    """(module name, {computation: [(instruction, opcode, op_name, fused
    computation or None)]}) of an HLO module's text."""
    module = _MODULE.match(text)
    comps, cur = {}, None
    for line in text.splitlines():
        if line and not line[0].isspace():
            head = _HEAD.match(line)
            cur = head.group(2) if head and line.rstrip().endswith("{") else None
            if cur:
                comps[cur] = []
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            name, rest = m.groups()
            op = _opcode(rest)
            op_name = _OP_NAME.search(rest)
            calls = _CALLS.search(rest) if op == "fusion" else None
            comps[cur].append((name, op, op_name.group(1) if op_name else "",
                               calls.group(1) if calls else None))
    return (module.group(1) if module else ""), comps


def in_scope(op_name: str) -> bool:
    return SCOPE in op_name.split("/")


def instruction_kinds(text: str) -> tuple[str, dict]:
    """(module name, {instruction: kind}) for every instruction of the
    module's text but those inside fusion bodies."""
    module, comps = parse_hlo(text)
    bodies = {c for instrs in comps.values() for *_, c in instrs if c}
    has_dot = {}

    def dot_inside(comp: str) -> bool:
        if comp not in has_dot:
            has_dot[comp] = False        # a cycle cannot hold a dot
            has_dot[comp] = any(
                op in ("dot", "convolution") or (c is not None and dot_inside(c))
                for _, op, _, c in comps.get(comp, []))
        return has_dot[comp]

    kinds = {}
    for comp, instrs in comps.items():
        if comp in bodies:
            continue
        for name, op, op_name, calls in instrs:
            if in_scope(op_name):
                kinds[name] = "attention"
            elif op in ("dot", "convolution") or (calls and dot_inside(calls)):
                kinds[name] = "matmul"
            else:
                kinds[name] = "memory"
    return module, kinds


def instruction_of(event_name: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...)`` -> ``fusion.3``."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def _load(path: str):
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = dt.xplane_file(path)
    return ProfileData.from_file(path)


def _host_events(pd) -> list:
    """(name, start_ns, end_ns) of every host event."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.end_ns) for e in line.events]
    return out


def _window(events, span: str) -> tuple[int, int]:
    spans = [(s, e) for n, s, e in events if n == span]
    if not spans:
        raise RuntimeError(f"no host span {span!r} in the trace")
    return spans[0]


def own_times(events, lo, hi):
    """(start, end, name, ns) of each (start, end, name) event, ``ns`` its
    time inside [lo, hi) that no event nested in it covers."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    children = [[] for _ in events]
    open_ = []
    for i, (s, e, _) in enumerate(events):
        while open_ and events[open_[-1]][1] <= s:
            open_.pop()
        if open_ and e <= events[open_[-1]][1]:
            children[open_[-1]].append(i)
        open_.append(i)
    for (s, e, name), kids in zip(events, children):
        inside = dt.clip([(s, e)], lo, hi)
        if inside:
            covered = dt.merge(dt.clip([events[k][:2] for k in kids], lo, hi))
            yield s, e, name, (inside[0][1] - inside[0][0]
                               - sum(b - a for a, b in covered))


def kind_times(trace: str, window_span: str, hlo_text: str) -> dict:
    """Device seconds per family inside ``window_span`` (each op's own time,
    clipped to the window, averaged over the chips), with ``unmapped``
    seconds and the window's busy seconds (``device_trace``'s union)."""
    module, kinds = instruction_kinds(hlo_text)
    pd = _load(trace)
    lo, hi = _window(_host_events(pd), window_span)
    per_kind = collections.Counter({k: 0 for k in KINDS})
    unmapped = collections.Counter()
    busy = 0
    chips = 0
    for plane in pd.planes:
        if not plane.name.startswith(dt.DEVICE_PREFIX):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if dt.OPS_LINE not in lines:
            continue
        chips += 1
        # the intervals in which the window program's module ran
        runs = None
        if MODULES_LINE in lines:
            runs = dt.merge((e.start_ns, e.end_ns)
                            for e in lines[MODULES_LINE].events
                            if e.name.partition("(")[0] == module)
        evs = [(e.start_ns, e.end_ns, e.name) for e in lines[dt.OPS_LINE].events
               if e.end_ns > lo and e.start_ns < hi]
        busy += sum(e - s for s, e in dt.merge(dt.clip(
            [(s, e) for s, e, _ in evs], lo, hi)))
        for s, e, name, ns in own_times(evs, lo, hi):
            kind = kinds.get(instruction_of(name))
            if kind is None or (runs is not None and not _within(runs, s, e)):
                unmapped[instruction_of(name)] += ns
            else:
                per_kind[kind] += ns
    if not chips:
        raise RuntimeError(f"no {dt.OPS_LINE!r} line on a {dt.DEVICE_PREFIX} "
                           f"plane in the trace")
    return {"kind_s": {k: v / chips / 1e9 for k, v in per_kind.items()},
            "unmapped_s": sum(unmapped.values()) / chips / 1e9,
            "unmapped_ops": [[k, v / chips / 1e9]
                             for k, v in unmapped.most_common(5)],
            "busy_s": busy / chips / 1e9}


def _within(runs, s, e) -> bool:
    """Whether [s, e) lies within one of the disjoint sorted ``runs``."""
    i = bisect.bisect_right(runs, (s, float("inf"))) - 1
    return i >= 0 and runs[i][0] <= s and e <= runs[i][1]


def query_spans(trace: str, window_span: str) -> dict:
    """The query phase's own spans inside ``window_span``: how many answers
    and compiles, and the seconds covered by each (unions of intervals;
    compiles counted only where an answer covers them)."""
    events = _host_events(_load(trace))
    lo, hi = _window(events, window_span)
    inside = [(n, s, e) for n, s, e in events if s >= lo and e <= hi]
    answers = dt.merge((s, e) for n, s, e in inside
                       if n.startswith(ANSWER_PREFIX))
    compiles = [(s, e) for n, s, e in inside if n == COMPILE_SPAN]
    covered = []
    for s, e in dt.merge(compiles):
        covered += dt.clip(answers, s, e)
    return {"answers": sum(1 for n, *_ in inside
                           if n.startswith(ANSWER_PREFIX)),
            "answer_s": sum(e - s for s, e in answers) / 1e9,
            "compiles": len(compiles),
            "compile_s": sum(e - s for s, e in covered) / 1e9}


def metrics(kinds: dict, steps: int, predicted: dict, spans: dict,
            queries: int) -> dict:
    """The five numbers: per family, |predicted - measured| ms per step;
    the share of answering time spent compiling (%); compiles per query."""
    out = {f"{k}_err_ms": abs(predicted[k] - kinds["kind_s"][k] / steps) * 1e3
           for k in KINDS}
    out["query_compile_pct"] = 100.0 * spans["compile_s"] / spans["answer_s"]
    out["query_compiles_mean"] = spans["compiles"] / queries
    return out
