"""The program's own spans and scopes, read beside the device's events.

The program names what it does in two ways that a profiler trace can
show (``repro.observe.span``, DESIGN.md §8.5):

* host spans named ``zen.*`` on the profiler's clock: the serving
  engine's ``zen.engine.tick`` and, inside it, ``zen.engine.admit`` and
  per bucket ``zen.engine.keys``, ``zen.engine.sweep`` and
  ``zen.engine.finish`` (the ticker thread); ``zen.train.step`` and
  ``zen.train.compile`` (the caller's thread);
* named scopes ``zen.*`` in the compiled step's HLO metadata: an
  instruction's ``op_name`` holds the scopes it was traced under
  (``zen.sweep``, ``zen.relayout``, ``zen.delta_counts``, ``zen.update``).

``load`` reads the ``zen.*`` spans of every host thread, the
``bench.window`` span and the device's ``XLA Modules`` and ``XLA Ops``
events from the same ``.xplane.pb`` as ``bench/trace.py``. ``reduce``
returns, inside the window:

* the ``zen.engine.tick`` spans that start in it, and their mean length;
* device-idle seconds (no module or op running on the first device, as
  ``trace.reduce`` names its gaps) while a tick is open, and per innermost
  ``zen.*`` span open at the time (the shortest; ``bench.window`` when
  none is);
* device program launches (module events) that start inside such a tick,
  and those that start while none is open.

``scope_map`` maps each instruction of an executable's ``as_text()`` to the
innermost ``zen.*`` scope of its ``op_name`` (or, for an instruction a
compiler pass made without one, of its neighbours); ``seconds_by_scope``
sums a ``trace.Reduction``'s per-op self time by it.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, NamedTuple

from bench.trace import WINDOW_SPAN, Event, _clip, _union, op_name

SPAN_PREFIX = "zen."
TICK_SPAN = "zen.engine.tick"
STEP_SCOPE = "zen.sweep"  # the outermost scope of a scoped train step
UNATTRIBUTED = "unattributed"

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")
_SCOPE = re.compile(r"zen\.\w+")


class ProgramTrace(NamedTuple):
    """The events of one trace that the program's spans are read with."""

    spans: List[Event]  # zen.* host spans, any thread
    windows: List[Event]  # bench.window spans
    modules: Dict[str, List[Event]]  # per device: XLA Modules
    ops: Dict[str, List[Event]]  # per device: XLA Ops


def load(path: str) -> ProgramTrace:
    """Read the ``zen.*`` and ``bench.window`` host spans and the device
    events of an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans: List[Event] = []
    windows: List[Event] = []
    modules: Dict[str, List[Event]] = {}
    ops: Dict[str, List[Event]] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            for line in plane.lines:
                if line.name not in ("XLA Modules", "XLA Ops"):
                    continue
                into = modules if line.name == "XLA Modules" else ops
                into.setdefault(plane.name, []).extend(
                    Event(int(e.start_ns), int(e.start_ns + e.duration_ns),
                          e.name) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    ev = Event(int(e.start_ns),
                               int(e.start_ns + e.duration_ns), e.name)
                    if e.name == WINDOW_SPAN:
                        windows.append(ev)
                    elif e.name.startswith(SPAN_PREFIX):
                        spans.append(ev)
    return ProgramTrace(spans, windows, modules, ops)


class ProgramReduction(NamedTuple):
    window_s: float
    idle_s: float  # device idle in the window
    ticks: int  # zen.engine.tick spans that start in the window
    tick_mean_s: float  # their mean length (0 without ticks)
    idle_in_tick_s: float  # device idle while one of them is open
    idle_by_span: Dict[str, float]  # innermost open zen.* span -> idle s
    launches_in_tick: int  # module events that start inside those ticks
    launches_outside: int  # module events in the window outside them

    @property
    def idle_in_tick_share(self) -> float:
        return self.idle_in_tick_s / self.window_s

    @property
    def launches_per_tick(self) -> float:
        return self.launches_in_tick / self.ticks if self.ticks else 0.0

    def idle_on_spans_s(self) -> float:
        """Device-idle seconds while some ``zen.*`` span was open."""
        return sum(v for k, v in self.idle_by_span.items()
                   if k != WINDOW_SPAN)


def _inside(t: int, starts: List[int], ends: List[int]) -> bool:
    """Whether ``t`` lies in one of the disjoint sorted intervals."""
    i = bisect.bisect_right(starts, t) - 1
    return i >= 0 and t < ends[i]


def reduce(trace: ProgramTrace) -> ProgramReduction:
    if not trace.windows:
        raise ValueError("trace has no bench.window span")
    win = max(trace.windows, key=lambda s: s.end - s.start)
    lo, hi = win.start, win.end
    devices = sorted(set(trace.modules) | set(trace.ops))
    busy = (_union(_clip(trace.modules.get(devices[0], []), lo, hi)
                   + _clip(trace.ops.get(devices[0], []), lo, hi))
            if devices else [])
    idle = []
    cursor = lo
    for s, e in busy + [(hi, hi)]:
        if s > cursor:
            idle.append((cursor, s))
        cursor = max(cursor, e)

    spans = [sp for sp in trace.spans if sp.end > lo and sp.start < hi]
    ticks = [sp for sp in spans if sp.name == TICK_SPAN and sp.start >= lo]
    tick_union = _union([(sp.start, sp.end) for sp in ticks])
    tick_starts = [s for s, _ in tick_union]
    tick_ends = [e for _, e in tick_union]

    # sweep the window's boundaries: between two of them the set of open
    # spans and whether the device idles stay the same
    IDLE, SPAN = 0, 1
    marks = []
    for s, e in idle:
        marks += [(s, 1, IDLE, -1), (e, -1, IDLE, -1)]
    for i, sp in enumerate(spans):
        marks += [(max(sp.start, lo), 1, SPAN, i),
                  (min(sp.end, hi), -1, SPAN, i)]
    marks.sort()
    by_span: Dict[str, float] = {}
    in_tick_ns = 0
    open_spans: set = set()
    idling = 0
    prev = lo
    for t, step, kind, i in marks:
        if idling and t > prev:
            dt = t - prev
            if open_spans:
                inner = min(open_spans,
                            key=lambda j: spans[j].end - spans[j].start)
                name = spans[inner].name
            else:
                name = WINDOW_SPAN
            by_span[name] = by_span.get(name, 0) + dt
            if _inside(prev, tick_starts, tick_ends):
                in_tick_ns += dt
        prev = t
        if kind == IDLE:
            idling += step
        elif step > 0:
            open_spans.add(i)
        else:
            open_spans.discard(i)

    launches = [m.start for dev in devices for m in trace.modules.get(dev, [])
                if lo <= m.start < hi]
    in_tick = sum(_inside(t, tick_starts, tick_ends) for t in launches)
    return ProgramReduction(
        window_s=(hi - lo) / 1e9,
        idle_s=sum(e - s for s, e in idle) / 1e9,
        ticks=len(ticks),
        tick_mean_s=(sum(sp.end - sp.start for sp in ticks) / len(ticks)
                     / 1e9 if ticks else 0.0),
        idle_in_tick_s=in_tick_ns / 1e9,
        idle_by_span={k: v / 1e9 for k, v in by_span.items()},
        launches_in_tick=in_tick,
        launches_outside=len(launches) - in_tick,
    )


def scope_map(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> the innermost ``zen.*`` scope of its
    ``op_name`` metadata.

    Compiler passes make instructions without metadata (a scatter
    rewritten into a fusion, the sort XLA puts in front of a scatter, a
    layout copy). Such an instruction takes, in this order: the scope
    most common among the instructions of the computations it calls (a
    fusion's body); then, repeated until nothing changes, the scope most
    common among its users (what it was made for), else among its
    operands, else, inside a computation that a scoped instruction calls
    (a loop body), that instruction's scope."""
    comps: Dict[str, List[str]] = {}  # computation -> its instructions
    lines: Dict[str, str] = {}  # instruction -> its text
    comp = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            comps[comp] = []
            continue
        m = _INSTR.match(line)
        if m and comp is not None:
            comps[comp].append(m.group(1))
            lines[m.group(1)] = line.split(" = ", 1)[1]
    own: Dict[str, str] = {}
    operands: Dict[str, List[str]] = {}
    callees: Dict[str, List[str]] = {}
    for name, text in lines.items():
        meta = _OP_NAME.search(text)
        scopes = _SCOPE.findall(meta.group(1)) if meta else []
        if scopes:
            own[name] = scopes[-1]
        refs = _REF.findall(_OP_NAME.sub("", text))
        operands[name] = [r for r in refs if r in lines]
        callees[name] = [r for r in refs if r in comps]
    out = dict(own)
    for name in lines:
        if name not in out:
            inner = [own[i] for c in callees[name] for i in comps[c]
                     if i in own]
            if inner:
                out[name] = _most_common(inner)
    users: Dict[str, List[str]] = {}
    for name, ops in operands.items():
        for op in ops:
            users.setdefault(op, []).append(name)
    caller: Dict[str, str] = {}  # instruction -> the one calling its comp
    for name in lines:
        for c in callees[name]:
            for i in comps[c]:
                caller.setdefault(i, name)
    changed = True
    while changed:
        changed = False
        for name in lines:
            if name in out:
                continue
            near = ([out[u] for u in users.get(name, []) if u in out]
                    or [out[o] for o in operands[name] if o in out])
            scope = (_most_common(near) if near
                     else out.get(caller.get(name, "")))
            if scope:
                out[name] = scope
                changed = True
    return out


def _most_common(scopes: List[str]) -> str:
    return max(sorted(set(scopes)), key=scopes.count)


def seconds_by_scope(op_s: Dict[str, float],
                     scopes: Dict[str, str]) -> Dict[str, float]:
    """Per-op self seconds (``trace.Reduction.op_s``, keyed by HLO text)
    summed by scope; ops in no scope under ``unattributed``."""
    out: Dict[str, float] = {}
    for text, sec in op_s.items():
        key = scopes.get(op_name(text), UNATTRIBUTED)
        out[key] = out.get(key, 0.0) + sec
    return out


def step_ms_by_scope(ctx):
    """Device milliseconds per step and chip of each scope of a train
    job's compiled step, from its reader context (the reduced trace,
    ``scopes``, ``steps``, ``chips``). None where the step has no
    ``zen.sweep`` scope (the mesh step): the kernel wrappers'
    ``zen.relayout`` would be its only scope, and ``scope_map`` would
    spread it over every instruction."""
    scopes = ctx.get("scopes")
    if not scopes or not ctx["steps"] or STEP_SCOPE not in scopes.values():
        return None
    return {scope: sec / ctx["steps"] * 1e3 / ctx["chips"] for scope, sec
            in seconds_by_scope(ctx["trace"].op_s, scopes).items()}
