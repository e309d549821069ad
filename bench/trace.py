"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Its device planes (``/device:TPU:<n>``) have an ``XLA Modules`` line (one
event per program run) and an ``XLA Ops`` line (one event per HLO
instruction; a ``while`` event spans the events of its body). The host
plane (``/host:CPU``) carries the benchmark's own spans, all named
``bench.*`` (``harness.Span``), on the same clock.

* busy: the union of the device's module and op intervals inside the
  ``bench.window`` span, averaged over the devices;
* per-op self time: an op's duration less the time its nested ops cover;
  a Pallas kernel is an op whose HLO is a ``custom-call``;
* idle gaps: the stretches of the window with no device interval, each
  named by the innermost ``bench.*`` host span open at its middle.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, NamedTuple, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


class Event(NamedTuple):
    start: int  # ns
    end: int  # ns
    name: str


class Trace(NamedTuple):
    """Events of one trace: per device, its modules and ops; host spans."""

    modules: Dict[str, List[Event]]
    ops: Dict[str, List[Event]]
    spans: List[Event]


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> Trace:
    """Read the events this reduction needs from an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    modules: Dict[str, List[Event]] = {}
    ops: Dict[str, List[Event]] = {}
    spans: List[Event] = []
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
                spans.extend(
                    Event(int(e.start_ns), int(e.start_ns + e.duration_ns),
                          e.name) for e in line.events
                    if e.name.startswith(SPAN_PREFIX))
    return Trace(modules, ops, spans)


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(ev: List[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(e.start, lo), min(e.end, hi)) for e in ev
            if e.end > lo and e.start < hi]


def op_name(name: str) -> str:
    """``%fusion.2 = s32[...] fusion(...)`` -> ``fusion.2``."""
    return name.split(" = ", 1)[0].lstrip("%")


def is_kernel(name: str) -> bool:
    """A Pallas kernel: an HLO custom call."""
    return " custom-call(" in name


def self_times(ops: List[Event], lo: int, hi: int) -> Dict[str, float]:
    """Seconds of each op inside [lo, hi), less the time its nested ops
    cover, keyed by the op's full HLO text."""
    out: Dict[str, float] = {}
    stack: List[List] = []  # [end, name, child_ns]

    def close(item):
        end, name, start, child = item
        s, e = max(start, lo), min(end, hi)
        if e > s:
            out[name] = out.get(name, 0.0) + (e - s - child) / 1e9

    for ev in sorted(ops, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0] <= ev.start:
            close(stack.pop())
        if stack:
            s, e = max(ev.start, lo), min(ev.end, hi)
            stack[-1][3] += max(0, e - s)
        stack.append([ev.end, ev.name, ev.start, 0])
    while stack:
        close(stack.pop())
    return out


class Reduction(NamedTuple):
    window_s: float
    busy_s: float  # averaged over devices
    op_s: Dict[str, float]  # self seconds per op, summed over devices
    gaps: List[Tuple[str, float]]  # (host span, seconds), longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_s(self) -> float:
        return sum(v for k, v in self.op_s.items() if is_kernel(k))

    def non_kernel_s(self) -> float:
        return sum(v for k, v in self.op_s.items() if not is_kernel(k))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[op_name(k), v] for k, v in ops],
                "idle_gaps": [[n, s] for n, s in self.gaps[:top]]}


def reduce(trace: Trace) -> Reduction:
    windows = [s for s in trace.spans if s.name == WINDOW_SPAN]
    if not windows:
        raise ValueError("trace has no bench.window span")
    win = max(windows, key=lambda s: s.end - s.start)
    lo, hi = win.start, win.end
    devices = sorted(set(trace.modules) | set(trace.ops))
    if not devices:
        raise ValueError("trace has no device plane")
    busy_total = 0.0
    op_s: Dict[str, float] = {}
    first_busy: List[Tuple[int, int]] = []
    for dev in devices:
        busy = _union(_clip(trace.modules.get(dev, []), lo, hi)
                      + _clip(trace.ops.get(dev, []), lo, hi))
        busy_total += sum(e - s for s, e in busy) / 1e9
        if not first_busy:
            first_busy = busy
        for k, v in self_times(trace.ops.get(dev, []), lo, hi).items():
            op_s[k] = op_s.get(k, 0.0) + v
    gaps = []
    cursor = lo
    for s, e in first_busy + [(hi, hi)]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    named = []
    inner = [sp for sp in trace.spans if sp is not win]
    for s, e in gaps:
        mid = (s + e) // 2
        open_ = [sp for sp in inner if sp.start <= mid < sp.end]
        name = (min(open_, key=lambda sp: sp.end - sp.start).name
                if open_ else WINDOW_SPAN)
        named.append((name, (e - s) / 1e9))
    named.sort(key=lambda g: -g[1])
    return Reduction(window_s=(hi - lo) / 1e9,
                     busy_s=busy_total / len(devices), op_s=op_s, gaps=named)
