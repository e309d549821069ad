"""What every cell shares: the run context, host spans, the window, the
checks and the result line."""
from __future__ import annotations

import contextlib
import json
import math
import os
import shutil
import sys
import time
from typing import Dict, List, NamedTuple, Optional


class Check(NamedTuple):
    """One number compared for ``correct``: passes when ``value <= limit``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


class Span(contextlib.ContextDecorator):
    """A host span in the profiler's trace (``jax.profiler.TraceAnnotation``)
    whose seconds optionally go into ``into[key]``."""

    def __init__(self, name: str, into: Optional[Dict] = None,
                 key: Optional[str] = None):
        self.name, self.into, self.key = name, into, key

    def __enter__(self):
        import jax

        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        if self.into is not None:
            self.into[self.key] = self.into.get(self.key, 0.0) + dt
        return False


def device_peak_bytes() -> int:
    """Peak bytes in use on the fullest device of this process."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100) by linear interpolation between
    closest ranks, the benchmark's own arithmetic."""
    xs = sorted(values)
    if not xs:
        return float("nan")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


class Window:
    """The measured window: host-clock seconds, and with ``--trace 1`` the
    profiler's trace of exactly that stretch."""

    def __init__(self, trace_dir: Optional[str]):
        self.trace_dir = trace_dir
        self.seconds = 0.0

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    def __enter__(self):
        import jax

        if self.trace_dir:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # host spans are bench.* only
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation("bench.window")
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        import jax

        self.seconds = time.perf_counter() - self.t0
        self._ann.__exit__(*exc)
        if self.trace_dir:
            jax.profiler.stop_trace()
        return False


class Context:
    """What a job needs from the harness for one run."""

    def __init__(self, root: str, workload: dict, cfg: dict, traffic: dict,
                 limits: dict, seed: int, seconds: float, trace: bool):
        self.root = root
        self.workload = workload
        self.cfg = cfg
        self.traffic = traffic
        self.limits = limits
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.setup_split: Dict[str, float] = {}
        self.trace_dir = (os.path.join(root, ".bench_trace", workload["name"])
                          if trace else None)

    def window(self) -> Window:
        self.win = Window(self.trace_dir)
        return self.win

    def log(self, **fields) -> None:
        print(json.dumps(fields, default=float), flush=True)


def print_result(result: dict, checks: List[Check]) -> None:
    """The last lines: the checks on stderr, then the result line."""
    for c in checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    line = dict(result)
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in checks}
    print(json.dumps(line), flush=True)
