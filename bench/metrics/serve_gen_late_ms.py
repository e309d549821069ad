"""95th percentile of how late the load generator submitted each request
after its due time: a starved generator must not read as a fast server."""
from bench.harness import percentile

UNIT, LAYER, MOVES, SOURCE = "ms", "load generator", "serve_p95_ms", "host_clock"


def read(ctx):
    return percentile(ctx["late_ms"], 95.0) if ctx["late_ms"] else None
