"""Device microseconds of the serving sampler's Pallas kernel per document
finished inside the traced window (the measured window; the drain
after it is not traced)."""
UNIT, LAYER, MOVES, SOURCE = "us", "kernels", "serve_docs_per_s", "device_trace"


def read(ctx):
    kernel = ctx["trace"].kernel_s()
    if not kernel or not ctx["docs_done"]:
        return None
    return kernel / ctx["docs_done"] * 1e6
