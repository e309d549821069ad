"""Share of the traced serving window in which no op ran on the device."""
UNIT, LAYER, MOVES, SOURCE = "%", "device", "serve_docs_per_s", "device_trace"


def read(ctx):
    return 100.0 * ctx["trace"].idle_share
