"""Share of the traced training window in which no op ran on the device."""
UNIT, LAYER, MOVES, SOURCE = "%", "device", "train_tokens_per_s", "device_trace"


def read(ctx):
    return 100.0 * ctx["trace"].idle_share
