"""Device milliseconds per training step and chip in the sampler's Pallas
kernels (every custom call of the step), from the trace: the kernels' time
summed over the chips, divided by their number."""
UNIT, LAYER, MOVES, SOURCE = "ms", "kernels", "train_tokens_per_s", "device_trace"


def read(ctx):
    kernel = ctx["trace"].kernel_s()
    if not kernel or not ctx["steps"]:
        return None
    return kernel / ctx["steps"] * 1e3 / ctx["chips"]
