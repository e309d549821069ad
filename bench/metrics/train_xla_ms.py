"""Device milliseconds per training step and chip outside the kernels: the
count update's scatter-adds, the re-layout copies of the count matrices
and the rest of the step's XLA ops (self time, from the trace, summed over
the chips and divided by their number)."""
UNIT, LAYER, MOVES, SOURCE = ("ms", "count update and re-layout",
                              "train_tokens_per_s", "device_trace")


def read(ctx):
    if not ctx["steps"]:
        return None
    return ctx["trace"].non_kernel_s() / ctx["steps"] * 1e3 / ctx["chips"]
