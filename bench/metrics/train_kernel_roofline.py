"""Share of its roofline that the training kernel reaches: the least time
of the dense conditional's required work (``bench/work.py``, bytes bound on
v5e) over the kernels' device time, per step."""
from bench import work

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "train_tokens_per_s", "device_trace"


def read(ctx):
    kernel = ctx["trace"].kernel_s()
    if not kernel or not ctx["steps"] or ctx["peaks"] is None:
        return None
    least = work.train_kernel_least_seconds(
        ctx["tokens_per_step"], ctx["cfg"]["num_topics"], ctx["peaks"])
    return 100.0 * least / (kernel / ctx["steps"])
