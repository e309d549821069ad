"""Device milliseconds per training step and chip in the delta exchange
of a mesh step: the self time of the ops that ``program_trace.scope_map``
of the compiled step puts in its ``zen.exchange`` scope (the ``psum``s
of dN_wk over the data axis, dN_kd over the model axis and dN_k)."""
from bench import program_trace

UNIT, LAYER, MOVES, SOURCE = ("ms", "delta exchange", "train_tokens_per_s",
                              "device_trace")


def read(ctx):
    return (program_trace.step_ms_by_scope(ctx) or {}).get("zen.exchange")
