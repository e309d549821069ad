"""Device milliseconds per training step and chip in the count update:
the self time of the ops that ``program_trace.scope_map`` of the compiled
step puts in its ``zen.delta_counts`` scope (the scatter-adds of the
delta counts and their index sorts)."""
from bench import program_trace

UNIT, LAYER, MOVES, SOURCE = ("ms", "count update and re-layout",
                              "train_tokens_per_s", "device_trace")


def read(ctx):
    return (program_trace.step_ms_by_scope(ctx) or {}).get("zen.delta_counts")
