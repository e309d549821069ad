"""Device milliseconds per training step and chip in the re-layout of the
count matrices for the kernel: the self time of the ops that
``program_trace.scope_map`` of the compiled step puts in its
``zen.relayout`` scope (the ``pad`` and ``reshape`` of N_wk and N_kd into
the kernel's row views, once per chunk)."""
from bench import program_trace

UNIT, LAYER, MOVES, SOURCE = ("ms", "count update and re-layout",
                              "train_tokens_per_s", "device_trace")


def read(ctx):
    return (program_trace.step_ms_by_scope(ctx) or {}).get("zen.relayout")
