"""Whole-step share of a chip's peak: ``train_tokens_per_s`` (the host
clock) per chip of the cell, times the dense conditional's required time
per token at the peaks of ``bench/peaks.json`` (its ``2 K`` int32 count
rows against HBM bandwidth, which bounds it on v5e). Nothing of it is read
from the trace."""
from bench import work

UNIT, LAYER, MOVES, SOURCE = "%", "whole step", "train_tokens_per_s", "host_clock"


def read(ctx):
    if ctx["peaks"] is None or not ctx["steps"]:
        return None
    per_token = work.train_step_least_seconds_per_token(
        ctx["cfg"]["num_topics"], ctx["peaks"])
    return 100.0 * ctx["tokens_per_s"] * per_token / ctx["chips"]
