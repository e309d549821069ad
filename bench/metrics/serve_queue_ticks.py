"""Mean admission ticks a request waited for a bucket slot
(``InferRequest.ticks_waited``)."""
UNIT, LAYER, MOVES, SOURCE = ("ticks", "engine admission", "serve_p95_ms",
                              "program_counter")


def read(ctx):
    ticks = ctx["ticks_waited"]
    return sum(ticks) / len(ticks) if ticks else None
