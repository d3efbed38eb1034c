"""99th percentile (nearest rank) over the traced window's scheduler rounds
(``scheduler.round`` spans: admit, make room, dispatch, finish) of the
round's host time: its duration less the time inside it spent waiting on
the device (``transfer.*``) or building programs (``build.*``).  Prints
the longest round with its children on standard error."""
from bench import program_trace

LAYER = "scheduler (repro.core.scheduler, repro.kv.radix)"
UNIT = "ms"
MOVES = "tpot_p95_ms"


def read(ctx):
    if ctx.kind != "serve":
        return None
    spans = program_trace.window_spans(ctx)
    rounds = program_trace.named(spans or [], "scheduler.round")
    if not rounds:
        return None
    kids = program_trace.children(spans)
    program_trace.print_longest_round(spans)
    return program_trace.percentile(
        [program_trace.host_ns(spans, kids, i) / 1e6 for i in rounds], 99)
