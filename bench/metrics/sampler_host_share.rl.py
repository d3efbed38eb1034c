"""Share of the traced window the host spends in the tree sampler's rounds
(``sampler.round`` spans: one decode and branching round each), less the
time inside them spent waiting on the device (``transfer.*``) or building
programs (``build.*``)."""
from bench import program_trace

LAYER = "tree sampler (repro.core.sampler)"
UNIT = "%"
MOVES = "rl_step_s"


def read(ctx):
    if ctx.kind != "rl":
        return None
    spans = program_trace.window_spans(ctx)
    rounds = program_trace.named(spans or [], "sampler.round")
    if not rounds:
        return None
    kids = program_trace.children(spans)
    host = sum(program_trace.host_ns(spans, kids, i) for i in rounds)
    return 100.0 * host / 1e9 / ctx.window_s
