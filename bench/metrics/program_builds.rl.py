"""Programs built per RL step inside the traced window: the lowerings to
MLIR (``build.<program>.lower`` spans, one per program built; a jitted
function called inside another is inlined into its module) over the
``rl.step`` spans.  A fresh engine every step means fresh jit caches, so
its programs are traced, lowered and loaded again.  Prints the window's
per-program build table on standard error."""
from bench import program_trace

LAYER = "engine and device (repro.core.engine)"
UNIT = "builds/step"
MOVES = "rl_step_s"


def read(ctx):
    if ctx.kind != "rl":
        return None
    spans = program_trace.window_spans(ctx)
    steps = len(program_trace.named(spans or [], "rl.step"))
    if not steps:
        return None
    program_trace.print_build_table(spans)
    lowerings = sum(1 for sp in spans
                    if sp[0].startswith("build.") and sp[0].endswith(".lower"))
    return lowerings / steps
