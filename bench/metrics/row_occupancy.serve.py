"""Share of the serve program's compiled rows that carried a running
request over the traced window's dispatches: ``rows_real /
rows_dispatched`` as each ``scheduler.dispatch`` span's attributes add
them (``rows``, ``bucket``); every dispatch pads to one compiled bucket."""
from bench import program_trace

LAYER = "scheduler (repro.core.scheduler, repro.kv.radix)"
UNIT = "%"
MOVES = "tpot_p95_ms"


def read(ctx):
    if ctx.kind != "serve":
        return None
    spans = program_trace.window_spans(ctx) or []
    got = [spans[i][4] for i in program_trace.named(spans,
                                                     "scheduler.dispatch")]
    bucket = sum(a.get("bucket", 0) for a in got)
    if not bucket:
        return None
    return 100.0 * sum(a.get("rows", 0) for a in got) / bucket
