"""95th percentile over the window's requests of the time from the due
time to the start of the scheduler round that first admitted it (a request
never admitted counts as the worst)."""
LAYER = "scheduler (repro.core.scheduler, repro.kv.radix)"
UNIT = "ms"
MOVES = "ttft_p95_ms"


def read(ctx):
    return ctx.queue_p95_ms if ctx.kind == "serve" else None
