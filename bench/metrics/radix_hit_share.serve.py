"""Share of the window's admitted prompt tokens whose KV came from the
radix cache (``ServeReport.radix_hit_tokens / prompt_tokens``)."""
LAYER = "scheduler (repro.core.scheduler, repro.kv.radix)"
UNIT = "%"
MOVES = "ttft_p95_ms"


def read(ctx):
    if ctx.kind != "serve" or not ctx.radix[1]:
        return None
    return 100.0 * ctx.radix[0] / ctx.radix[1]
