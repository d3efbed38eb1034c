"""Least time the window's paged decode attention needs at the chip's
peaks (the K/V bytes of every position each query reads, with q and the
output, against HBM bandwidth, or its FLOPs against the bf16 peak, the
larger: bandwidth bounds it) over the trace's time in
``fused_paged_attention_pallas``."""
LAYER = "kernels (repro.kernels)"
UNIT = "%"
MOVES = "tpot_p95_ms"
KERNEL = "fused_paged_attention_pallas"


def read(ctx):
    if ctx.kind != "serve" or ctx.trace is None:
        return None
    t = ctx.trace["kernel_s"].get(KERNEL, 0.0)
    least = ctx.least_s[KERNEL]
    if t <= 0.0 or least <= 0.0:
        return None
    return 100.0 * least / t
