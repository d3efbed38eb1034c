"""Least time the window's flash attention forwards need at the chip's
peaks (causal FLOPs over the valid tokens of each row against the bf16
peak, or q, k, v and output bytes against HBM bandwidth, the larger) over
the trace's time in ``flash_attention_pallas`` (prefill of each rollout
and the forward of each packed update)."""
LAYER = "kernels (repro.kernels)"
UNIT = "%"
MOVES = "rl_step_s"
KERNEL = "flash_attention_pallas"


def read(ctx):
    if ctx.kind != "rl" or ctx.trace is None:
        return None
    t = ctx.trace["kernel_s"].get(KERNEL, 0.0)
    least = ctx.least_s[KERNEL]
    if t <= 0.0 or least <= 0.0:
        return None
    return 100.0 * least / t
