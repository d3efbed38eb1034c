"""Model FLOPs of the work the window did over (window x bf16 peak): 2 x
matmul parameters per token a forward pass computes, 6 x per token an
update trains, plus attention (4 x context x heads x head size per token
and layer; x3 in the update).  Recomputed and padded work is not
counted."""
LAYER = "whole step"
UNIT = "%"
MOVES = "tpot_p95_ms"


def read(ctx):
    if ctx.kind != "serve" or ctx.model_flops <= 0.0:
        return None
    return 100.0 * ctx.model_flops / (ctx.window_s * ctx.peaks["flops_bf16"])
