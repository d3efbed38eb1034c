"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window."""
LAYER = "engine and device (repro.core.engine)"
UNIT = "%"
MOVES = "rl_step_s"


def read(ctx):
    if ctx.kind != "rl" or ctx.trace is None or not ctx.trace["chips"]:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.window_s)
