"""Share of the trajectories' tokens (prompt and response, per trajectory)
that the tree did not recompute: 1 - engine model tokens / trajectory
tokens, from ``EngineStats`` (the formula of ``RolloutCost.sharing_ratio``)."""
LAYER = "tree sampler (repro.core.sampler)"
UNIT = "%"
MOVES = "rl_step_s"


def read(ctx):
    if ctx.kind != "rl" or not ctx.sharing[1]:
        return None
    shared, total = ctx.sharing
    return 100.0 * shared / total
