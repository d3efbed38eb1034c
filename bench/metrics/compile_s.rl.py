"""Seconds JAX spent lowering and compiling (persistent-cache loads
included) inside the window: the trainer builds a fresh engine, so fresh
jitted closures, every step."""
LAYER = "RL loop (repro.rl.trainer)"
UNIT = "s"
MOVES = "rl_step_s"


def read(ctx):
    return ctx.compile_s if ctx.kind == "rl" else None
