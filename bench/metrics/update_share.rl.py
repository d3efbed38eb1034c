"""Share of the window spent in the packed PPO update (host clock around
``RLTrainer.update_packed``, which returns host metrics, so the device
work is done)."""
LAYER = "RL loop (repro.rl.trainer)"
UNIT = "%"
MOVES = "rl_step_s"


def read(ctx):
    if ctx.kind != "rl" or "update" not in ctx.spans:
        return None
    return 100.0 * ctx.spans["update"] / ctx.window_s
