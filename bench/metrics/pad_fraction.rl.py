"""Mean over the window's steps of the trainer's ``padded_token_fraction``:
the share of the update's token grid that is padding."""
LAYER = "update and packing (repro.rl.update, repro.rl.packing)"
UNIT = "%"
MOVES = "rl_step_s"


def read(ctx):
    if ctx.kind != "rl" or not ctx.pad_fractions:
        return None
    return 100.0 * sum(ctx.pad_fractions) / len(ctx.pad_fractions)
