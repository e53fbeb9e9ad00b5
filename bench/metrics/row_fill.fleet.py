"""Share of the rows that each fit wave launches that are real: the real
clients' rows over the wave's slots times its padded row count (every
client is padded to the fleet's largest), summed over the fit waves of
the traced window."""
MOVES = "train_round_s"


def read(ctx):
    launched = sum(shape[0] * shape[1] for shape in ctx.info["fit_shapes"])
    real = sum(r for _, r in ctx.info["wave_rows"])
    return 100.0 * real / launched if launched else None
