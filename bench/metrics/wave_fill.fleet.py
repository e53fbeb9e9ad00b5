"""Share of each power-of-two fit wave that is real clients: clients
fitted over the slots launched, summed over the fit waves of the traced
window (counted at the batched fit's entry and the local update)."""
MOVES = "train_round_s"


def read(ctx):
    slots = sum(shape[0] for shape in ctx.info["fit_shapes"])
    real = sum(s for s, _ in ctx.info["wave_rows"])
    return 100.0 * real / slots if slots else None
