"""Share of the traced window in which no operation ran on the chips the
training cell uses: 1 - busy union / window, averaged over the devices
(profiler trace)."""
MOVES = "train_round_s"


def read(ctx):
    return 100.0 * ctx.trace.idle_share if ctx.trace.n_devices else None
