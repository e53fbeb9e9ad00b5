"""Share of the rows that the ragged fit waves launch that are real, in
the traced window: the program's counters ``train.fleet.real_rows`` over
``train.fleet.packed_rows_launched`` (row-block padding included), read
at the window's ends.  Silent where the program counts no packed rows."""
MOVES = "train_round_s"


def read(ctx):
    counted = ctx.info.get("counters", {})
    launched = counted.get("packed_rows_launched", 0)
    if not launched:
        return None
    return 100.0 * counted.get("real_rows", 0) / launched
