"""The batched stump-scan kernel's share of its roofline in the fleet
cell: the least time the v5e could take for the work the algorithm needs
in every fit wave of the traced window (bytes and compares-and-adds of
the wave's real clients and rows, ``bench/work.py``), over the kernel's
device time in the trace.  Memory bounds it: each (row, feature,
threshold) needs two operations against about 4 / T bytes."""
from bench import work

MOVES = "train_round_s"
KERNEL = r"^stump_scan_kernel"


def read(ctx):
    kernel_s = ctx.trace.op_seconds(KERNEL)
    calls = [work.stump_scan(rows, slots, shape[2], shape[3])
             for shape, (slots, rows) in zip(ctx.info["fit_shapes"],
                                             ctx.info["wave_rows"])]
    if not calls or kernel_s <= 0 or not ctx.peaks:
        return None
    share, _bound = work.roofline_share(calls, kernel_s, ctx.peaks)
    return 100.0 * share
