"""The ragged stump-scan kernel's share of its roofline in the Sent140
cell: the least time the v5e could take for the work the algorithm needs
in every ragged fit of the traced window (each fit's real rows read once
and each client's stump written once, ``bench/work_ragged.py``), over the
kernel's device time in the trace.  Memory bounds it.  Silent where the
trace holds no such kernel."""
from bench import work, work_ragged

MOVES = "train_round_s"
KERNEL = r"^ragged_scan_kernel"


def read(ctx):
    kernel_s = ctx.trace.op_seconds(KERNEL)
    fits = ctx.info.get("ragged_fits", [])
    calls = [work_ragged.ragged_scan(rows, clients, shape[1], shape[3])
             for shape, (clients, rows) in zip(fits, ctx.info["wave_rows"])]
    if not calls or kernel_s <= 0 or not ctx.peaks:
        return None
    share, _bound = work.roofline_share(calls, kernel_s, ctx.peaks)
    return 100.0 * share
