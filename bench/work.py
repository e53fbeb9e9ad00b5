"""The work each kernel's algorithm needs, from the shapes of a call:
bytes it must read and write, and operations it must make.  Counted from
what the computation needs, never from what an implementation moves, so a
kernel that replaces another is judged on the same yardstick.  All data
is float32 (4 bytes).
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

F32 = 4


def stump_scan(rows: int, slots: int, F: int, T: int
               ) -> Tuple[float, float]:
    """Weighted error of every (feature, threshold) stump of ``slots``
    clients holding ``rows`` rows in all, F features, on a grid of T
    thresholds per feature.  Reads each real row's F features, label and
    weight once and each client's thresholds, and writes its (F, T) error
    grid; each (row, feature, threshold) is one compare and one add.
    Padding rows and padding slots are no part of the work."""
    nbytes = F32 * (rows * F + 2 * rows + 2 * slots * F * T)
    ops = 2.0 * rows * F * T
    return float(nbytes), ops


def roofline_share(calls: Iterable[Tuple[float, float]], kernel_s: float,
                   peaks: Dict[str, float]):
    """The least time the chip could take for ``calls`` (bytes, ops) over
    the time the kernel took: ``(share, bound)`` with ``bound`` "memory"
    or "compute", whichever of bytes over bandwidth and operations over
    the peak rate is larger.  The operation peak is the chip's bf16
    matrix peak, the only one published; these kernels run float32
    compares and adds on the vector unit, so the compute bound is a
    lower bound on their compute time."""
    nbytes = ops = 0.0
    for b, o in calls:
        nbytes += b
        ops += o
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    t_ops = ops / peaks["flops_bf16"]
    least = max(t_mem, t_ops)
    return least / kernel_s, ("memory" if t_mem >= t_ops else "compute")
