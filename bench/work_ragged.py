"""The work the ragged stump scan's algorithm needs, from the shapes of a
call: bytes it must read and write, and operations it must make.  Counted
from what the computation needs, never from what an implementation moves
(``bench/work.py`` holds the other kernels' yardsticks).  All data is
float32 (4 bytes).
"""
from __future__ import annotations

from typing import Tuple

F32 = 4
STUMP_WORDS = 3          # a client's result: feature, threshold, polarity


def ragged_scan(rows: int, clients: int, F: int, T: int
                ) -> Tuple[float, float]:
    """Every client's best stump over ``rows`` real rows of ``clients``
    clients, F features, T thresholds per feature.  Reads each real row's
    F features, its label and its weight once, and writes each client's
    stump once; each (row, feature, threshold) is one compare and one add.
    The threshold grid is left out (thresholds are a function of the
    client's own rows, which an implementation may derive rather than
    read), and so is all padding, so the share stays at most 100% for any
    implementation."""
    nbytes = F32 * (rows * F + 2 * rows + STUMP_WORDS * clients)
    ops = 2.0 * rows * F * T
    return float(nbytes), ops
