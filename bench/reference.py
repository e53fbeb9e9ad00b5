"""Plain references for the comparisons that decide ``correct``.

Straightforward NumPy in float32, written from the algorithms' published
description and imports nothing of the program.  Every function takes a
``prec`` of ``"f32"`` (the reference) or ``"bf16"`` (the control): the
control rounds each operand and each result to bfloat16, the precision a
later change would be tempted to drop to, and has to come out as not
correct under the same comparison.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

F32 = np.float32
EPS_CLIP = 1e-6          # AdaBoost vote weight: eps kept off {0, 1}


def rounder(prec: str):
    """Identity for f32; round to bfloat16 and back for bf16."""
    if prec == "f32":
        return lambda a: np.asarray(a, F32)
    if prec == "bf16":
        import ml_dtypes
        return lambda a: np.asarray(a, F32).astype(
            ml_dtypes.bfloat16).astype(F32)
    raise ValueError(f"unknown precision {prec!r}")


def vote_weight(eps, r):
    """alpha = 1/2 ln((1 - eps) / eps), eps clipped to [1e-6, 1 - 1e-6]."""
    e = np.clip(r(eps), EPS_CLIP, 1.0 - EPS_CLIP)
    return r(0.5 * np.log((1.0 - e) / e))


def stump_predict(x_f, thr, pol):
    """A stump's vote in {-1, +1}: pol * sign(x - thr), with x == thr on
    the +1 side (the 1e-12 tie-break of the training-time predictor)."""
    return pol * np.sign(x_f - thr + F32(1e-12))


# ------------------------------------------------------------------ fleet
def quantile_grid(x: np.ndarray, n: int, n_thresholds: int = 16
                  ) -> np.ndarray:
    """Per-feature thresholds at the n_thresholds inner quantiles
    (linear interpolation) of a client's first ``n`` rows: (F, T)."""
    qs = np.linspace(0.0, 1.0, n_thresholds + 2)[1:-1]
    return np.quantile(x[:n].astype(np.float64), qs, axis=0).T.astype(F32)


def slot_errors(x, y, w, grid, r) -> np.ndarray:
    """Weighted error of every (feature, threshold) stump with polarity +1
    of one slot: x (N, F), y and w (N,), grid (F, T) -> (F, T).  The +1
    side of a stump is x > thr."""
    miss = (np.where(x[:, :, None] > grid[None, :, :], 1.0, -1.0)
            != y[:, None, None]).astype(F32)                 # (N, F, T)
    return r(np.einsum("n,nft->ft", w, miss, dtype=F32))


def best_stump_error(x, y, w, grid, prec: str = "f32") -> np.ndarray:
    """Least weighted error over every (feature, threshold, polarity) of
    each slot: x (S, N, F), y and w (S, N), grid (S, F, T) -> (S,)."""
    r = rounder(prec)
    x, w, grid = r(x), r(w), r(grid)
    out = np.empty(len(x), F32)
    for s in range(len(x)):
        err = slot_errors(x[s], y[s], w[s], grid[s], r)
        out[s] = min(err.min(), r(1.0 - err).min())
    return out


def stump_error(x, y, w, f, thr, pol, prec: str = "f32") -> np.ndarray:
    """Weighted error of each slot's stump as the fit scores it, the +1
    side being x > thr: x (S, N, F), y and w (S, N), f, thr, pol (S,)."""
    r = rounder(prec)
    xf = r(np.take_along_axis(r(x), f[:, None, None], axis=2)[:, :, 0])
    h = pol[:, None] * np.where(xf > r(thr)[:, None], 1.0, -1.0)
    return r(np.sum(r(w) * (h != y), axis=1, dtype=F32))


def best_stumps(x, y, w, grid, prec: str):
    """(feature, threshold, polarity) of the least-error stump of each
    slot, lowest index first: what the control puts in the program's
    place."""
    r = rounder(prec)
    x, w, grid = r(x), r(w), r(grid)
    S, F, T = grid.shape
    f = np.empty(S, np.int64)
    thr = np.empty(S, F32)
    pol = np.empty(S, F32)
    for s in range(S):
        err = slot_errors(x[s], y[s], w[s], grid[s], r).reshape(-1)
        idx = int(np.concatenate([err, r(1.0 - err)]).argmin())
        pol[s] = 1.0 if idx < F * T else -1.0
        f[s], t = divmod(idx % (F * T), T)
        thr[s] = grid[s, f[s], t]
    return f, thr, pol


def initial_distribution(y, balanced: bool) -> np.ndarray:
    """A client's first distribution over its rows: uniform, or with each
    class holding half of the weight when the configuration balances."""
    n = len(y)
    if not balanced:
        return np.full(n, 1.0 / n, F32)
    pos = (np.asarray(y) > 0).astype(F32)
    npos = max(float(pos.sum()), 1.0)
    nneg = max(n - float(pos.sum()), 1.0)
    return (pos / (2 * npos) + (1 - pos) / (2 * nneg)).astype(F32)


def local_update(x, y, D, f, thr, pol, prec: str = "f32"):
    """A client's round after its fit: the stump's weighted error eps on
    D, its vote weight, and the updated distribution
    D' = D exp(-alpha y h) / Z.  x (S, N, F); y, D (S, N); f, thr, pol
    (S,).  Returns (eps (S,), D' (S, N))."""
    r = rounder(prec)
    xf = r(np.take_along_axis(x, f[:, None, None], axis=2)[:, :, 0])
    h = stump_predict(xf, r(thr)[:, None], pol[:, None])
    D = r(D)
    eps = r(np.sum(D * (np.where(h > 0, 1.0, -1.0) != y), axis=1,
                   dtype=F32))
    alpha = vote_weight(eps, r)
    w = r(D * r(np.exp(-alpha[:, None] * y * h)))
    Z = r(np.sum(w, axis=1, dtype=F32))
    return eps, r(w / (Z[:, None] + F32(1e-30)))
