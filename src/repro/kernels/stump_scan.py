"""Pallas TPU kernel: AdaBoost weighted-error sweep over the (feature x
threshold) stump grid — the compute hot-spot of every boosting round.

One kernel serves both the single-client fit and the stacked fleet fit:
every operand carries a leading client axis (B = 1 for one client).  Each
grid step takes a (block_b, block_n, F) sample block and accumulates the
(block_b, T, F) weighted-error tile, which stays resident in VMEM across
the sample-block axis (revisiting-output pattern).  Thresholds arrive
transposed to (T, F) so features sit on the 128 lanes next to the sample
block's; the sweep over the T thresholds is a static loop of 2-D compares
and a sublane reduction, so no (block_n, F, T) intermediate exists and
every sum is exact float32 on the VPU (an MXU contraction at default
precision would round the weights to bfloat16 and move the argmin).

    err[b, t, f] = sum_i w[b,i] * [ sign(x[b,i,f] - thr[b,t,f]) != y[b,i] ]
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _stump_kernel(x_ref, y_ref, w_ref, thr_ref, err_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        err_ref[...] = jnp.zeros_like(err_ref)

    x = x_ref[...].astype(jnp.float32)              # (bb, bn, F)
    y = y_ref[...].astype(jnp.float32)              # (bb, bn, 1)
    w = w_ref[...].astype(jnp.float32)              # (bb, bn, 1)
    for t in range(thr_ref.shape[1]):
        thr = thr_ref[:, t:t + 1, :].astype(jnp.float32)        # (bb, 1, F)
        pred = jnp.where(x > thr, 1.0, -1.0)
        miss = jnp.where(pred != y, w, 0.0)                     # (bb, bn, F)
        err_ref[:, t:t + 1, :] += jnp.sum(miss, axis=1, keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("block_b", "block_n", "interpret"))
def stump_scan_kernel(x: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray,
                      thresholds_t: jnp.ndarray, *, block_b: int = 1,
                      block_n: int = 256,
                      interpret: bool = True) -> jnp.ndarray:
    """x: (B,N,F); y, w: (B,N,1); thresholds_t: (B,T,F) -> (B,T,F) f32.
    B and N must be multiples of block_b and block_n (the dispatch wrapper
    pads with w = 0 rows and slots, which contribute nothing)."""
    B, N, F = x.shape
    T = thresholds_t.shape[1]
    assert B % block_b == 0 and N % block_n == 0, (B, N, block_b, block_n)
    col = pl.BlockSpec((block_b, block_n, 1), lambda b, i: (b, i, 0))
    grid_tile = pl.BlockSpec((block_b, T, F), lambda b, i: (b, 0, 0))
    return pl.pallas_call(
        _stump_kernel,
        grid=(B // block_b, N // block_n),
        in_specs=[
            pl.BlockSpec((block_b, block_n, F), lambda b, i: (b, i, 0)),
            col, col, grid_tile,
        ],
        out_specs=grid_tile,
        out_shape=jax.ShapeDtypeStruct((B, T, F), jnp.float32),
        interpret=interpret,
    )(x, y, w, thresholds_t)
