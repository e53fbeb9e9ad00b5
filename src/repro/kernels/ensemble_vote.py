"""Pallas TPU kernels: fused weighted ensemble vote H(x) = sum_t a~_t h_t(x).

Fuses the (T-learner x N-sample) weighted reduction into one VMEM-resident
pass — the XLA fallback materializes the full scaled-margin tensor in HBM
(T x N x 4 bytes) before reducing; here each (block_t x block_n) tile is
reduced on the fly into the (1, block_n) output accumulator.

Requests from B tenants are packed into one padded (B, T, N) block (the
unbatched vote is the B = 1 case).  Per-learner vectors (alphas,
thresholds, polarities) travel as (B, T, 1) columns so a (block_t, 1)
block broadcasts across the request lanes, and outputs are (B, 1, N) rows;
the leading tenant axis is squeezed out of every block.  Sums run over the
sublane axis in exact float32 on the VPU.

* :func:`ensemble_vote_batched_kernel` — per-tenant weighted vote over
  precomputed margins (generic weak learners).
* :func:`stump_vote_batched_kernel`    — the stump fast path: the weak-
  learner prediction margin pol*sign(x[feat] - thr) and the weighted vote
  are fused in a single VMEM-resident pass, so the (T, N) margin tensor is
  never materialized in HBM.
* :func:`stump_vote_fp_batched_kernel` — the one-launch serving path: the
  stump margin, the weighted vote, *and* a per-column xor-fold feature
  fingerprint (two uint32 lanes, mixing constants shared with
  ``ref._fp_lanes``) in a single launch, so ``BatchEvaluator`` can key its
  result cache without re-walking any feature vector on the host.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import FP_ODD0, FP_ODD1, FP_SALT0, FP_SALT1


def _weighted_rows(a, m):
    """sum_t a[t] * m[t, :] for a (bt, 1) column and a (bt, bn) tile."""
    return jnp.sum(a * m, axis=0, keepdims=True)            # (1, bn)


def _stump_margins(x_ref, thr_ref, pol_ref):
    # the 1e-12 tiebreak matches fed_mesh._predict_stumps /
    # models.weak.predict_stump
    x = x_ref[...].astype(jnp.float32)                      # (bt, bn)
    thr = thr_ref[...].astype(jnp.float32)                  # (bt, 1)
    pol = pol_ref[...].astype(jnp.float32)
    return pol * jnp.sign(x - thr + 1e-12)


def _vote_call(kernel, args, n_out_u32: int, block_t: int, block_n: int,
               interpret: bool):
    """Launch one batched vote kernel: args[0] is the (B, T, N) tile
    source, the rest are (B, T, 1) per-learner columns; outputs are one
    (B, 1, N) f32 margin row plus ``n_out_u32`` uint32 rows."""
    B, T, N = args[0].shape
    assert T % block_t == 0 and N % block_n == 0 and block_t % 8 == 0, (
        B, T, N, block_t, block_n)
    tile = pl.BlockSpec((None, block_t, block_n), lambda b, n, t: (b, t, n))
    column = pl.BlockSpec((None, block_t, 1), lambda b, n, t: (b, t, 0))
    row = pl.BlockSpec((None, 1, block_n), lambda b, n, t: (b, 0, n))
    out_shape = [jax.ShapeDtypeStruct((B, 1, N), jnp.float32)]
    out_shape += [jax.ShapeDtypeStruct((B, 1, N), jnp.uint32)] * n_out_u32
    return pl.pallas_call(
        kernel,
        # T innermost: each (b, n) output row accumulates over t blocks
        grid=(B, N // block_n, T // block_t),
        in_specs=[tile] + [column] * (len(args) - 1),
        out_specs=[row] * len(out_shape),
        out_shape=out_shape,
        interpret=interpret,
    )(*args)


def _vote_kernel(m_ref, a_ref, out_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += _weighted_rows(a_ref[...].astype(jnp.float32),
                                   m_ref[...].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("block_t", "block_n", "interpret"))
def ensemble_vote_batched_kernel(margins: jnp.ndarray, alphas: jnp.ndarray, *,
                                 block_t: int = 128, block_n: int = 512,
                                 interpret: bool = True) -> jnp.ndarray:
    """margins: (B,T,N); alphas: (B,T,1) -> (B,1,N) f32 per-tenant
    ensemble margins.  T, N must be multiples of the block sizes (the
    dispatch wrapper pads with zero-alpha rows / dummy columns)."""
    return _vote_call(_vote_kernel, (margins, alphas), 0, block_t, block_n,
                      interpret)[0]


def _stump_vote_kernel(x_ref, thr_ref, pol_ref, a_ref, out_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += _weighted_rows(a_ref[...].astype(jnp.float32),
                                   _stump_margins(x_ref, thr_ref, pol_ref))


@functools.partial(jax.jit, static_argnames=("block_t", "block_n", "interpret"))
def stump_vote_batched_kernel(xsel: jnp.ndarray, thr: jnp.ndarray,
                              pol: jnp.ndarray, alphas: jnp.ndarray, *,
                              block_t: int = 128, block_n: int = 512,
                              interpret: bool = True) -> jnp.ndarray:
    """Fused stump prediction + weighted vote.

    xsel: (B,T,N) pre-gathered features xsel[b,t,n] = x_b[n, feat_{b,t}];
    thr, pol, alphas: (B,T,1) -> (B,1,N) f32 ensemble margins.  Zero-alpha
    padding rows contribute nothing regardless of thr/pol."""
    return _vote_call(_stump_vote_kernel, (xsel, thr, pol, alphas), 0,
                      block_t, block_n, interpret)[0]


def _xor_fold(v: jnp.ndarray) -> jnp.ndarray:
    """XOR-reduce a (bt, bn) uint32 block over its rows -> (1, bn): whole
    8-row sublane tiles first, then halving inside the last tile."""
    acc = v[0:8]
    for i in range(8, v.shape[0], 8):
        acc = acc ^ v[i:i + 8]
    for h in (4, 2, 1):
        acc = acc[:h] ^ acc[h:2 * h]
    return acc


def _stump_vote_fp_kernel(x_ref, thr_ref, pol_ref, a_ref,
                          out_ref, f0_ref, f1_ref):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        f0_ref[...] = jnp.zeros_like(f0_ref)
        f1_ref[...] = jnp.zeros_like(f1_ref)

    a = a_ref[...].astype(jnp.float32)                      # (bt, 1)
    out_ref[...] += _weighted_rows(a, _stump_margins(x_ref, thr_ref,
                                                     pol_ref))

    # xor-fold fingerprint: same mixing as ref._fp_lanes, with the row
    # position offset by this block's place in the t grid.  alpha-gating
    # makes zero-alpha padding rows the XOR identity, so the fingerprint
    # is invariant under the batch's T padding; XOR associativity makes
    # it invariant under the block layout.
    x = x_ref[...].astype(jnp.float32)
    bt = x.shape[0]
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    row = (t * bt + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0))
    tt = row.astype(jnp.uint32)
    live = a != 0.0
    zero = jnp.zeros_like(bits)
    f0_ref[...] ^= _xor_fold(jnp.where(
        live, (bits ^ jnp.uint32(FP_SALT0)) * (2 * tt + FP_ODD0), zero))
    f1_ref[...] ^= _xor_fold(jnp.where(
        live, (bits ^ jnp.uint32(FP_SALT1)) * (2 * tt + FP_ODD1), zero))


@functools.partial(jax.jit, static_argnames=("block_t", "block_n", "interpret"))
def stump_vote_fp_batched_kernel(xsel: jnp.ndarray, thr: jnp.ndarray,
                                 pol: jnp.ndarray, alphas: jnp.ndarray, *,
                                 block_t: int = 128, block_n: int = 512,
                                 interpret: bool = True):
    """Fused stump prediction + weighted vote + feature fingerprint.

    Same contract as :func:`stump_vote_batched_kernel` plus two uint32
    fingerprint outputs: ``(margins (B,1,N) f32, fp0 (B,1,N) u32,
    fp1 (B,1,N) u32)``.  Zero-alpha padding rows contribute nothing to the
    vote *or* the fingerprint, so both are stable across batch packing.
    """
    return tuple(_vote_call(_stump_vote_fp_kernel, (xsel, thr, pol, alphas),
                            2, block_t, block_n, interpret))
