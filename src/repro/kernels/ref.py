"""Pure-jnp oracles for every Pallas kernel in this package.

Each ``*_ref`` function defines the semantics the kernel must match
(asserted allclose in tests over shape/dtype sweeps, with the kernel run in
interpret mode on CPU).  Every contraction runs at ``HIGHEST`` precision:
at the default precision the TPU rounds float32 operands to bfloat16,
which would move the stump argmin and flip near-zero margins.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_EXACT = jax.lax.Precision.HIGHEST


def stump_scan_ref(x: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray,
                   thresholds: jnp.ndarray) -> jnp.ndarray:
    """Weighted error of the polarity-(+1) stump for every (feature,
    threshold) pair.

    x: (N,F); y: (N,) in {-1,+1}; w: (N,); thresholds: (F,T) -> (F,T) f32.

    err[f,t] = sum_i w_i * [ sign(x[i,f] - thr[f,t]) != y_i ]
    (sign(0) counts as -1: strict `>` decides the +1 side.)
    """
    pred = jnp.where(x[:, :, None] > thresholds[None, :, :], 1.0, -1.0)
    miss = (pred != y[:, None, None]).astype(jnp.float32)
    return jnp.einsum("n,nft->ft", w.astype(jnp.float32), miss,
                      precision=_EXACT)


def stump_scan_batched_ref(x: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray,
                           thresholds: jnp.ndarray) -> jnp.ndarray:
    """Per-client weighted stump errors for a stacked fleet batch.

    x: (B,N,F); y, w: (B,N); thresholds: (B,F,T) -> (B,F,T) f32 — exactly
    :func:`stump_scan_ref` per batch slot.  Rows padded with w = 0
    contribute nothing, so ragged client shards stack safely.
    """
    return jax.vmap(stump_scan_ref)(x, y, w, thresholds)


def stump_scan_ragged_ref(x: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray,
                          seg: jnp.ndarray, thresholds: jnp.ndarray
                          ) -> jnp.ndarray:
    """Each client's best stump of each polarity over packed rows.

    x: (R,F) rows of all clients back to back; y, w: (R,); seg: (R,)
    the client of each row, non-decreasing (rows at or past B are
    padding and carry w = 0); thresholds: (B,T,Fp), Fp >= F, features
    past F not scanned -> (6,B) f32: the least
    error of polarity +1, its flat index f*T + t (lowest on ties) and its
    threshold, then the same for polarity -1, whose error is 1 - err.
    err[b] is :func:`stump_scan_ref` over client b's rows, as (F,T).
    """
    F = x.shape[1]
    B, T, _ = thresholds.shape
    thresholds = thresholds[:, :, :F]
    s = jnp.minimum(seg, B - 1)
    pred = jnp.where(x[:, None, :] > thresholds[s], 1.0, -1.0)   # (R,T,F)
    miss = jnp.where(pred != y[:, None, None], w[:, None, None], 0.0)
    err = jax.ops.segment_sum(miss.astype(jnp.float32), s, B,
                              indices_are_sorted=True)             # (B,T,F)
    err = jnp.swapaxes(err, 1, 2).reshape(B, F * T)
    thr = jnp.swapaxes(thresholds, 1, 2).reshape(B, F * T)
    out = []
    for e in (err, 1.0 - err):
        idx = jnp.argmin(e, axis=1)
        out += [jnp.min(e, axis=1), idx.astype(jnp.float32),
                jnp.take_along_axis(thr, idx[:, None], axis=1)[:, 0]]
    return jnp.stack(out)


def ensemble_vote_ref(margins: jnp.ndarray, alphas: jnp.ndarray) -> jnp.ndarray:
    """Weighted ensemble margin: H(x) = sum_t alpha_t h_t(x).

    margins: (T, N) per-learner predictions in [-1, 1]; alphas: (T,)
    (already staleness-compensated) -> (N,) f32 ensemble margin.
    """
    return jnp.einsum("t,tn->n", alphas.astype(jnp.float32),
                      margins.astype(jnp.float32), precision=_EXACT)


def ensemble_vote_batched_ref(margins: jnp.ndarray, alphas: jnp.ndarray
                              ) -> jnp.ndarray:
    """Per-tenant weighted ensemble margins (serving batch path).

    margins: (B, T, N) per-learner predictions for B packed tenants;
    alphas: (B, T) -> (B, N) f32 ensemble margins.
    """
    return jnp.einsum("bt,btn->bn", alphas.astype(jnp.float32),
                      margins.astype(jnp.float32), precision=_EXACT)


def stump_vote_batched_ref(xsel: jnp.ndarray, thr: jnp.ndarray,
                           pol: jnp.ndarray, alphas: jnp.ndarray
                           ) -> jnp.ndarray:
    """Fused stump prediction + weighted vote (serving stump fast path).

    xsel: (B, T, N) gathered features xsel[b,t,n] = x_b[n, feat_{b,t}];
    thr, pol, alphas: (B, T) -> (B, N) f32 ensemble margins.  The 1e-12
    sign tiebreak matches the stump predictors used at training time.
    """
    m = (pol[:, :, None].astype(jnp.float32)
         * jnp.sign(xsel.astype(jnp.float32)
                    - thr[:, :, None].astype(jnp.float32) + 1e-12))
    return jnp.einsum("bt,btn->bn", alphas.astype(jnp.float32), m,
                      precision=_EXACT)


# Feature-fingerprint mixing constants, shared verbatim with the fused
# Pallas kernel (kernels/ensemble_vote.py) so oracle and kernel fold the
# same bits: two independent 32-bit lanes give a 64-bit fingerprint.  The
# multiplier 2*t + ODD is always odd (invertible mod 2^32), making the
# fold position-sensitive; rows are gated on alpha != 0 so zero-alpha
# padding rows contribute the XOR identity and the fingerprint is
# invariant under the serving batch's T padding.
FP_SALT0 = 0x9E3779B9
FP_SALT1 = 0x85EBCA6B
FP_ODD0 = 0x0001_0001
FP_ODD1 = 0x00C2_B2AF


def _fp_lanes(xsel: jnp.ndarray, alphas: jnp.ndarray):
    """The two uint32 fingerprint lanes of each (batch, column) pair.

    xsel: (B, T, N) float features; alphas: (B, T).  Lane k folds
    ``XOR_t [(bits(x[t]) ^ SALT_k) * (2 t + ODD_k)]`` over the rows with
    ``alpha_t != 0``.  Because alpha-zero rows contribute nothing to the
    weighted vote either, two columns sharing a fingerprint under the same
    (tenant, version) alphas share the ensemble margin too.
    """
    bits = jax.lax.bitcast_convert_type(xsel.astype(jnp.float32),
                                        jnp.uint32)              # (B, T, N)
    T = xsel.shape[1]
    tt = jnp.arange(T, dtype=jnp.uint32)[None, :, None]
    live = (alphas.astype(jnp.float32) != 0.0)[:, :, None]
    zero = jnp.zeros_like(bits)
    c0 = jnp.where(live,
                   (bits ^ jnp.uint32(FP_SALT0)) * (2 * tt + FP_ODD0), zero)
    c1 = jnp.where(live,
                   (bits ^ jnp.uint32(FP_SALT1)) * (2 * tt + FP_ODD1), zero)
    f0 = jax.lax.reduce(c0, jnp.uint32(0), jax.lax.bitwise_xor, (1,))
    f1 = jax.lax.reduce(c1, jnp.uint32(0), jax.lax.bitwise_xor, (1,))
    return f0, f1


def stump_vote_fp_batched_ref(xsel: jnp.ndarray, thr: jnp.ndarray,
                              pol: jnp.ndarray, alphas: jnp.ndarray):
    """Fused stump vote + per-column feature fingerprint (serving one-launch
    path).

    Same margin semantics as :func:`stump_vote_batched_ref`, plus two
    uint32 fingerprint lanes per column — ``(margins (B,N) f32,
    fp0 (B,N) u32, fp1 (B,N) u32)``.  The fingerprint lanes are *exact*
    integers: every backend must reproduce them bit-for-bit (XOR folding
    is order-independent, so block layout cannot perturb them).
    """
    margins = stump_vote_batched_ref(xsel, thr, pol, alphas)
    f0, f1 = _fp_lanes(xsel, alphas)
    return margins, f0, f1


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        *, causal: bool = True) -> jnp.ndarray:
    """Plain softmax attention.  q,k,v: (B,H,T,hd) -> (B,H,T,hd)."""
    Tq, Tk = q.shape[2], k.shape[2]
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((Tq, Tk), bool), k=Tk - Tq)
        logits = jnp.where(mask[None, None], logits, -1e30)
    wts = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", wts, v.astype(jnp.float32))
    return out.astype(q.dtype)


def dist_update_ref(alpha, D, y, h):
    """AdaBoost distribution update (paper eq. 4): returns normalized D'.

    D'_i = D_i exp(-alpha y_i h_i) / Z,  Z = sum_i D_i exp(-alpha y_i h_i).
    """
    import jax.numpy as _jnp
    w = D.astype(_jnp.float32) * _jnp.exp(
        -_jnp.asarray(alpha, _jnp.float32) * y.astype(_jnp.float32)
        * h.astype(_jnp.float32))
    Z = _jnp.sum(w)
    return w / (Z + 1e-30), Z
