"""Public kernel entry points, routed through the backend dispatcher.

The padding/dtype handling for the Pallas substrates and the pure-XLA
fallback live together in :mod:`repro.kernels.dispatch`; each wrapper here
names the kernel, forwards its block-shape hints, and exposes the common
selection surface:

* ``backend=``   one-call override: ``"interpret"`` | ``"mosaic"`` | ``"xla"``
* ``policy=``    a :class:`~repro.kernels.dispatch.KernelPolicy` (forced
                 backend and/or calibration table)
* ``interpret=`` deprecated bool shim (True -> "interpret", False ->
                 "mosaic"); warns and will be removed next release

Block-shape kwargs (``block_t``/``block_n``/``block_q``/``block_k``)
default to ``None``, which means "let the calibration table decide": the
dispatcher injects the tuned layout recorded for the resolved (kernel,
shape-bucket, backend) — or the hardcoded reference layout when nothing is
tuned.  Passing an explicit int always wins over both.

With no backend selection, the process-default policy re-resolves on every
call: ``REPRO_KERNEL_BACKEND`` env var > calibration table > platform
default (Mosaic on TPU, interpret elsewhere).
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.dispatch import KernelPolicy  # noqa: F401  (re-export)

# back-compat aliases for the helpers that used to live here
_pad_to = dispatch.pad_to
_on_tpu = dispatch.on_tpu
_vote_blocks = dispatch.vote_blocks


def stump_scan(x: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray,
               thresholds: jnp.ndarray, *, block_n: Optional[int] = None,
               backend: Optional[str] = None,
               policy: Optional[KernelPolicy] = None,
               interpret: Optional[bool] = None) -> jnp.ndarray:
    """Weighted stump errors over the (F, T) grid.  Pallas substrates pad N
    to block_n with zero-weight rows (no contribution) and F/T to the
    8-sublane boundary."""
    return dispatch.dispatch(
        "stump_scan", (x, y, w, thresholds), dict(block_n=block_n),
        policy=policy, backend=backend, interpret=interpret)


def stump_scan_batched(x: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray,
                       thresholds: jnp.ndarray, *,
                       block_n: Optional[int] = None,
                       backend: Optional[str] = None,
                       policy: Optional[KernelPolicy] = None,
                       interpret: Optional[bool] = None) -> jnp.ndarray:
    """Per-client weighted stump errors for a stacked fleet batch.

    x: (B,N,F); y, w: (B,N); thresholds: (B,F,T) -> (B,F,T).  Same padding
    contract as :func:`stump_scan` per batch slot (w = 0 rows contribute
    nothing, so ragged shards stack safely); B lifts onto the launch grid."""
    return dispatch.dispatch(
        "stump_scan_batched", (x, y, w, thresholds), dict(block_n=block_n),
        policy=policy, backend=backend, interpret=interpret)


def stump_scan_ragged(x: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray,
                      seg: jnp.ndarray, thresholds: jnp.ndarray, *,
                      block_n: Optional[int] = None,
                      block_f: Optional[int] = None,
                      backend: Optional[str] = None,
                      policy: Optional[KernelPolicy] = None) -> jnp.ndarray:
    """Each client's best stump of each polarity over packed rows.

    x: (R,F) rows of every client back to back; y, w, seg: (R,), seg the
    client of each row, non-decreasing, every client in [0, B) holding a
    row before any client without one (rows at or past B are padding,
    w = 0); thresholds: (B,T,Fp), Fp >= F (features past F are not
    scanned) -> (6,B): least error, its flat index f*T + t and its
    threshold for polarity +1, then for -1.  Pallas substrates walk F in
    ``block_f`` tiles over ``block_n``-row blocks and keep no (B,T,F)
    error grid."""
    return dispatch.dispatch(
        "stump_scan_ragged", (x, y, w, seg, thresholds),
        dict(block_n=block_n, block_f=block_f),
        policy=policy, backend=backend)


def ensemble_vote(margins: jnp.ndarray, alphas: jnp.ndarray, *,
                  block_t: Optional[int] = None,
                  block_n: Optional[int] = None,
                  backend: Optional[str] = None,
                  policy: Optional[KernelPolicy] = None,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """H margins = sum_t alpha_t h_t.  Pallas substrates pad T with
    zero-alpha rows and N with dummy columns (sliced off)."""
    return dispatch.dispatch(
        "ensemble_vote", (margins, alphas),
        dict(block_t=block_t, block_n=block_n),
        policy=policy, backend=backend, interpret=interpret)


def ensemble_vote_batched(margins: jnp.ndarray, alphas: jnp.ndarray, *,
                          block_t: Optional[int] = None,
                          block_n: Optional[int] = None,
                          backend: Optional[str] = None,
                          policy: Optional[KernelPolicy] = None,
                          interpret: Optional[bool] = None) -> jnp.ndarray:
    """Per-tenant H margins for packed serving batches.

    margins: (B,T,N); alphas: (B,T) -> (B,N).  Same padding contract as
    :func:`ensemble_vote`, per batch slot."""
    return dispatch.dispatch(
        "ensemble_vote_batched", (margins, alphas),
        dict(block_t=block_t, block_n=block_n),
        policy=policy, backend=backend, interpret=interpret)


def stump_vote_batched(xsel: jnp.ndarray, thr: jnp.ndarray, pol: jnp.ndarray,
                       alphas: jnp.ndarray, *,
                       block_t: Optional[int] = None,
                       block_n: Optional[int] = None,
                       backend: Optional[str] = None,
                       policy: Optional[KernelPolicy] = None,
                       interpret: Optional[bool] = None) -> jnp.ndarray:
    """Fused stump-margin + weighted-vote for packed serving batches.

    xsel: (B,T,N) gathered features; thr/pol/alphas: (B,T) -> (B,N).
    Zero-alpha padding rows nullify whatever thr/pol padding holds."""
    return dispatch.dispatch(
        "stump_vote_batched", (xsel, thr, pol, alphas),
        dict(block_t=block_t, block_n=block_n),
        policy=policy, backend=backend, interpret=interpret)


def stump_vote_fp_batched(xsel: jnp.ndarray, thr: jnp.ndarray,
                          pol: jnp.ndarray, alphas: jnp.ndarray, *,
                          block_t: Optional[int] = None,
                          block_n: Optional[int] = None,
                          backend: Optional[str] = None,
                          policy: Optional[KernelPolicy] = None,
                          interpret: Optional[bool] = None):
    """One-launch serving path: fused stump-margin + weighted-vote + xor-fold
    feature fingerprint.

    Same contract as :func:`stump_vote_batched`, returning ``(margins
    (B,N) f32, fp0 (B,N) u32, fp1 (B,N) u32)``.  The fingerprint lanes are
    exact integers, identical across backends, block layouts, and T/N
    padding (zero-alpha rows are the XOR identity), so
    ``serve.engine.BatchEvaluator`` can key its result cache on them
    without re-hashing any feature vector on the host."""
    return dispatch.dispatch(
        "stump_vote_fp_batched", (xsel, thr, pol, alphas),
        dict(block_t=block_t, block_n=block_n),
        policy=policy, backend=backend, interpret=interpret)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    backend: Optional[str] = None,
                    policy: Optional[KernelPolicy] = None,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """q,k,v: (B,H,T,d) -> (B,H,T,d).  Pallas substrates pad d to 128 lanes
    (with a q pre-scale correcting the kernel's 1/sqrt(d_padded))."""
    return dispatch.dispatch(
        "flash_attention", (q, k, v),
        dict(causal=causal, block_q=block_q, block_k=block_k),
        policy=policy, backend=backend, interpret=interpret)


def dist_update(alpha, D, y, h, *, block_n: Optional[int] = None,
                backend: Optional[str] = None,
                policy: Optional[KernelPolicy] = None,
                interpret: Optional[bool] = None):
    """Fused AdaBoost distribution update -> (D_normalized, Z).
    Pallas substrates pad N with zero-mass rows (no contribution to Z)."""
    return dispatch.dispatch(
        "dist_update", (alpha, D, y, h), dict(block_n=block_n),
        policy=policy, backend=backend, interpret=interpret)
