"""Unified kernel-backend dispatch with shape-bucketed autotuning.

Every public kernel entry point in :mod:`repro.kernels.ops` routes through
this module.  Three execution substrates implement the same numerical
contract (asserted against each other in ``tests/test_backend_parity.py``):

* ``interpret`` — the Pallas kernels under the Pallas interpreter.  The
  default off the TPU; refused on it.
* ``mosaic``    — the same Pallas kernels compiled by Mosaic.  TPU only.
* ``xla``       — the pure-jnp oracles from :mod:`repro.kernels.ref`,
  jit-compiled by XLA.  Always available; the fallback of last resort and
  frequently the fastest substrate on CPU.

Backend choice is re-resolved on *every* call (nothing is captured at
construction time — a policy/env change or a TPU hot-attach takes effect on
the next kernel launch), in priority order::

    explicit ``backend=`` argument (or the deprecated ``interpret=`` shim)
    > ``KernelPolicy(backend=...)`` forced policy
    > the ``REPRO_KERNEL_BACKEND`` environment variable
    > the policy's calibration table (per (kernel, shape-bucket) winner)
    > platform default ("mosaic" on TPU, "interpret" elsewhere)

Off the TPU, an unavailable candidate (``mosaic``) falls through to the
next priority with a one-shot RuntimeWarning, so a policy calibrated on one
substrate degrades gracefully on another.  On the TPU nothing falls
through: a candidate that cannot run there (``interpret``, from any level)
raises, so the interpreter never runs on the chip unnoticed.

Shapes are *bucketed* by rounding each dimension up to the block boundary
the padded Pallas call would use — under the kernel's **reference layout**
(``DEFAULT_LAYOUTS``), never the candidate layout under test — so every
raw shape that lowers to the same padded reference kernel shares one
calibration measurement and one entry in the per-(kernel, bucket, backend)
dispatch cache, and every candidate layout of one call shares a single
table entry.

Calibration is a **layout autotune**, not just a backend choice:
``KernelPolicy.calibrate_call`` times each available backend over a small
grid of block layouts (``LAYOUT_GRIDS`` — ``(block_t, block_n)`` for the
vote kernels, ``block_n`` for stump_scan/dist_update, ``(block_n,
block_f)`` for the ragged stump scan, ``(block_q, block_k)`` for flash
attention, following the xformers Triton config-sweep
pattern) and records the ``(winner_backend, winner_layout)`` pair per
(kernel, bucket).  ``dispatch()`` then injects the winning layout kwargs
on every resolved call whose backend matches the winner — explicit caller
layout kwargs still win.  ``save``/``load`` persist the table to JSON
(schema v2; v1 backend-only tables load transparently with empty layouts;
default ``artifacts/backend_calibration.json``) so serving restarts skip
recalibration — see ``benchmarks/backend_matrix.py`` for the one-shot
sweep pass.
"""
from __future__ import annotations

import json
import os
import statistics
import time
import warnings
from pathlib import Path
from typing import (Callable, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import ref
from repro.kernels.dist_update import dist_update_kernel
from repro.kernels.ensemble_vote import (
    ensemble_vote_batched_kernel, stump_vote_batched_kernel,
    stump_vote_fp_batched_kernel)
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.stump_ragged import SMEM_ROWS, ragged_scan_kernel
from repro.kernels.stump_scan import stump_scan_kernel

ENV_VAR = "REPRO_KERNEL_BACKEND"
DEFAULT_CALIBRATION_PATH = "artifacts/backend_calibration.json"
CALIBRATION_SCHEMA_VERSION = 2
# sample rows (slots x block_n) one stump-sweep grid step covers
STUMP_ROWS_PER_STEP = 512

Bucket = Tuple[int, ...]
Layout = Dict[str, int]                 # block-shape kwargs of one launch
LayoutKey = Tuple[Tuple[str, int], ...]  # canonical (sorted items) form

# The block-shape kwargs the autotuner owns.  Any of these passed as None
# by an ops wrapper means "let the calibration table (or the reference
# layout) decide"; an explicit int always wins.
LAYOUT_KWARGS = ("block_t", "block_n", "block_q", "block_k", "block_f")

# Reference layouts: the pre-autotune hardcoded defaults.  Buckets are
# always computed against these (layout-canonical bucketing), and they are
# the fallback layout when the table has no tuned entry for the resolved
# backend.
DEFAULT_LAYOUTS: Dict[str, Layout] = {
    "stump_scan": {"block_n": 256},
    "stump_scan_batched": {"block_n": 256},
    "stump_scan_ragged": {"block_n": 128, "block_f": 128},
    "ensemble_vote": {"block_t": 128, "block_n": 512},
    "ensemble_vote_batched": {"block_t": 128, "block_n": 512},
    "stump_vote_batched": {"block_t": 128, "block_n": 512},
    "stump_vote_fp_batched": {"block_t": 128, "block_n": 512},
    "flash_attention": {"block_q": 128, "block_k": 128},
    "dist_update": {"block_n": 1024},
}

# The sweep grid per kernel (each entry is one complete candidate layout;
# the reference layout is always a member).  Kept small on purpose — the
# xformers Triton sweeps that inspired this stay in the single digits per
# kernel too; a candidate that clamps to the same effective blocks as
# another (small problem sizes) just measures the same launch twice.
_VOTE_GRID = [
    {"block_t": 64, "block_n": 256},
    {"block_t": 128, "block_n": 512},       # reference
    {"block_t": 128, "block_n": 1024},
    {"block_t": 256, "block_n": 2048},
]
LAYOUT_GRIDS: Dict[str, List[Layout]] = {
    "stump_scan": [{"block_n": 128}, {"block_n": 256}, {"block_n": 512},
                   {"block_n": 1024}],
    "stump_scan_batched": [{"block_n": 128}, {"block_n": 256},
                           {"block_n": 512}, {"block_n": 1024}],
    "stump_scan_ragged": [{"block_n": 64, "block_f": 128},
                          {"block_n": 128, "block_f": 128},   # reference
                          {"block_n": 256, "block_f": 128},
                          {"block_n": 128, "block_f": 256}],
    "ensemble_vote": _VOTE_GRID,
    "ensemble_vote_batched": _VOTE_GRID,
    "stump_vote_batched": _VOTE_GRID,
    "stump_vote_fp_batched": _VOTE_GRID,
    "flash_attention": [{"block_q": 64, "block_k": 64},
                        {"block_q": 128, "block_k": 128},   # reference
                        {"block_q": 128, "block_k": 256},
                        {"block_q": 256, "block_k": 256}],
    "dist_update": [{"block_n": 512}, {"block_n": 1024}, {"block_n": 2048},
                    {"block_n": 4096}],
}


def layout_key(layout) -> LayoutKey:
    """Canonical hashable form of a layout (dict or item tuple -> sorted
    ``((kwarg, int), ...)``)."""
    if not layout:
        return ()
    items = layout.items() if isinstance(layout, dict) else layout
    return tuple(sorted((str(k), int(v)) for k, v in items))


def layout_label(layout) -> str:
    """Render a layout for logs/metrics ("block_n=512,block_t=128")."""
    items = layout if isinstance(layout, tuple) else layout_key(layout)
    return ",".join(f"{k}={v}" for k, v in items) or "-"


class CalEntry(NamedTuple):
    """One calibration-table value: the winning backend and its layout."""
    backend: str
    layout: LayoutKey = ()


def _entry(value) -> "CalEntry":
    """Normalize a calibration-table value to :class:`CalEntry`.

    Accepts a bare backend name (the v1 / pre-layout form), a CalEntry, a
    ``(backend, layout)`` pair, or a ``{"backend": ..., "layout": ...}``
    dict — so v1 tables, hand-written test tables, and serialized v2
    entries all coexist."""
    if isinstance(value, CalEntry):
        return CalEntry(canonical(value.backend), layout_key(value.layout))
    if isinstance(value, str):
        return CalEntry(canonical(value))
    if isinstance(value, dict):
        return CalEntry(canonical(value["backend"]),
                        layout_key(value.get("layout")))
    backend, layout = value
    return CalEntry(canonical(backend), layout_key(layout))


# ---------------------------------------------------------------------------
# shared shape helpers (the single home of the padding boilerplate that used
# to be copy-pasted across every ops.py wrapper)
# ---------------------------------------------------------------------------

def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pad_to(x: jnp.ndarray, axis: int, mult: int, value=0.0) -> jnp.ndarray:
    """Pad ``axis`` up to the next multiple of ``mult`` with ``value``."""
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def ceil_to(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def next_pow2(n: int) -> int:
    return 1 << (max(int(n), 1) - 1).bit_length()


def vote_blocks(T: int, N: int, block_t: int, block_n: int) -> Tuple[int, int]:
    """Effective (block_t, block_n) for the vote kernels: shrink to the next
    power of two covering the problem so tiny ensembles don't pad to 128."""
    bt = min(block_t, max(8, next_pow2(T)))
    bn = min(block_n, max(128, next_pow2(N)))
    return bt, bn


def _largest_divisor_leq(n: int, cap: int) -> int:
    """Largest divisor of ``n`` that is <= ``cap`` (>= 1)."""
    for d in range(min(int(cap), int(n)), 0, -1):
        if n % d == 0:
            return d
    return 1


def _flash_blocks(T: int, block_q: int, block_k: int) -> Tuple[int, int]:
    # largest divisor of T at or under the requested block, so ragged
    # sequence lengths still tile (T=192 with block_q=128 runs 96-tiled,
    # not as one untiled T-slab)
    return (_largest_divisor_leq(T, block_q), _largest_divisor_leq(T, block_k))


# ---------------------------------------------------------------------------
# Pallas substrate: pad to hardware-aligned blocks, launch, slice back
# ---------------------------------------------------------------------------

def _pallas_stump_scan_batched(x, y, w, thresholds, *, block_n=256,
                               interpret=True):
    # pad N with zero-weight rows and B with zero-weight slots (neither
    # contributes); block_n shrinks to the next power of two covering N so
    # fleet batches of tiny shards don't pad 64x, and several slots share
    # one grid step so a step holds about STUMP_ROWS_PER_STEP sample rows
    B, N, F = x.shape
    bn = min(block_n, max(8, next_pow2(N)))
    bb = min(next_pow2(B), max(1, STUMP_ROWS_PER_STEP // bn))
    xp = pad_to(pad_to(x, 1, bn), 0, bb)
    yp = pad_to(pad_to(y, 1, bn, value=1.0), 0, bb, value=1.0)
    wp = pad_to(pad_to(w, 1, bn), 0, bb)
    tp = pad_to(jnp.swapaxes(thresholds, 1, 2), 0, bb)
    err = stump_scan_kernel(xp, yp[..., None], wp[..., None], tp,
                            block_b=bb, block_n=bn, interpret=interpret)
    return jnp.swapaxes(err, 1, 2)[:B]


def ragged_tables(seg, n_clients: int, block_n: int, block_c: int):
    """The ragged scan's per-row-block tables, from each row's client
    (non-decreasing; rows past the last client are padding): the client
    block of each row block's first row, and the range of clients whose
    last row lies in each row block (a client without rows takes the
    last row before it, so folds once, after the clients below it)."""
    nrb = seg.shape[0] // block_n
    kk = seg[::block_n] >> (block_c.bit_length() - 1)
    seg = seg[:nrb * block_n]
    last = jnp.searchsorted(seg, jnp.arange(n_clients, dtype=seg.dtype),
                            side="right") - 1
    blocks = jnp.arange(nrb, dtype=jnp.int32)
    fold = (last // block_n).astype(jnp.int32)
    lo = jnp.searchsorted(fold, blocks, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(fold, blocks, side="right").astype(jnp.int32)
    return kk.astype(jnp.int32), lo, hi


def _pallas_stump_scan_ragged(x, y, w, seg, thresholds, *, block_n=128,
                              block_f=128, interpret=True):
    # each row's weight on the side it misses: a when x > thr (the +1
    # side; a miss unless y = +1), b otherwise; padding rows, appended
    # to whole SMEM blocks (x itself is not copied), add 0 to the last
    # client
    B = thresholds.shape[0]
    bc = next_pow2(max(block_n, 8))
    sb = max(block_n, SMEM_ROWS)
    s = pad_to(jnp.minimum(seg, B - 1).astype(jnp.int32), 0, sb,
               value=B - 1)
    a = pad_to(jnp.where(y == 1.0, 0.0, w).astype(jnp.float32), 0, sb)
    b = pad_to(jnp.where(y == -1.0, 0.0, w).astype(jnp.float32), 0, sb)
    kk, lo, hi = ragged_tables(s[:ceil_to(x.shape[0], block_n)], B,
                               block_n, bc)
    out = ragged_scan_kernel(x, s, a, b, thresholds, kk, lo, hi,
                             block_n=block_n, block_f=block_f, block_c=bc,
                             interpret=interpret)
    return jnp.swapaxes(out, 0, 1).reshape(6, -1)[:, :B]


def _pallas_stump_scan(x, y, w, thresholds, *, block_n=256, interpret=True):
    # the B = 1 case of the batched sweep
    return _pallas_stump_scan_batched(
        x[None], y[None], w[None], thresholds[None], block_n=block_n,
        interpret=interpret)[0]


def _pallas_ensemble_vote_batched(margins, alphas, *, block_t=128,
                                  block_n=512, interpret=True):
    # pad T with zero-alpha rows and N with dummy columns (sliced off)
    B, T, N = margins.shape
    bt, bn = vote_blocks(T, N, block_t, block_n)
    mp = pad_to(pad_to(margins, 1, bt), 2, bn)
    ap = pad_to(alphas, 1, bt, value=0.0)
    out = ensemble_vote_batched_kernel(mp, ap[..., None], block_t=bt,
                                       block_n=bn, interpret=interpret)
    return out[:, 0, :N]


def _pallas_ensemble_vote(margins, alphas, *, block_t=128, block_n=512,
                          interpret=True):
    # the one-tenant case of the batched vote
    return _pallas_ensemble_vote_batched(
        margins[None], alphas[None], block_t=block_t, block_n=block_n,
        interpret=interpret)[0]


def _stump_vote_operands(xsel, thr, pol, alphas, block_t, block_n):
    # zero-alpha padding rows nullify whatever thr/pol padding holds, in
    # the vote and in the alpha-gated fingerprint alike
    B, T, N = xsel.shape
    bt, bn = vote_blocks(T, N, block_t, block_n)
    xp = pad_to(pad_to(xsel, 1, bt), 2, bn)
    cols = [pad_to(v, 1, bt, value=fill)[..., None]
            for v, fill in ((thr, 0.0), (pol, 1.0), (alphas, 0.0))]
    return (xp, *cols), dict(block_t=bt, block_n=bn)


def _pallas_stump_vote_batched(xsel, thr, pol, alphas, *, block_t=128,
                               block_n=512, interpret=True):
    args, blocks = _stump_vote_operands(xsel, thr, pol, alphas, block_t,
                                        block_n)
    out = stump_vote_batched_kernel(*args, interpret=interpret, **blocks)
    return out[:, 0, :xsel.shape[2]]


def _pallas_stump_vote_fp_batched(xsel, thr, pol, alphas, *, block_t=128,
                                  block_n=512, interpret=True):
    args, blocks = _stump_vote_operands(xsel, thr, pol, alphas, block_t,
                                        block_n)
    N = xsel.shape[2]
    return tuple(o[:, 0, :N] for o in stump_vote_fp_batched_kernel(
        *args, interpret=interpret, **blocks))


def _pallas_flash_attention(q, k, v, *, causal=True, block_q=128,
                            block_k=128, interpret=True):
    B, H, T, d = q.shape
    bq, bk = _flash_blocks(T, block_q, block_k)
    qf = q.reshape(B * H, T, d)
    kf = k.reshape(B * H, T, d)
    vf = v.reshape(B * H, T, d)
    dp = (-d) % 128
    if dp:
        # zero-pad head_dim: extra lanes contribute 0 to q.k and to output
        qf = pad_to(qf, 2, 128)
        kf = pad_to(kf, 2, 128)
        vf = pad_to(vf, 2, 128)
        # the kernel scales by 1/sqrt(d_padded); pre-scale q so the
        # effective scale reflects the true head_dim
        qf = qf * (((d + dp) ** 0.5) / (d ** 0.5))
    out = flash_attention_kernel(
        qf, kf, vf, causal=causal, block_q=bq, block_k=bk,
        interpret=interpret)
    out = out[..., :d]
    return out.reshape(B, H, T, d)


def _pallas_dist_update(alpha, D, y, h, *, block_n=1024, interpret=True):
    # pad N with zero-mass rows (no contribution to Z)
    N = D.shape[0]
    bn = min(block_n, max(256, next_pow2(N)))
    Dp = pad_to(D, 0, bn, value=0.0)
    yp = pad_to(y, 0, bn, value=1.0)
    hp = pad_to(h, 0, bn, value=0.0)
    w, Z = dist_update_kernel(jnp.asarray(alpha, jnp.float32), Dp, yp, hp,
                              block_n=bn, interpret=interpret)
    return (w / (Z[0] + 1e-30))[:N], Z[0]


_PALLAS_IMPLS: Dict[str, Callable] = {
    "stump_scan": _pallas_stump_scan,
    "stump_scan_batched": _pallas_stump_scan_batched,
    "stump_scan_ragged": _pallas_stump_scan_ragged,
    "ensemble_vote": _pallas_ensemble_vote,
    "ensemble_vote_batched": _pallas_ensemble_vote_batched,
    "stump_vote_batched": _pallas_stump_vote_batched,
    "stump_vote_fp_batched": _pallas_stump_vote_fp_batched,
    "flash_attention": _pallas_flash_attention,
    "dist_update": _pallas_dist_update,
}


# ---------------------------------------------------------------------------
# XLA substrate: the ref.py oracles on the raw (unpadded) inputs,
# jit-compiled so the fallback path is a real compiled alternative (not an
# eager op-by-op walk) — what calibration then measures and persists
# ---------------------------------------------------------------------------

_jit_stump_scan_ref = jax.jit(ref.stump_scan_ref)
_jit_stump_scan_batched_ref = jax.jit(ref.stump_scan_batched_ref)
_jit_stump_scan_ragged_ref = jax.jit(ref.stump_scan_ragged_ref)
_jit_ensemble_vote_ref = jax.jit(ref.ensemble_vote_ref)
_jit_ensemble_vote_batched_ref = jax.jit(ref.ensemble_vote_batched_ref)
_jit_stump_vote_batched_ref = jax.jit(ref.stump_vote_batched_ref)
_jit_stump_vote_fp_batched_ref = jax.jit(ref.stump_vote_fp_batched_ref)
_jit_flash_attention_ref = jax.jit(ref.flash_attention_ref,
                                   static_argnames=("causal",))
_jit_dist_update_ref = jax.jit(ref.dist_update_ref)

_XLA_IMPLS: Dict[str, Callable] = {
    "stump_scan":
        lambda x, y, w, thr, **_: _jit_stump_scan_ref(x, y, w, thr),
    "stump_scan_batched":
        lambda x, y, w, thr, **_: _jit_stump_scan_batched_ref(x, y, w, thr),
    "stump_scan_ragged":
        lambda x, y, w, seg, thr, **_:
            _jit_stump_scan_ragged_ref(x, y, w, seg, thr),
    "ensemble_vote":
        lambda m, a, **_: _jit_ensemble_vote_ref(m, a),
    "ensemble_vote_batched":
        lambda m, a, **_: _jit_ensemble_vote_batched_ref(m, a),
    "stump_vote_batched":
        lambda x, t, p, a, **_: _jit_stump_vote_batched_ref(x, t, p, a),
    "stump_vote_fp_batched":
        lambda x, t, p, a, **_: _jit_stump_vote_fp_batched_ref(x, t, p, a),
    "flash_attention":
        lambda q, k, v, *, causal=True, **_:
            _jit_flash_attention_ref(q, k, v, causal=causal),
    "dist_update":
        lambda alpha, D, y, h, **_: _jit_dist_update_ref(alpha, D, y, h),
}

KERNELS: Tuple[str, ...] = tuple(_PALLAS_IMPLS)


# ---------------------------------------------------------------------------
# shape buckets: round every call up to the padded shape it lowers to under
# the kernel's *reference* layout (DEFAULT_LAYOUTS) — never the candidate
# layout under test — so calls sharing one compiled reference kernel share
# one calibration/dispatch entry and every candidate layout of one call
# maps to the same table entry (layout-canonical bucketing)
# ---------------------------------------------------------------------------

def _bucket_stump_scan(x, y, w, thresholds, **_):
    N, F = x.shape
    T = thresholds.shape[1]
    return (ceil_to(N, 256), ceil_to(F, 8), ceil_to(T, 8))


def _bucket_stump_scan_batched(x, y, w, thresholds, **_):
    B, N, F = x.shape
    T = thresholds.shape[2]
    bn = min(256, max(8, next_pow2(N)))
    return (next_pow2(B), ceil_to(N, bn), ceil_to(F, 8), ceil_to(T, 8))


def _bucket_stump_scan_ragged(x, y, w, seg, thresholds, **_):
    R, F = x.shape
    B, T, _ = thresholds.shape
    return (ceil_to(R, 128), ceil_to(B, 128), ceil_to(F, 128), T)


def _bucket_ensemble_vote(margins, alphas, **_):
    T, N = margins.shape
    bt, bn = vote_blocks(T, N, 128, 512)
    return (ceil_to(T, bt), ceil_to(N, bn))


def _bucket_vote_batched(margins, alphas, **_):
    B, T, N = margins.shape
    bt, bn = vote_blocks(T, N, 128, 512)
    return (next_pow2(B), ceil_to(T, bt), ceil_to(N, bn))


def _bucket_stump_vote_batched(xsel, thr, pol, alphas, **_):
    return _bucket_vote_batched(xsel, alphas)


def _bucket_flash_attention(q, k, v, **_):
    B, H, T, d = q.shape
    bq, bk = _flash_blocks(T, 128, 128)
    return (next_pow2(B * H), ceil_to(T, bq), ceil_to(d, 128))


def _bucket_dist_update(alpha, D, y, h, **_):
    N = D.shape[0]
    bn = min(1024, max(256, next_pow2(N)))
    return (ceil_to(N, bn),)


_BUCKETERS: Dict[str, Callable[..., Bucket]] = {
    "stump_scan": _bucket_stump_scan,
    "stump_scan_batched": _bucket_stump_scan_batched,
    "stump_scan_ragged": _bucket_stump_scan_ragged,
    "ensemble_vote": _bucket_ensemble_vote,
    "ensemble_vote_batched": _bucket_vote_batched,
    "stump_vote_batched": _bucket_stump_vote_batched,
    "stump_vote_fp_batched": _bucket_stump_vote_batched,
    "flash_attention": _bucket_flash_attention,
    "dist_update": _bucket_dist_update,
}


def bucket_of(kernel: str, args: Sequence, kwargs: Optional[dict] = None
              ) -> Bucket:
    """The shape bucket one call lowers to (its padded kernel shape)."""
    return _BUCKETERS[kernel](*args, **(kwargs or {}))


# ---------------------------------------------------------------------------
# backends
# ---------------------------------------------------------------------------

class PallasInterpretBackend:
    """Pallas kernels under the interpreter — off the TPU only."""
    name = "interpret"

    def available(self) -> bool:
        return not on_tpu()

    def run(self, kernel: str, *args, **kwargs):
        return _PALLAS_IMPLS[kernel](*args, interpret=True, **kwargs)


class PallasMosaicBackend:
    """Pallas kernels compiled by Mosaic — TPU only."""
    name = "mosaic"

    def available(self) -> bool:
        return on_tpu()

    def run(self, kernel: str, *args, **kwargs):
        return _PALLAS_IMPLS[kernel](*args, interpret=False, **kwargs)


class XlaRefBackend:
    """The jnp oracles, jit-compiled by XLA — the universal fallback."""
    name = "xla"

    def available(self) -> bool:
        return True

    def run(self, kernel: str, *args, **kwargs):
        return _XLA_IMPLS[kernel](*args, **kwargs)


BACKENDS: Dict[str, object] = {b.name: b for b in (
    PallasInterpretBackend(), PallasMosaicBackend(), XlaRefBackend())}

_ALIASES = {"pallas": "interpret", "pallas_interpret": "interpret",
            "pallas_mosaic": "mosaic", "tpu": "mosaic",
            "ref": "xla", "jnp": "xla", "fallback": "xla"}


def canonical(name: str) -> str:
    key = str(name).strip().lower()
    key = _ALIASES.get(key, key)
    if key not in BACKENDS:
        raise KeyError(
            f"unknown kernel backend {name!r}: expected one of "
            f"{sorted(BACKENDS)} (or aliases {sorted(_ALIASES)})")
    return key


def platform_default() -> str:
    return "mosaic" if on_tpu() else "interpret"


def available_backends() -> List[str]:
    return [n for n, b in sorted(BACKENDS.items()) if b.available()]


# ---------------------------------------------------------------------------
# policy
# ---------------------------------------------------------------------------

class KernelPolicy:
    """Per-call backend + layout selection with a shape-bucketed
    calibration table.

    ``backend=`` forces one backend policy-wide (still subject to
    availability).  ``table`` maps ``(kernel, bucket) -> CalEntry`` (bare
    backend-name values are accepted and normalized to layout-less
    entries) — normally filled by :meth:`calibrate_call` or loaded from
    the JSON written by ``benchmarks/backend_matrix.py``.  Backend
    resolution consults, in order: the per-call explicit argument, the
    forced ``backend``, the ``env_var`` environment variable (read on
    every call), the calibration table, then the platform default.  When
    the resolved backend matches a table entry's winner, :func:`dispatch`
    additionally injects the entry's tuned block layout (explicit caller
    layout kwargs always win).

    ``fused_fingerprint`` opts a serving tenant into the one-launch
    ``stump_vote_fp_batched`` path (`serve/engine.py`); the dispatcher
    itself ignores it.

    ``choices`` records the backend actually dispatched per (kernel,
    bucket) and ``layout_choices`` the injected layout; the internal
    dispatch cache is keyed on the full resolution input (including the
    live env value) so repeated same-bucket calls skip re-resolution
    without ever pinning a stale choice.
    """

    def __init__(self, backend: Optional[str] = None,
                 table: Optional[Dict[Tuple[str, Bucket], object]] = None,
                 env_var: Optional[str] = ENV_VAR,
                 fused_fingerprint: bool = False):
        self.backend = canonical(backend) if backend is not None else None
        self.table: Dict[Tuple[str, Bucket], CalEntry] = {}
        for (kern, bucket), value in (table or {}).items():
            self.table[(kern, tuple(bucket))] = _entry(value)
        self.env_var = env_var
        self.fused_fingerprint = bool(fused_fingerprint)
        self.choices: Dict[Tuple[str, Bucket], str] = {}
        self.layout_choices: Dict[Tuple[str, Bucket], Layout] = {}
        self.cache_hits = 0
        self._cache: Dict[tuple, object] = {}
        self._warned: set = set()
        # platform the loaded calibration table was measured on (None for
        # in-process tables; set by load())
        self.measured_on: Optional[str] = None

    # ------------------------------------------------------------ resolve
    def _env_backend(self) -> Optional[str]:
        if not self.env_var:
            return None
        return os.environ.get(self.env_var) or None

    def resolve_name(self, kernel: str, bucket: Bucket, *,
                     explicit: Optional[str] = None) -> str:
        """Backend name for one (kernel, bucket) call.  Off the TPU a
        candidate whose substrate is unavailable is skipped with a warning;
        on the TPU it raises."""
        bucket = tuple(bucket)
        entry = self.table.get((kernel, bucket))
        for cand in (explicit, self.backend, self._env_backend(),
                     entry.backend if entry is not None else None):
            if cand is None:
                continue
            name = canonical(cand)
            if BACKENDS[name].available():
                return name
            if on_tpu():
                raise RuntimeError(
                    f"kernel backend '{name}' cannot run on the TPU "
                    f"({kernel} {bucket_label(bucket)}); choose 'mosaic' or "
                    f"'xla', or unset {ENV_VAR}")
            if name not in self._warned:
                self._warned.add(name)
                warnings.warn(
                    f"kernel backend '{name}' is unavailable on "
                    f"'{jax.default_backend()}'; falling back",
                    RuntimeWarning, stacklevel=3)
        return platform_default()

    def resolve(self, kernel: str, bucket: Bucket, *,
                explicit: Optional[str] = None):
        """Backend object for one call, via the dispatch cache.  The key
        includes every resolution input — the live env value *and* the
        platform — so an env change or TPU hot-attach is never masked by
        a stale cached choice."""
        bucket = tuple(bucket)
        key = (kernel, bucket, explicit, self.backend, self._env_backend(),
               jax.default_backend())
        hit = self._cache.get(key)
        if hit is None:
            hit = BACKENDS[self.resolve_name(kernel, bucket,
                                             explicit=explicit)]
            self._cache[key] = hit
        else:
            self.cache_hits += 1
        self.choices[(kernel, bucket)] = hit.name
        return hit

    # ------------------------------------------------------------- layout
    def layout_for(self, kernel: str, bucket: Bucket, backend: str
                   ) -> Layout:
        """The tuned block layout for one (kernel, bucket) — only if the
        table's winning backend matches the one actually resolved (a tuned
        layout measured for one substrate says nothing about another)."""
        entry = self.table.get((kernel, tuple(bucket)))
        if entry is not None and entry.backend == backend and entry.layout:
            return dict(entry.layout)
        return {}

    # -------------------------------------------------------- calibration
    def record(self, kernel: str, bucket: Bucket, backend: str,
               layout: Optional[Layout] = None) -> None:
        self.table[(kernel, tuple(bucket))] = CalEntry(canonical(backend),
                                                       layout_key(layout))
        self._cache.clear()

    def calibrate_call(self, kernel: str, *args, reps: int = 5,
                       backends: Optional[Sequence[str]] = None,
                       layouts: Optional[Sequence[Layout]] = None, **kwargs
                       ) -> Tuple[Bucket, Dict[Tuple[str, LayoutKey],
                                               List[float]]]:
        """Time every available backend over the kernel's layout grid (one
        compile/warm-up launch per candidate, then ``reps`` timed
        launches), record the ``(backend, layout)`` median winner for the
        call's bucket, and return ``(bucket, {(backend, layout_key):
        [seconds]})``.

        Pallas backends sweep ``layouts`` (default: the kernel's
        ``LAYOUT_GRIDS`` entry); the ``xla`` oracle has no block layout
        and is measured once with an empty layout."""
        bucket = bucket_of(kernel, args, kwargs)
        base = {k: v for k, v in kwargs.items()
                if k not in LAYOUT_KWARGS and v is not None}
        samples: Dict[Tuple[str, LayoutKey], List[float]] = {}
        for name in (backends if backends is not None else sorted(BACKENDS)):
            be = BACKENDS[canonical(name)]
            if not be.available():
                continue
            if be.name == "xla":
                grid: Sequence[Layout] = [{}]
            elif layouts is not None:
                grid = list(layouts)
            else:
                grid = LAYOUT_GRIDS.get(kernel, [{}])
            for layout in grid:
                call_kwargs = dict(base, **layout)
                jax.block_until_ready(be.run(kernel, *args, **call_kwargs))
                ts = []
                for _ in range(reps):
                    t0 = time.perf_counter()
                    jax.block_until_ready(
                        be.run(kernel, *args, **call_kwargs))
                    ts.append(time.perf_counter() - t0)
                samples[(be.name, layout_key(layout))] = ts
        if not samples:
            raise ValueError(
                f"no backend to calibrate {kernel!r}: none of "
                f"{list(backends) if backends is not None else sorted(BACKENDS)} "
                f"is available on '{jax.default_backend()}' "
                f"(available: {available_backends()})")
        wname, wlayout = min(
            samples, key=lambda k: statistics.median(samples[k]))
        self.record(kernel, bucket, wname, dict(wlayout))
        return bucket, samples

    # -------------------------------------------------------- persistence
    def save(self, path: str = DEFAULT_CALIBRATION_PATH,
             measured_on: Optional[str] = None) -> str:
        """Persist the calibration table (JSON, schema v2: every entry
        carries its winning backend *and* block layout, and the table
        records the platform it was measured on) so restarts skip
        recalibration; returns the path written."""
        p = Path(path)
        p.parent.mkdir(parents=True, exist_ok=True)
        data = {
            "version": CALIBRATION_SCHEMA_VERSION,
            "env_var": self.env_var,
            "backend": self.backend,
            "measured_on": (measured_on if measured_on is not None
                            else jax.default_backend()),
            "table": [{"kernel": k, "bucket": list(b), "backend": e.backend,
                       "layout": dict(e.layout)}
                      for (k, b), e in sorted(self.table.items())],
        }
        p.write_text(json.dumps(data, indent=2) + "\n")
        return str(p)

    @classmethod
    def load(cls, path: str = DEFAULT_CALIBRATION_PATH) -> "KernelPolicy":
        """Load a persisted table.  Schema v1 (backend-only entries, no
        ``version`` field) loads transparently with empty layouts — the
        reference ``DEFAULT_LAYOUTS`` then apply at dispatch time.  A
        non-empty table measured on a different platform raises: its
        winners say nothing about this substrate — re-run
        benchmarks.backend_matrix here to re-measure."""
        data = json.loads(Path(path).read_text())
        version = int(data.get("version", 1))
        if version > CALIBRATION_SCHEMA_VERSION:
            raise ValueError(
                f"calibration table {path!r} has schema v{version}; this "
                f"build reads up to v{CALIBRATION_SCHEMA_VERSION}")
        measured_on = data.get("measured_on")
        platform = jax.default_backend()
        if measured_on and measured_on != platform and data.get("table"):
            raise ValueError(
                f"calibration table {path!r} was measured on "
                f"'{measured_on}' but this process runs on '{platform}'; "
                f"re-run `python -m benchmarks.backend_matrix` on this "
                f"platform to re-measure")
        pol = cls(backend=data.get("backend"),
                  table={(e["kernel"], tuple(e["bucket"])):
                         CalEntry(canonical(e["backend"]),
                                  layout_key(e.get("layout")))
                         for e in data.get("table", [])},
                  env_var=data.get("env_var", ENV_VAR))
        pol.measured_on = measured_on
        return pol


_DEFAULT_POLICY = KernelPolicy()


def default_policy() -> KernelPolicy:
    """The process-wide policy used when no ``policy=`` is passed."""
    return _DEFAULT_POLICY


def set_default_policy(policy: KernelPolicy) -> KernelPolicy:
    """Swap the process-wide default policy; returns the previous one."""
    global _DEFAULT_POLICY
    old, _DEFAULT_POLICY = _DEFAULT_POLICY, policy
    return old


# ---------------------------------------------------------------------------
# dispatch entry (the single funnel behind every ops.py wrapper)
# ---------------------------------------------------------------------------

def _with_layout(kernel: str, kwargs: dict, pol: "KernelPolicy",
                 bucket: Bucket, backend_name: str) -> dict:
    """Resolve the block layout for one call: explicit caller kwargs win
    over the calibration table's tuned layout, which wins over the
    reference ``DEFAULT_LAYOUTS``.  ``None`` layout kwargs (the ops
    wrappers' "let the table decide" default) are stripped."""
    kwargs = dict(kwargs)
    explicit: Layout = {}
    for k in LAYOUT_KWARGS:
        if k in kwargs:
            v = kwargs.pop(k)
            if v is not None:
                explicit[k] = int(v)
    layout = dict(DEFAULT_LAYOUTS.get(kernel, {}))
    layout.update(pol.layout_for(kernel, bucket, backend_name))
    layout.update(explicit)
    kwargs.update(layout)
    pol.layout_choices[(kernel, tuple(bucket))] = layout
    return kwargs


def dispatch(kernel: str, args: Sequence, kwargs: Optional[dict] = None, *,
             policy: Optional[KernelPolicy] = None,
             backend: Optional[str] = None,
             interpret: Optional[bool] = None):
    """Resolve a backend + block layout for this call and run it.

    ``interpret`` is the deprecated bool shim: True maps to the
    ``interpret`` backend, False to ``mosaic`` (which falls back to the
    platform default where Mosaic is unavailable).
    """
    kwargs = dict(kwargs or {})
    if interpret is not None:
        warnings.warn(
            "interpret= is deprecated; pass backend='interpret'/'mosaic'/"
            "'xla' or a KernelPolicy", DeprecationWarning, stacklevel=3)
        if backend is None:
            backend = "interpret" if interpret else "mosaic"
    pol = policy if policy is not None else _DEFAULT_POLICY
    bucket = bucket_of(kernel, args, kwargs)
    be = pol.resolve(kernel, bucket, explicit=backend)
    kwargs = _with_layout(kernel, kwargs, pol, bucket, be.name)
    if not obs.profiling_enabled():
        return be.run(kernel, *args, **kwargs)
    # profiling path: timing a launch requires blocking on the device, so
    # this only runs while obs profiling is switched on
    blabel = bucket_label(bucket)
    with obs.span(f"kernel.{kernel}", backend=be.name, bucket=blabel):
        t0 = time.perf_counter()
        out = jax.block_until_ready(be.run(kernel, *args, **kwargs))
        dt = time.perf_counter() - t0
    reg = obs.get_registry()
    labels = dict(kernel=kernel, bucket=blabel, backend=be.name)
    # the first profiled launch of a (kernel, bucket, backend) pays jit
    # trace/compile inside the blocked region — keep it out of the
    # steady-state wall_s histogram (calibration_check reads p50s there)
    seen = getattr(reg, "_kernel_seen", None)
    if seen is None:
        seen = set()
        setattr(reg, "_kernel_seen", seen)
    first = (kernel, blabel, be.name) not in seen
    seen.add((kernel, blabel, be.name))
    reg.counter("kernel.launches", **labels).inc()
    if first:
        reg.histogram("kernel.compile_s", **labels).observe(dt)
    else:
        reg.histogram("kernel.wall_s", **labels).observe(dt)
    return out


def bucket_label(bucket: Bucket) -> str:
    """Render a shape bucket as a metrics label ("256x8x8")."""
    return "x".join(str(int(d)) for d in bucket)


def calibration_check(policy: Optional[KernelPolicy] = None,
                      registry=None, *, min_count: int = 5
                      ) -> List[Dict[str, object]]:
    """Sanity-check the calibration table against *observed* launch timings.

    For every (kernel, bucket) the policy has a calibrated winner for,
    compare the winner's observed p50 wall time (from the
    ``kernel.wall_s{kernel,bucket,backend}`` histograms that profiled
    dispatches record; first-launch compile times land in
    ``kernel.compile_s`` and never skew this) against every other backend
    observed on the same bucket.  Backends with fewer than ``min_count``
    steady-state observations are ignored entirely — a single stray
    sample must not outvote a calibrated winner.  Returns one flag dict
    per entry where a non-winner was measurably faster (including the
    per-backend observation ``counts``) — i.e. the persisted calibration
    no longer matches live behavior and a recalibration pass is
    warranted.  Entries with no cross-backend observations are skipped,
    not flagged."""
    pol = policy if policy is not None else _DEFAULT_POLICY
    reg = registry if registry is not None else obs.get_registry()
    min_count = max(1, int(min_count))
    observed: Dict[Tuple[str, str], Dict[str, object]] = {}
    for name, labels, h in reg.histograms():
        if name != "kernel.wall_s" or h.count < min_count:
            continue
        key = (labels.get("kernel", ""), labels.get("bucket", ""))
        observed.setdefault(key, {})[labels.get("backend", "")] = h
    flags: List[Dict[str, object]] = []
    for (kern, bucket), entry in sorted(pol.table.items()):
        winner = entry.backend
        hists = observed.get((kern, bucket_label(bucket)))
        if not hists or winner not in hists or len(hists) < 2:
            continue
        best = min(hists, key=lambda b: hists[b].p50)
        if best != winner and hists[best].p50 < hists[winner].p50:
            flags.append({
                "kernel": kern, "bucket": bucket_label(bucket),
                "calibrated": winner,
                "calibrated_p50_s": hists[winner].p50,
                "observed_best": best,
                "observed_best_p50_s": hists[best].p50,
                "counts": {b: hists[b].count for b in sorted(hists)},
            })
    return flags
