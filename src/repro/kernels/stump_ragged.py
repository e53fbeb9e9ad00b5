"""Pallas TPU kernel: the ragged stump scan — every client's best stump
over packed rows, without a per-client error grid.

The batched scan (``stump_scan.py``) needs every client padded to the
fleet's largest shard.  A heavy-tailed fleet (most clients holding one or
two rows, a few a hundred) would launch mostly padding that way, so here
the rows of all clients sit back to back, each tagged with its client
(``seg``, non-decreasing), and each client's (T, F) thresholds come from a
``(B, T, Fp)`` grid (Fp >= F: lanes past the last feature are not
scanned).  The launch grid walks feature tiles (outer) and row
blocks (inner):

* a row block of ``block_n`` rows holds the rows of several clients;
  because every client holds at least one row, those clients lie within
  ``block_n`` of the block's first, so two aligned ``(block_c, T,
  block_f)`` slices of the grid (client blocks ``kk`` and ``kk + 1``)
  hold all their thresholds;
* each row adds its weight, on the side it misses, into its client's
  ``(T, block_f)`` accumulator (a two-slot ring over client blocks), in
  row order: every sum is exact float32 on the VPU;
* once a client's last row is in, its tile is folded into the client's
  running best of each polarity (least error, then lowest flat index
  ``f * T + t`` — ``_pick_stump``'s tie rule, since tiles arrive in
  feature order and a later tile wins only when strictly better), and its
  accumulator is cleared for the next client of that slot.

No ``(B, T, F)`` error grid exists; the output is six numbers a client
(the fold reads the winning threshold from the grid slice it already
holds, so the caller gathers nothing from the grid).
Rows that are padding carry zero weight and add exactly nothing; lanes
past the last feature and slots past the last client are garbage that
the fold masks or the caller slices off.

    best[:, b] = (min err+, argmin err+, its threshold,
                  min err-, argmin err-, its threshold)
    err+[f, t] = sum_{i in b} w_i [sign(x_if - thr[b, t, f]) != y_i],
    err- = 1 - err+, argmins over the flat index f * T + t.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# a flat index no stump reaches; flat indices are carried as float32,
# exact below 2**24
NO_INDEX = float(2 ** 24)
LANES = 128
# rows per block of the per-row scalars in SMEM: XLA tiles a 1-D int32 or
# float32 array of 1024 or more elements in 1024s, and a Mosaic block
# has to match that tiling
SMEM_ROWS = 1024


def _min_all(v):
    """Least element of a 2-D tile, as a (1, 1) array."""
    return jnp.min(jnp.min(v, axis=1, keepdims=True), axis=0, keepdims=True)


def _ragged_kernel(kk_ref, lo_ref, hi_ref, seg_ref, a_ref, b_ref, x_ref,
                   ga_ref, gb_ref, out_ref, acc_ref, *, n_features: int,
                   block_c: int):
    j, i = pl.program_id(0), pl.program_id(1)
    _, T, bf = ga_ref.shape
    bn = x_ref.shape[0]
    shift = block_c.bit_length() - 1

    @pl.when((j == 0) & (i == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        row = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
        out_ref[...] = jnp.where(row % 3 == 0, jnp.inf, 0.0)

    kk = kk_ref[i]
    off = (i % (seg_ref.shape[0] // bn)) * bn     # this block in the SMEM one

    def rows(g, carry):
        base = pl.multiple_of(g * 8, 8)
        xg = x_ref[pl.ds(base, 8), :]                       # (8, bf)
        for k in range(8):
            r = off + base + k
            s = seg_ref[r]
            blk = s >> shift
            l = s & (block_c - 1)
            thr = jnp.where(blk == kk, ga_ref[l], gb_ref[l])  # (T, bf)
            add = jnp.where(xg[k:k + 1, :] > thr, a_ref[r], b_ref[r])
            acc_ref[blk & 1, l] += add
        return carry

    jax.lax.fori_loop(0, bn // 8, rows, 0)

    feat = j * bf + jax.lax.broadcasted_iota(jnp.int32, (T, bf), 1)
    flat = (feat * T + jax.lax.broadcasted_iota(jnp.int32, (T, bf), 0)
            ).astype(jnp.float32)
    valid = feat < n_features
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def fold(c, carry):
        blk = c >> shift
        l = c & (block_c - 1)
        e = acc_ref[blk & 1, l]
        acc_ref[blk & 1, l] = jnp.zeros_like(e)
        # the client's last row is in this block, so its thresholds are
        # in the window (a client without rows folds garbage, unread)
        thr = jnp.where(blk == kk, ga_ref[l], gb_ref[l])
        hit = lane == (c & (LANES - 1))
        row = c >> 7
        for p, err in enumerate((e, 1.0 - e)):
            err = jnp.where(valid, err, jnp.inf)
            m = _min_all(err)
            idx = _min_all(jnp.where(err == m, flat, NO_INDEX))
            t = _min_all(jnp.where(flat == idx, thr, jnp.inf))
            best = out_ref[row, pl.ds(3 * p, 1), :]          # (1, LANES)
            win = hit & (m < best)
            for q, v in enumerate((m, idx, t)):
                old = out_ref[row, pl.ds(3 * p + q, 1), :]
                out_ref[row, pl.ds(3 * p + q, 1), :] = jnp.where(win, v, old)
        return carry

    jax.lax.fori_loop(lo_ref[i], hi_ref[i], fold, 0)


@functools.partial(jax.jit, static_argnames=("block_n", "block_f",
                                             "block_c", "interpret"))
def ragged_scan_kernel(x: jnp.ndarray, seg: jnp.ndarray, a: jnp.ndarray,
                       b: jnp.ndarray, grid: jnp.ndarray, kk: jnp.ndarray,
                       lo: jnp.ndarray, hi: jnp.ndarray, *, block_n: int,
                       block_f: int, block_c: int,
                       interpret: bool = True) -> jnp.ndarray:
    """x: (R, F) packed rows; seg, a, b: (Rp,) with Rp a multiple of
    ``max(block_n, SMEM_ROWS)`` at or past R — each row's client, and the
    weight it adds when x > thr (a) and when x <= thr (b); grid: (B, T,
    Fp) thresholds, Fp >= F (lanes past F are not scanned); kk:
    (ceil(R/block_n),) the client block of each row block's first row;
    lo, hi: the clients whose last row lies in each row block.  block_n divides ``SMEM_ROWS`` or is a multiple of it;
    block_c is a power of two, at least block_n.  -> (ceil(B/128), 6,
    128) f32: per client (lane), the least error, its flat index and its
    threshold for polarity +1, then for -1."""
    R, F = x.shape
    B, T, _ = grid.shape
    Rp = seg.shape[0]
    sb = max(block_n, SMEM_ROWS)
    assert sb % block_n == 0 and Rp % sb == 0 and Rp >= R, (Rp, block_n)
    assert block_c >= block_n and kk.shape[0] == pl.cdiv(R, block_n)
    assert block_c & (block_c - 1) == 0 and block_f % LANES == 0
    assert F * T < 2 ** 24 and grid.shape[2] >= F, (F, T, grid.shape)
    nkb = pl.cdiv(B, block_c)
    per = sb // block_n
    smem = lambda: pl.BlockSpec((sb,), lambda j, i, *_: (i // per,),
                                memory_space=pltpu.SMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(pl.cdiv(F, block_f), pl.cdiv(R, block_n)),
        in_specs=[
            smem(), smem(), smem(),
            pl.BlockSpec((block_n, block_f), lambda j, i, *_: (i, j)),
            pl.BlockSpec((block_c, T, block_f),
                         lambda j, i, kk, *_: (kk[i], 0, j)),
            pl.BlockSpec((block_c, T, block_f),
                         lambda j, i, kk, *_: (jnp.minimum(kk[i] + 1,
                                                           nkb - 1), 0, j)),
        ],
        out_specs=pl.BlockSpec((pl.cdiv(B, LANES), 6, LANES),
                               lambda j, i, *_: (0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, block_c, T, block_f), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_ragged_kernel, n_features=F, block_c=block_c),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((pl.cdiv(B, LANES), 6, LANES),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="ragged_scan_kernel",
    )(kk, lo, hi, seg, a, b, x, grid, grid)
