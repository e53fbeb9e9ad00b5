"""The ragged stump scan and the segmented threshold grid against plain
NumPy, client by client, on Sent140-like packed rows: most clients hold
one or two rows, a few more than a row block, so they span block
boundaries.  The kernel runs under the Pallas interpreter, the oracle as
XLA; both must give what ``fit_stump_batched`` + ``_pick_stump`` give per
client.  Error sums may differ from NumPy's by float32 rounding (a few
units in the last place of values at most 1), so the least errors agree
within 1e-6 and the chosen stumps agree wherever the best is unique by
more than that.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch, ops, ref
from repro.models import weak

TOL = 1e-6


def packed_fleet(counts, F: int, seed: int = 0, T: int = 16):
    """Rows of clients of ``counts`` back to back: x (R, F), labels,
    weights normalized per client, each row's client, and each client's
    (T, F) grid from its own rows."""
    rng = np.random.default_rng(seed)
    counts = np.asarray(counts)
    B, R = len(counts), int(counts.sum())
    seg = np.repeat(np.arange(B), counts).astype(np.int32)
    x = rng.normal(size=(R, F)).astype(np.float32)
    x[rng.random((R, F)) < 0.2] = 0.5          # repeated values: ties
    y = np.where(rng.random(R) < 0.5, 1.0, -1.0).astype(np.float32)
    w = rng.random(R).astype(np.float32) + 0.05
    w = (w / np.bincount(seg, w)[seg]).astype(np.float32)
    off = np.r_[0, np.cumsum(counts)]
    grid = np.stack([np.asarray(weak.stump_thresholds(
        jnp.asarray(x[off[b]:off[b + 1]]), T)).T for b in range(B)])
    return x, y, w, seg, grid, off


def numpy_best(x, y, w, off, grid):
    """Per client, the error grid of polarity +1 (float32 sums in row
    order, as ``bench/reference.py`` scores a stump) and ``_pick_stump``'s
    choice from it: (errors (B, 4): least error and its flat index per
    polarity, picks (B, 3): feature, threshold, polarity, unique (B,):
    whether the best beats every other stump by more than TOL)."""
    B, T, F = grid.shape
    best, picks, unique = [], [], []
    for b in range(B):
        s = slice(off[b], off[b + 1])
        side = np.where(x[s][:, :, None] > grid[b].T[None], 1.0, -1.0)
        miss = (side != y[s][:, None, None]).astype(np.float32)
        err = np.zeros((F, T), np.float32)
        for i in range(off[b + 1] - off[b]):
            err += w[s][i] * miss[i]
        flat = err.reshape(-1)
        neg = np.float32(1.0) - flat
        ip, ineg = int(flat.argmin()), int(neg.argmin())
        best.append([flat[ip], ip, neg[ineg], ineg])
        pos = flat[ip] <= neg[ineg]
        k = ip if pos else ineg
        picks.append([k // T, grid[b, k % T, k // T], 1.0 if pos else -1.0])
        both = np.sort(np.concatenate([flat, neg]))
        unique.append(both[1] - both[0] > TOL)
    return np.array(best), np.array(picks), np.array(unique)


SENT140_LIKE = [1] * 40 + [2] * 10 + [3, 5, 150, 1, 1, 200, 2, 1, 97]


@pytest.mark.parametrize("counts, F, block_n, block_f", [
    (SENT140_LIKE, 200, 64, 128),          # F past one tile, not a multiple
    (SENT140_LIKE, 128, 128, 128),         # F one whole tile
    (SENT140_LIKE, 300, 128, 256),         # wide tiles, two clients > block
    ([1] * 300, 40, 64, 128),              # one-row clients only
    ([130, 1, 129, 2, 300], 20, 64, 128),  # every block boundary inside one
], ids=["f200", "f128", "f300_wide", "single_rows", "spanning"])
@pytest.mark.parametrize("backend", ["interpret", "xla"])
def test_ragged_scan_matches_numpy_per_client(counts, F, block_n, block_f,
                                              backend):
    x, y, w, seg, grid, off = packed_fleet(counts, F)
    args = tuple(map(jnp.asarray, (x, y, w, seg, grid)))
    best = np.asarray(ops.stump_scan_ragged(
        *args, block_n=block_n, block_f=block_f, backend=backend))
    want, picks, unique = numpy_best(x, y, w, off, grid)
    assert best.shape == (6, len(counts))
    np.testing.assert_allclose(best[0], want[:, 0], atol=TOL)
    np.testing.assert_allclose(best[3], want[:, 2], atol=TOL)
    got = weak.fit_stump_ragged(*args, backend=backend)
    for k, name in enumerate(("feature", "threshold", "polarity")):
        np.testing.assert_array_equal(np.asarray(got[name])[unique],
                                      picks[unique, k], err_msg=name)
    # the flat index and the threshold the scan reports belong together
    for p in (0, 3):
        flat = best[p + 1].astype(np.int64)
        T = grid.shape[1]
        np.testing.assert_array_equal(
            best[p + 2], grid[np.arange(len(counts)), flat % T, flat // T])


@pytest.mark.parametrize("backend", ["interpret", "xla"])
def test_grid_on_whole_lanes_scans_only_the_real_features(backend):
    """The segmented grid's lanes past F (zeros) are never a stump: the
    scan over them gives what the scan over the F real features gives."""
    x, y, w, seg, grid, _ = packed_fleet(SENT140_LIKE, 200)
    wide = np.zeros(grid.shape[:2] + (256,), np.float32)
    wide[:, :, :200] = grid
    a = ops.stump_scan_ragged(*map(jnp.asarray, (x, y, w, seg, grid)),
                              backend=backend, block_n=64)
    b = ops.stump_scan_ragged(*map(jnp.asarray, (x, y, w, seg, wide)),
                              backend=backend, block_n=64)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_kernel_and_oracle_agree_exactly_on_one_row_clients():
    """A one-row client's errors are its weight or 0, exactly, so the
    kernel and the oracle give the same bests bit for bit, ties broken
    at the lowest flat index alike."""
    x, y, w, seg, grid, _ = packed_fleet([1] * 100 + [2] * 20, 150)
    args = tuple(map(jnp.asarray, (x, y, w, seg, grid)))
    k = np.asarray(ops.stump_scan_ragged(*args, backend="interpret",
                                         block_n=64))
    r = np.asarray(ref.stump_scan_ragged_ref(*args))
    np.testing.assert_array_equal(k, r)


def test_padding_rows_add_nothing():
    """Rows past the last client (seg = B, weight 0, any values) leave
    every client's best as it was."""
    x, y, w, seg, grid, _ = packed_fleet(SENT140_LIKE, 130)
    B = grid.shape[0]
    pad = 77
    xp = np.concatenate([x, np.full((pad, 130), 3.0, np.float32)])
    yp = np.concatenate([y, np.ones(pad, np.float32)])
    wp = np.concatenate([w, np.zeros(pad, np.float32)])
    sp = np.concatenate([seg, np.full(pad, B, np.int32)])
    for backend in ("interpret", "xla"):
        a = ops.stump_scan_ragged(*map(jnp.asarray, (x, y, w, seg, grid)),
                                  backend=backend, block_n=64)
        b = ops.stump_scan_ragged(*map(jnp.asarray, (xp, yp, wp, sp, grid)),
                                  backend=backend, block_n=64)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("counts, F", [
    (SENT140_LIKE, 37), ([1] * 50, 9), ([2, 3, 17, 1, 64, 1, 5], 130)],
    ids=["sent140_like", "single_rows", "mixed"])
@pytest.mark.parametrize("pad_rows", [0, 45])
def test_segmented_grid_equals_each_clients_own_grid(counts, F, pad_rows):
    x, _, _, seg, grid, off = packed_fleet(counts, F)
    B = len(counts)
    xs = np.concatenate([x, np.full((pad_rows, F), -9.0, np.float32)])
    ss = np.concatenate([seg, np.full(pad_rows, B, np.int32)])
    got = np.asarray(weak.stump_thresholds_segmented(
        jnp.asarray(xs), jnp.asarray(ss), jnp.asarray(np.diff(off))))
    # whole 128-lane tiles, the lanes past F zero
    assert got.shape == (B, 16, -(-F // 128) * 128)
    np.testing.assert_array_equal(got[:, :, :F], grid)
    assert not got[:, :, F:].any()


def test_segmented_grid_gathers_in_client_chunks(monkeypatch):
    """A fleet of several gather chunks, the last one short, gives the
    same grid as one chunk."""
    x, _, _, seg, grid, off = packed_fleet([1, 2, 3, 4, 5] * 9, 11)
    monkeypatch.setattr(weak, "GRID_CLIENTS", 8)
    weak.stump_thresholds_segmented.clear_cache()
    try:
        got = weak.stump_thresholds_segmented(
            jnp.asarray(x), jnp.asarray(seg), jnp.asarray(np.diff(off)))
    finally:
        weak.stump_thresholds_segmented.clear_cache()
    np.testing.assert_array_equal(np.asarray(got)[:, :, :11], grid)


def test_tables_cover_every_client_once():
    """Every client with rows folds exactly once, in the row block of its
    last row, and the two-block window of each row block holds every
    client whose rows it holds."""
    counts = np.array(SENT140_LIKE)
    seg = np.repeat(np.arange(len(counts)), counts).astype(np.int32)
    bn = 64
    sp = np.concatenate([seg, np.full((-len(seg)) % bn, len(counts) - 1,
                                      np.int32)])
    kk, lo, hi = map(np.asarray, dispatch.ragged_tables(
        jnp.asarray(sp), len(counts), bn, bn))
    folded = np.concatenate([np.arange(a, b) for a, b in zip(lo, hi)])
    np.testing.assert_array_equal(folded, np.arange(len(counts)))
    last = np.r_[0, np.cumsum(counts)][1:] - 1
    for i in range(len(kk)):
        assert np.all(last[lo[i]:hi[i]] // bn == i)
        blocks = sp[i * bn:(i + 1) * bn] // bn
        assert np.all((blocks == kk[i]) | (blocks == kk[i] + 1))
