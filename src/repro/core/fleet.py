"""Vectorized fleet profile of the event-driven engine core.

:class:`~repro.core.async_engine.FederatedBoostEngine` delegates here when
``fleet=True`` (auto-enabled at ``FLEET_AUTO_CLIENTS``+ clients).  The
reference profile runs one device dispatch per client fit and one python
merge per learner — fine at 32 clients, hopeless at 100 000.  The fleet
profile keeps the *same event-queue semantics* but restructures the math:

* **One shard layout, chosen from the fleet's shape.**  The shards are
  padded to the fleet's most rows and stacked (:class:`_Padded`), unless
  that stack's *row fill* (real rows over ``B * N``) is below
  ``PACKED_BELOW_FILL`` and the fleet fits one ``THRESHOLD_CHUNK``: then
  they are packed back to back (:class:`_Packed`).  Pad rows carry zero
  weight, so no kernel or sum takes anything from them.  LEAF FEMNIST
  (2,048 writers, largest 573 rows) stacks at a fill of 39.6%; LEAF
  Sent140 (16,384 users, mean 2.42 rows) would stack at 2.49% — 47.7 GB
  at 7,500 features against 1.19 GB packed — and packs.  The quarter
  between them is a guess; the cells' numbers decide where the two
  layouts really cross.
* **The layout is the one seam.**  A layout owns what differs between
  the two: staging the shards and their initial weights; the threshold
  grid's launch, whose rows and grids a fleet of one chunk keeps on the
  device, so that a fit wave of every client in order reads them there
  and stages only its labels and weights (any other wave gathers on the
  host); a wave's operands, weak fit and launch counters; and a *row
  view* of some clients — labels, weights, feature columns, ``expand``
  (a per-client value to its rows), ``segsum`` (rows summed per client)
  and ``put`` (weights written back).  The fit wave, the local update,
  both catch-ups and the stump vote (:func:`_vote`) are written once
  above it, each layout running its own arithmetic op for op; the two
  layouts sum in different orders and may differ in the last place.
* **Deferred, batched fits.**  A client leg between syncs is causally
  closed, so its *timing* walk (availability/compute/stall/link draws — the
  behavior calls, in the reference call order) runs eagerly while the stump
  fits it implies are queued.  Pending fits resolve in dependency *waves* —
  wave ``j`` fits round ``j`` of every pending leg in one bucketed launch
  (batch padded to a power of two so the jit cache stays small) — and
  each wave's local eps/alpha/distribution updates run vectorized in
  numpy.
* **Vectorized server math, tabulated per fit wave.**  What the server
  needs of a stump depends only on the stump and the held-out rows, so
  each fit wave computes it for all its stumps at once (in blocks of
  ``SERVER_CHUNK`` stumps): each stump's votes on the validation and test
  rows, kept as one byte per row until the stump merges, and its server
  alpha before compensation.  An arrival only reads its entries' rows:
  it compensates their alphas, folds them into the margins with the same
  float32 product, and counts the validation error.  The merged learners
  are numpy columns, so the capped catch-up picks its learners with array
  operations and replays them as numpy matrix ops.

Communication/time accounting is identical integer/float bookkeeping to the
reference profile — byte counts, message counts, and simulated clocks match
exactly at equal seeds.  Floating-point *learning* results (errors, alphas)
match up to summation order: the fleet profile sums in numpy float32 where
the reference reduces on the device, and folds a sync's distribution
updates in one exponential rather than entry-by-entry (equal up to the
``1e-30`` normalization epsilon).  ``cfg.catch_up_cap`` is how fleet-scale
scenarios bound catch-up work per sync; ``None`` replays the whole window
exactly like the reference.

Only the ``stump`` weak learner is supported — the batched launch path is
stump-specific (the other learners never run at fleet scale).

Tracing is per phase, never per client: a job is one ``train.fleet.run``
span holding the layout's staging (``train.fleet.stack`` or
``train.fleet.pack``), the threshold grids, the first timing walk, each
fit wave (``train.fit_batch``: gather, transfer, fit) and its local
update, one ``train.fleet.sync`` per arrival, and the finish; the
``train.fleet.*`` counters add the bytes handed to the device and copied
on the host, what the waves launch (slots and padded rows when stacked,
rows and clients when packed), the waves that read the resident chunk,
and the stumps whose held-out votes were tabulated (outside any phase
span, in ``train.fleet.run``'s self time).  With
``obs.tracing(profiler=True)`` the spans land in a ``jax.profiler`` trace
beside the device ops, so the device's idle time can be split by phase.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro import obs
from repro.core import events
from repro.core.buffers import BufferEntry, ENTRY_OVERHEAD_BYTES
from repro.core.compensation import staleness_scale

# threshold-grid launches are chunked to this many clients (padded to the
# chunk size, so the jit cache holds exactly one entry per fleet dtype)
THRESHOLD_CHUNK = 16384
# a fleet whose padded stack would hold fewer real rows than this share
# is packed (module docstring)
PACKED_BELOW_FILL = 0.25
# packed rows are padded to a multiple of this: every row block of the
# ragged scan's layouts divides it
ROW_ALIGN = 512
# server-side re-weighting materializes at most (n_val x SERVER_CHUNK)
SERVER_CHUNK = 4096
_F32 = np.float32
# a stump's held-out vote code: its validation vote + 1 in bits 0-1 and its
# test vote + 1 in bits 2-3 (votes are -1, 0 or +1); these decode a code
_VAL_VOTE = (np.arange(16) % 4 - 1).astype(_F32)
_TEST_VOTE = (np.arange(16) // 4 - 1).astype(_F32)


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _vote(x, thr, pol):
    """A stump's vote on feature values ``x``, elementwise: -1, 0 or +1."""
    return pol * np.sign(x - thr + 1e-12)


def _to_device(*arrays):
    """Hand host arrays to the device, counting their bytes."""
    import jax.numpy as jnp
    with obs.span("train.fleet.h2d"):
        obs.count("train.fleet.h2d_bytes", sum(a.nbytes for a in arrays))
        return tuple(jnp.asarray(a) for a in arrays)


@dataclass
class _FleetEntry(BufferEntry):
    """A buffered entry with what the server needs of its stump, filled
    in when its fit wave resolves: the stump's vote codes on the held-out
    rows, dropped once it merges, and its server alpha before
    compensation."""
    votes: Optional[np.ndarray] = None
    server_alpha: float = 0.0


class _Learners:
    """The merged learners' columns in merge order, the source of every
    catch-up window: feature, threshold, polarity, compensated server
    alpha and owner, grown by doubling."""

    _DTYPES = (("f", np.int64), ("thr", _F32), ("pol", _F32),
               ("alpha", _F32), ("owner", np.int64))

    def __init__(self) -> None:
        self.n = 0
        self._cols = {k: np.empty(64, dt) for k, dt in self._DTYPES}

    def extend(self, **cols) -> None:
        k, cap = len(cols["owner"]), len(self._cols["owner"])
        if self.n + k > cap:
            self._cols = {name: np.resize(a, max(2 * cap, self.n + k))
                          for name, a in self._cols.items()}
        for name, v in cols.items():
            self._cols[name][self.n:self.n + k] = v
        self.n += k

    def __getitem__(self, name: str) -> np.ndarray:
        return self._cols[name][:self.n]


def _newest_foreign(owners: np.ndarray, lo: int, cid: int,
                    cap: Optional[int], own_max: int) -> np.ndarray:
    """Indices in ``[lo, len(owners))`` of the learners ``cid`` does not
    own, oldest first: all of them where ``cap`` is None, else the newest
    ``cap``, as the reference's reverse scan picks them.  ``cid`` owns at
    most ``own_max`` learners, so those ``cap`` lie in the last
    ``cap + own_max``."""
    hi = len(owners)
    w0 = lo if cap is None else max(lo, hi - cap - own_max)
    idx = w0 + np.flatnonzero(owners[w0:hi] != cid)
    return idx if cap is None else idx[max(0, len(idx) - cap):]


# ------------------------------------------------------------ shard layouts
class _Padded:
    """Shards padded to the fleet's most rows and stacked: ``X`` (B, N, F),
    ``Y`` and ``D`` (B, N), client b in row b; a pad row has x = 0, y = 0
    and D = 0.  The grids come back to the host as ``THR`` (B, F, T); a
    fleet of one chunk also keeps the chunk's rows and grids on the
    device (pad slots included) for the waves that read them in place."""

    FIT, KERNEL = "fit_stump_batched", "stump_scan_batched"

    def __init__(self, clients, n_valid: np.ndarray, balanced: bool) -> None:
        with obs.span("train.fleet.stack"):
            B, N = len(clients), int(n_valid.max())
            F = int(np.asarray(clients[0].x).shape[1])
            self.n_valid = n_valid
            self.X = np.zeros((B, N, F), _F32)
            self.Y = np.zeros((B, N), _F32)
            self.D = np.zeros((B, N), _F32)
            for b, c in enumerate(clients):
                n = int(n_valid[b])
                self.X[b, :n] = np.asarray(c.x, _F32)
                self.Y[b, :n] = np.asarray(c.y, _F32)
                yb = self.Y[b, :n]
                if balanced:
                    pos = (yb > 0).astype(_F32)
                    npos = max(float(pos.sum()), 1.0)
                    nneg = max(n - float(pos.sum()), 1.0)
                    self.D[b, :n] = pos / (2 * npos) + (1 - pos) / (2 * nneg)
                else:
                    self.D[b, :n] = 1.0 / n
        self._Xd = self._THRd = None

    def build_grid(self) -> None:
        """The grids, chunk by chunk."""
        from repro.models.weak import stump_thresholds_batched
        B = self.X.shape[0]
        chunk = min(THRESHOLD_CHUNK, _next_pow2(B))
        grids = []
        for lo in range(0, B, chunk):
            xb = self.X[lo:lo + chunk]
            nb = self.n_valid[lo:lo + chunk]
            pad = chunk - xb.shape[0]
            if pad:
                with obs.span("train.fleet.gather"):
                    xb = np.concatenate([xb, np.zeros(
                        (pad,) + xb.shape[1:], _F32)])
                    nb = np.concatenate([nb, np.ones(pad, np.int32)])
                    obs.count("train.fleet.gather_bytes",
                              xb.nbytes + nb.nbytes)
            args = _to_device(xb, nb)
            with obs.span("train.fleet.grid_wait"):
                g = stump_thresholds_batched(*args)
                grids.append(np.asarray(g, _F32)[:xb.shape[0] - pad
                                                 if pad else None])
        if B <= chunk:
            self._Xd, self._THRd = args[0], g
        self.THR = np.concatenate(grids)[:B]                   # (B, F, T)

    def release(self) -> None:
        self._Xd = self._THRd = None

    def wave(self, slots: np.ndarray) -> Tuple[int, bool]:
        """A wave's slots, a power of two, and whether it is exactly the
        resident chunk: every client, in order, with the chunk's pad."""
        BP = max(8, _next_pow2(len(slots)))
        return BP, (self._Xd is not None and self._Xd.shape[0] == BP
                    and np.array_equal(slots, np.arange(len(self.X))))

    def stage(self, slots: np.ndarray, BP: int, resident: bool):
        """The batched fit's operands (X, Y, D, THR) on the device, padded
        to ``BP`` zero-weight slots, and the wave's real rows."""
        pad = BP - len(slots)
        with obs.span("train.fleet.gather"):
            ops = [self.Y[slots], self.D[slots]]
            if not resident:
                ops = [self.X[slots]] + ops + [self.THR[slots]]
            staged = sum(a.nbytes for a in ops)
            if pad:
                ops = [np.concatenate(
                    [a, np.zeros((pad,) + a.shape[1:], a.dtype)])
                    for a in ops]
                staged += sum(a.nbytes for a in ops)
            obs.count("train.fleet.gather_bytes", staged)
        args = _to_device(*ops)
        if resident:
            args = (self._Xd,) + args + (self._THRd,)
        return args, int(self.n_valid[slots].sum())

    def count_launch(self, Bw: int, BP: int, args) -> None:
        obs.count("train.fleet.slots_launched", BP)
        obs.count("train.fleet.rows_launched", BP * self.X.shape[1])

    def view(self, slots=slice(None)) -> "_PaddedRows":
        """The rows of ``slots``; every client's by default."""
        return _PaddedRows(self, slots)

    def rows_of(self, b: int) -> int:
        return b


class _Rows:
    """A row view of some clients in a layout ``sh``, at ``at``: their
    labels ``y`` and weights ``d`` (copies where ``at`` is an index
    array); ``col(c)`` feature c of every row and ``fitted(f)`` each row's
    value of its client's fitted feature; ``expand`` takes a per-client
    value to the rows, ``segsum`` sums the rows per client, and ``put``
    writes the weights back."""

    def __init__(self, sh, at) -> None:
        self.sh, self.at = sh, at
        self.y, self.d = sh.Y[at], sh.D[at]

    def put(self, d: np.ndarray) -> None:
        self.sh.D[self.at] = d


class _PaddedRows(_Rows):
    """Padded clients: a client's values broadcast along its row, and its
    row sums along that axis."""

    def col(self, c):
        """Feature ``c`` of every row, or with one ``c`` per client, each
        client's own."""
        return self.sh.X[self.at, :, c]

    fitted = col

    def expand(self, v: np.ndarray) -> np.ndarray:
        return v[:, None]

    def segsum(self, a: np.ndarray) -> np.ndarray:
        return np.sum(a, axis=1, dtype=_F32)


class _Packed:
    """Shards packed back to back: ``X`` (Rp, F), ``Y``, ``D`` and each
    row's client ``seg`` (Rp,), client b's rows at ``off[b]:off[b+1]``; the
    ``R`` real rows are padded to a multiple of ``ROW_ALIGN`` with rows of
    no client (seg = B, x = 0, y = 0, D = 0).  The grids (B, T, F) come
    from one launch and stay on the device with the rows and row clients;
    nothing of them comes back to the host."""

    FIT, KERNEL = "fit_stump_ragged", "stump_scan_ragged"

    def __init__(self, clients, n_valid: np.ndarray, balanced: bool) -> None:
        with obs.span("train.fleet.pack"):
            B = len(clients)
            n = n_valid.astype(np.int64)
            self.n_valid = n_valid
            self.off = np.concatenate([[0], np.cumsum(n)])
            self.R = R = int(self.off[-1])
            Rp = -(-R // ROW_ALIGN) * ROW_ALIGN
            F = int(np.asarray(clients[0].x).shape[1])
            self.X = np.zeros((Rp, F), _F32)
            np.concatenate([np.asarray(c.x, _F32) for c in clients],
                           out=self.X[:R])
            self.Y = np.zeros(Rp, _F32)
            np.concatenate([np.asarray(c.y, _F32) for c in clients],
                           out=self.Y[:R])
            self.seg = np.full(Rp, B, np.int32)
            self.seg[:R] = np.repeat(np.arange(B, dtype=np.int32), n)
            self.D = np.zeros(Rp, _F32)
            s = self.seg[:R]
            if balanced:
                pos = (self.Y[:R] > 0).astype(_F32)
                npos = np.add.reduceat(pos, self.off[:-1]).astype(np.float64)
                nneg = np.maximum(n - npos, 1.0).astype(_F32)
                npos = np.maximum(npos, 1.0).astype(_F32)
                self.D[:R] = pos / (2 * npos[s]) + (1 - pos) / (2 * nneg[s])
            else:
                self.D[:R] = (1.0 / n)[s]
        self._Xd = self._THRd = self._segd = None

    def build_grid(self) -> None:
        from repro.models.weak import stump_thresholds_segmented
        args = _to_device(self.X, self.seg, self.n_valid)
        with obs.span("train.fleet.grid_wait"):
            g = stump_thresholds_segmented(*args)
            g.block_until_ready()
        self._Xd, self._segd, self._THRd = args[0], args[1], g

    def release(self) -> None:
        self._Xd = self._THRd = self._segd = None

    def wave(self, slots: np.ndarray) -> Tuple[int, bool]:
        """A wave's clients and whether it reads the resident rows: every
        client in order does, at its own count; any other wave pads its
        clients to a power of two."""
        resident = np.array_equal(slots, np.arange(len(self.n_valid)))
        return (len(slots) if resident else max(8, _next_pow2(len(slots))),
                resident)

    def stage(self, slots: np.ndarray, BP: int, resident: bool):
        """The ragged fit's operands (X, Y, D, seg, grids) on the device,
        and the wave's real rows.  A wave that is not resident gathers its
        clients' rows on the host, padded to a power of two (at least
        ``ROW_ALIGN``) with rows of no client, and their grids on the
        device, padded to ``BP`` clients."""
        with obs.span("train.fleet.gather"):
            if resident:
                ops, real = [self.Y, self.D], self.R
            else:
                v = self.view(slots)
                real = len(v.at)
                Rp = max(ROW_ALIGN, _next_pow2(real))
                ops = [np.zeros((Rp,) + a.shape[1:], a.dtype)
                       for a in (self.X, self.Y, self.D)]
                for o, a in zip(ops, (self.X[v.at], v.y, v.d)):
                    o[:real] = a
                seg = np.full(Rp, BP, np.int32)
                seg[:real] = v.local
                ops.append(seg)
                obs.count("train.fleet.gather_bytes",
                          sum(a.nbytes for a in ops))
        args = _to_device(*ops)
        if resident:
            return (self._Xd,) + args + (self._segd, self._THRd), real
        import jax.numpy as jnp
        idx = np.zeros(BP, np.int32)
        idx[:len(slots)] = slots
        thr = jnp.take(self._THRd, jnp.asarray(idx), axis=0)
        return args + (thr,), real

    def count_launch(self, Bw: int, BP: int, args) -> None:
        obs.count("train.fleet.packed_rows_launched", args[0].shape[0])
        obs.count("train.fleet.segments_launched", Bw)

    def view(self, slots: Optional[np.ndarray] = None) -> "_PackedRows":
        """The rows of ``slots``, client by client; every client's by
        default."""
        if slots is None:
            return _PackedRows(self, slice(0, self.R), self.seg[:self.R],
                               self.off[:-1])
        lens = self.n_valid[slots].astype(np.int64)
        starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
        local = np.repeat(np.arange(len(slots)), lens)
        rows = self.off[slots][local] + (np.arange(int(lens.sum()))
                                         - starts[local])
        return _PackedRows(self, rows, local, starts)

    def rows_of(self, b: int) -> slice:
        return slice(self.off[b], self.off[b + 1])


class _PackedRows(_Rows):
    """Packed clients: ``local`` is each row's client in the view and
    ``starts`` where each client's rows begin, so a client's values reach
    its rows by ``local`` and its rows sum by segment."""

    def __init__(self, sh, at, local, starts) -> None:
        super().__init__(sh, at)
        self.local, self.starts = local, starts

    def col(self, c):
        """Feature ``c`` of every row."""
        return self.sh.X[self.at, c]

    def fitted(self, f: np.ndarray) -> np.ndarray:
        """Each row's value of the feature its client fitted."""
        return self.col(f[self.local])

    def expand(self, v: np.ndarray) -> np.ndarray:
        return v[self.local]

    def segsum(self, a: np.ndarray) -> np.ndarray:
        return np.add.reduceat(a, self.starts, dtype=_F32)


class FleetCore:
    """One engine run in the vectorized fleet profile."""

    # the distributions (assignable, replacing the layout's) and packed
    # offsets: client b's weights are D[b] stacked, D[off[b]:off[b + 1]]
    # packed
    D = property(lambda self: self.shards.D,
                 lambda self, d: setattr(self.shards, "D", d))
    off = property(lambda self: self.shards.off)

    def __init__(self, eng) -> None:
        if eng.weak.name != "stump":
            raise ValueError(
                "the fleet profile batches stump fits; weak learner "
                f"{eng.weak.name!r} has no batched launch path")
        self.eng = eng
        self.cfg = eng.cfg
        self.m = eng.metrics
        self.clients = eng.clients
        B = len(self.clients)

        self.n_valid = np.array([c.x.shape[0] for c in self.clients],
                                np.int32)
        N = int(self.n_valid.max())
        self.packed = (B <= THRESHOLD_CHUNK and int(self.n_valid.sum())
                       < PACKED_BELOW_FILL * B * N)
        layout = _Packed if self.packed else _Padded
        self.shards = layout(self.clients, self.n_valid,
                             self.cfg.balanced_init)
        with obs.span("train.fleet.thresholds"):
            self.shards.build_grid()

        # ---- server state mirrors (numpy-side ensemble view) ----
        xv, yv = eng.data["val"]
        xt, yt = eng.data["test"]
        # held-out features by feature: row f is feature f of every row
        self._xv_f = np.ascontiguousarray(np.asarray(xv, _F32).T)
        self._xt_f = np.ascontiguousarray(np.asarray(xt, _F32).T)
        self.yv = np.asarray(yv, _F32)
        self.yt = np.asarray(yt, _F32)
        self.Mval = np.zeros(len(self.yv), _F32)
        self.Mtest = np.zeros(len(self.yt), _F32)
        # a vote of +1 misses a validation row not labelled +1, a vote of
        # -1 one not labelled -1: ((vote > 0) ^ _yv_pos) | _yv_other
        self._yv_pos = self.yv == 1.0
        self._yv_other = (self.yv != 1.0) & (self.yv != -1.0)
        self._learners = _Learners()
        # deferred fits: cid -> FIFO of unresolved entries (insertion
        # order over cids is the wave's batch order)
        self._pending: Dict[int, List[_FleetEntry]] = {}
        # stump wire size is params-independent, so accounting never needs
        # the (possibly still unresolved) params
        self._entry_bytes = (int(eng.weak.param_bytes(None))
                             + ENTRY_OVERHEAD_BYTES)

    # ------------------------------------------------------------ batched fits
    def _fit_backend(self, xb, kernel: str = "stump_scan_batched"
                     ) -> Optional[str]:
        """Resolve the batched-fit backend.  No policy keeps the jnp
        oracle (a single vmapped XLA launch — the right default off-TPU);
        a policy resolves normally except that the *interpret* substrate is
        swapped for ``xla`` at fleet batch sizes, where an interpreter
        launch is pathological.  The backend that runs is what
        ``policy.choices`` records."""
        policy = self.eng.kernel_policy
        if policy is None:
            return None
        from repro.kernels import dispatch as kdispatch
        bucket = kdispatch.bucket_of(kernel, xb)
        name = policy.resolve(kernel, bucket).name
        if name == "interpret" and xb[0].shape[0] >= 64:
            name = "xla"
            policy.choices[(kernel, bucket)] = name
        return name

    def _fit_wave(self, slots: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """One fit launch over ``slots`` (clients), padded to a power of
        two with zero-weight slots or rows, staged by the layout.  A wave
        that is the resident chunk stages only its labels and weights."""
        from repro.models import weak
        sh, Bw = self.shards, len(slots)
        BP, resident = sh.wave(slots)
        with obs.span("train.fit_batch", n_slots=Bw, padded=BP):
            args, real = sh.stage(slots, BP, resident)
            if resident:
                obs.count("train.fleet.resident_waves")
            with obs.span("train.fleet.fit"):
                # looked up at each call, so a wrapper set on the module
                # sees every wave
                params = getattr(weak, sh.FIT)(
                    *args, backend=self._fit_backend(args, sh.KERNEL))
                f = np.asarray(params["feature"])[:Bw].astype(np.int64)
                thr = np.asarray(params["threshold"], _F32)[:Bw]
                pol = np.asarray(params["polarity"], _F32)[:Bw]
        obs.count("train.fit_batches")
        obs.count("train.fits", Bw)
        sh.count_launch(Bw, BP, args)
        obs.count("train.fleet.real_rows", real)
        return f, thr, pol

    def _local_update(self, slots: np.ndarray, f: np.ndarray,
                      thr: np.ndarray, pol: np.ndarray
                      ) -> Tuple[np.ndarray, np.ndarray]:
        """Vectorized mirror of the reference ``_train_one`` tail: eps on
        the pre-update distribution, the local alpha, and the eq.-(4)
        distribution update, for every fitted slot at once."""
        with obs.span("train.fleet.local_update"):
            with obs.span("train.fleet.gather"):
                v = self.shards.view(slots)
                xsel, yb, Db = v.fitted(f), v.y, v.d
                obs.count("train.fleet.gather_bytes",
                          xsel.nbytes + yb.nbytes + Db.nbytes)
            h = _vote(xsel, v.expand(thr), v.expand(pol))
            pred = np.where(h > 0, 1.0, -1.0).astype(_F32)
            eps = v.segsum(Db * (pred != yb))
            epsc = np.clip(eps, 1e-6, 1.0 - 1e-6)
            alpha = (0.5 * np.log((1.0 - epsc) / epsc)).astype(_F32)
            w = Db * np.exp(-v.expand(alpha) * yb * h)
            Z = v.segsum(w)
            v.put(w / (v.expand(Z) + 1e-30))
        return eps, alpha

    def _defer_fit(self, c) -> _FleetEntry:
        """Queue one deferred stump fit for client ``c``'s current round;
        the placeholder entry is filled in by the next resolution wave."""
        e = _FleetEntry(None, 0.0, 0.0, c.local_round)
        c.local_round += 1
        self._pending.setdefault(c.cid, []).append(e)
        return e

    def _resolve_pending(self) -> None:
        """Drain every queued fit, one dependency wave at a time: wave j
        fits the j-th unresolved round of each pending client (all waves
        are single bucketed launches)."""
        while self._pending:
            obs.get_registry().gauge("train.pending_fits").set(
                sum(len(v) for v in self._pending.values()))
            slots = np.fromiter(self._pending.keys(), np.int64,
                                len(self._pending))
            f, thr, pol = self._fit_wave(slots)
            eps, alpha = self._local_update(slots, f, thr, pol)
            wave = []
            for j, cid in enumerate(slots.tolist()):
                fifo = self._pending[cid]
                e = fifo.pop(0)
                e.params = {"feature": int(f[j]),
                            "threshold": float(thr[j]),
                            "polarity": float(pol[j])}
                e.eps = float(eps[j])
                e.alpha = float(alpha[j])
                wave.append(e)
                if not fifo:
                    del self._pending[cid]
            self._tabulate(wave, f, thr, pol)
        obs.get_registry().gauge("train.pending_fits").set(0)

    # --------------------------------------------------------- server math
    def _tabulate(self, wave: List[_FleetEntry], f: np.ndarray,
                  thr: np.ndarray, pol: np.ndarray) -> None:
        """Give each entry of a fit wave what the server needs of its
        stump, computed for the whole wave at once: its row of held-out
        vote codes (an array of its own, freed when the stump merges)
        and its server alpha before compensation."""
        Bw, nv, nt = len(f), len(self.yv), len(self.yt)
        for lo in range(0, Bw, SERVER_CHUNK):
            s = slice(lo, min(lo + SERVER_CHUNK, Bw))
            t, p = thr[s, None], pol[s, None]
            # (stumps, rows): the predictor's arithmetic, element by element
            hv = _vote(self._xv_f[f[s]], t, p)
            ht = _vote(self._xt_f[f[s]], t, p)
            codes = np.zeros((len(hv), max(nv, nt)), np.uint8)
            codes[:, :nv] = hv + 1
            codes[:, :nt] += (4 * ht + 4).astype(np.uint8)
            for e, row, a in zip(wave[s], codes,
                                 self._server_alphas(hv).tolist()):
                e.votes, e.server_alpha = row.copy(), a
        obs.count("train.fleet.vote_rows", Bw)

    def _merge_window(self, entries: List[_FleetEntry], owners: List[int],
                      sync_round: int, compensated: bool) -> None:
        """Fold ``entries`` into the global ensemble from their tabulated
        votes and alphas (staleness-compensated where ``compensated``),
        then the bookkeeping the reference ``_merge`` does per entry.  The
        fold's operand is (n, K) float32 with the layout a fancy-index
        gather of K stump columns has, so the sums run in the same order
        as the reference's."""
        if not entries:
            return
        eng, K = self.eng, len(entries)
        nv, nt = len(self.yv), len(self.yt)
        a = np.array([e.server_alpha for e in entries], _F32)
        if compensated:
            scale = np.array(
                [staleness_scale(max(0, sync_round - e.round_stamp),
                                 self.cfg.compensation) for e in entries],
                _F32)
            a = a * scale
        for lo in range(0, K, SERVER_CHUNK):
            s = slice(lo, min(lo + SERVER_CHUNK, K))
            codes = np.array([e.votes for e in entries[s]])    # (k, n)
            self.Mval += _VAL_VOTE.take(codes[:, :nv]).T @ a[s]
            self.Mtest += _TEST_VOTE.take(codes[:, :nt]).T @ a[s]
        params = [e.params for e in entries]
        for e, p, ai in zip(entries, params, a.tolist()):
            e.votes = None
            eng.ensemble.add(p, ai)
        eng._owners.extend(owners)
        eng._round_stamps.extend(e.round_stamp for e in entries)
        self._learners.extend(f=[p["feature"] for p in params],
                              thr=[p["threshold"] for p in params],
                              pol=[p["polarity"] for p in params],
                              alpha=a, owner=owners)
        self.m.learners_merged += K

    def _server_alphas(self, h: np.ndarray) -> np.ndarray:
        """Vectorized ``_server_alpha``: validation-set re-weighting of a
        block of stumps at once, from their votes ``h`` (stumps, validation
        rows).  The misses are counted, so the error rates are exact
        whatever the block."""
        miss = ((h > 0) ^ self._yv_pos) | self._yv_other
        if self.cfg.balanced_init:
            pos, neg = self.yv > 0, self.yv < 0
            ep = np.sum(miss & pos, axis=1) / max(float(pos.sum()), 1.0)
            en = np.sum(miss & neg, axis=1) / max(float(neg.sum()), 1.0)
            eps = np.clip(0.5 * (ep + en), 0.02, 0.98)
        else:
            eps = np.clip(miss.mean(axis=1), 0.02, 0.98)
        return (0.5 * np.log((1.0 - eps) / eps)).astype(_F32)

    def _val_err(self) -> float:
        """The share of validation rows the ensemble's sign gets wrong:
        the reference's ``mean(where(M > 0, 1, -1) != y)`` as one count."""
        return np.count_nonzero(
            ((self.Mval > 0) ^ self._yv_pos) | self._yv_other) / len(self.yv)

    # ----------------------------------------------------------- catch-up
    def _catch_up_fleet(self, w0: int, w1: int) -> None:
        """Every client replays the newest ``catch_up_cap`` foreign
        learners of window [w0, w1) into its local distribution — the
        whole fleet at once, one folded exponential per client (the
        baseline's per-round catch-up).  An owner-aware mask reproduces
        the reference reverse scan: the window is extended by the largest
        per-owner multiplicity so every client finds ``cap`` foreign
        entries even when its own sit inside the candidate tail."""
        K = w1 - w0
        if K <= 0:
            return
        B = len(self.clients)
        cap = self.cfg.catch_up_cap
        L = self._learners
        owners = L["owner"][w0:w1]
        if cap is None:
            W = K
        else:
            maxdup = int(np.bincount(owners - owners.min()).max()) if K else 0
            W = min(K, cap + maxdup)
        cand = slice(w1 - W, w1)              # oldest -> newest candidates
        foreign = L["owner"][cand][None, :] != np.arange(B)[:, None]
        if cap is None:
            sel = foreign
        else:
            rev = foreign[:, ::-1]
            sel = (rev & (np.cumsum(rev, axis=1) <= cap))[:, ::-1]
        cf, ct, cp, ca = (L[k][cand] for k in ("f", "thr", "pol", "alpha"))
        v = self.shards.view()
        Macc = np.zeros_like(v.d)
        for w in range(W):
            h = _vote(v.col(cf[w]), ct[w], cp[w])
            Macc += v.expand(ca[w] * sel[:, w].astype(_F32)) * h
        wgt = v.d * np.exp(-v.y * Macc)
        Z = v.segsum(wgt)
        v.put(wgt / (v.expand(Z) + 1e-30))
        for c in self.clients:
            c.last_merged_idx = w1

    def _catch_up_client(self, c) -> None:
        """Per-client capped catch-up at its own sync (enhanced mode):
        the foreign learners of [last_merged_idx, hi) that the reference
        reverse scan picks, folded into one exponential.  A client owns
        one learner per round at most."""
        L = self._learners
        idxs = _newest_foreign(L["owner"], c.last_merged_idx, c.cid,
                              self.cfg.catch_up_cap, self.cfg.n_rounds)
        c.last_merged_idx = L.n
        if not len(idxs):
            return
        sh = self.shards
        rows = sh.rows_of(c.cid)
        f, thr, pol, a = (L[k][idxs] for k in ("f", "thr", "pol", "alpha"))
        h = _vote(sh.X[rows][:, f], thr[None, :], pol[None, :])
        wgt = sh.D[rows] * np.exp(-sh.Y[rows] * (h @ a))
        Z = float(np.sum(wgt, dtype=_F32))
        sh.D[rows] = wgt / (Z + 1e-30)

    # ---------------------------------------------------------------- run
    def run(self) -> None:
        """Run the job and finalize the engine's metrics.  The resident
        rows and grids are released at the end, so they are gone before
        the next job's grid launch."""
        try:
            if self.m.mode == "baseline":
                self._run_baseline()
            else:
                self._run_enhanced()
            with obs.span("train.fleet.finish"):
                # hand the accumulated margins back so the engine's
                # _finalize / _val_error see the fleet-computed state
                self.eng._val_margin, self.eng._test_margin = (
                    _to_device(self.Mval, self.Mtest))
                self.eng._finalize()
        finally:
            self.shards.release()

    def _run_baseline(self) -> None:
        """Synchronous baseline, fleet profile.  Same TRIGGER/BARRIER
        event structure as the reference event core; per-message ARRIVAL
        events are folded into the barrier payload — the barrier consumes
        the round's messages in client order regardless, and a heap push
        per message at 100k clients buys nothing."""
        cfg, m, eng = self.cfg, self.m, self.eng
        vc = events.VirtualClock()
        B = len(self.clients)
        all_slots = np.arange(B)
        pending_late: List[Tuple[int, _FleetEntry]] = []
        t = 0.0
        vc.push(0.0, events.TRIGGER, payload=0)
        while vc:
            ev = vc.pop()
            if ev.kind == events.TRIGGER:
                r, t0 = ev.payload, ev.t
                f, thr, pol = self._fit_wave(all_slots)
                eps, alpha = self._local_update(all_slots, f, thr, pol)
                late, pending_late = pending_late, []
                wave: List[_FleetEntry] = []
                on_time: List[Tuple[int, _FleetEntry]] = []
                durations: List[float] = []
                with obs.span("train.fleet.walk"):
                    for b, c in enumerate(self.clients):
                        dropped = not c.behavior.availability(t0)
                        dur = c.behavior.compute_time(eng.BASE_ROUND_S, t0)
                        e = _FleetEntry(
                            {"feature": int(f[b]),
                             "threshold": float(thr[b]),
                             "polarity": float(pol[b])},
                            float(eps[b]), float(alpha[b]), c.local_round)
                        wave.append(e)
                        c.local_round += 1
                        if dropped:
                            m.rounds_unavailable += 1
                            pending_late.append((b, e))
                            continue
                        up = self._entry_bytes + cfg.header_bytes
                        m.uplink_bytes += up
                        m.n_messages += 1
                        durations.append(
                            dur + c.behavior.link(t0).tx_time(up))
                        on_time.append((b, e))
                self._tabulate(wave, f, thr, pol)
                close = t0 + (max(durations) if durations
                              else eng.BASE_ROUND_S)
                vc.push(close, events.BARRIER, payload=(r, late, on_time))
            elif ev.kind == events.BARRIER:
                r, late, on_time = ev.payload
                t = ev.t
                with obs.span("train.fleet.sync"):
                    for cid, e in late:
                        m.uplink_bytes += (self._entry_bytes
                                           + cfg.header_bytes)
                        m.n_messages += 1
                    w0 = self._learners.n
                    batch = late + on_time
                    self._merge_window([e for _, e in batch],
                                       [cid for cid, _ in batch],
                                       sync_round=r, compensated=False)
                    delta = self._learners.n - w0
                    pkg = delta * 16 + cfg.header_bytes
                    m.downlink_bytes += B * pkg
                    m.n_messages += B
                    self._catch_up_fleet(w0, self._learners.n)
                    m.n_syncs += 1
                    obs.count("train.syncs")
                    obs.count("train.learners_merged", delta)
                    eng._maybe_publish(t)
                    eng._record(t, err=self._val_err())
                if r + 1 < cfg.n_rounds:
                    vc.push(t, events.TRIGGER, payload=r + 1)
        obs.count("train.events", vc.n_popped)
        m.sim_time_s = self._flush_late(pending_late, t)

    def _flush_late(self, pending_late: List[Tuple[int, _FleetEntry]],
                    t: float) -> float:
        """Fleet mirror of the engine's ``_flush_late``: deliver + charge
        the final round's dropped messages, merge them stale-by-one at
        full weight, no downlink/sync tick."""
        cfg, m = self.cfg, self.m
        if not pending_late:
            return t
        with obs.span("train.fleet.sync"):
            t_flush = t
            for cid, e in pending_late:
                c = self.clients[cid]
                up = self._entry_bytes + cfg.header_bytes
                m.uplink_bytes += up
                m.n_messages += 1
                t_flush = max(t_flush, t + c.behavior.link(t).tx_time(up))
            self._merge_window([e for _, e in pending_late],
                               [cid for cid, _ in pending_late],
                               sync_round=cfg.n_rounds, compensated=False)
            if obs.enabled():
                obs.point("train.late_flush", sim_t0=t_flush,
                          n=len(pending_late))
            self.eng._record(t_flush, err=self._val_err())
        return t_flush

    def _run_enhanced(self) -> None:
        """The paper's algorithm, fleet profile: the reference event loop
        with eager per-client timing walks and deferred, wave-batched
        fits.  Arrivals pop in the same (t, kind, cid) order; a payload
        still holding unresolved fits triggers a resolution sweep over
        *every* pending leg — at fleet scale many legs are in flight at
        once, so the sweep's waves stay large."""
        cfg, m, eng = self.cfg, self.m, self.eng
        vc = events.VirtualClock()
        for c in self.clients:
            c.known_interval = eng.scheduler.current
        finished = [False] * len(self.clients)

        def advance(c) -> None:
            while c.local_round < cfg.n_rounds:
                dropped = not c.behavior.availability(c.clock)
                e = self._defer_fit(c)
                c.clock += c.behavior.compute_time(eng.BASE_ROUND_S,
                                                   c.clock)
                c.buffer.entries.append(e)
                if dropped:
                    m.rounds_unavailable += 1
                    c.clock += c.behavior.stall_time(eng.BASE_ROUND_S,
                                                     c.clock)
                if len(c.buffer) >= c.known_interval:
                    arrival, payload = self._prepare_sync(c)
                    vc.push(arrival, events.ARRIVAL, c.cid, payload)
                    return
            finished[c.cid] = True
            if len(c.buffer):             # flush the tail buffer
                arrival, payload = self._prepare_sync(c)
                vc.push(arrival, events.ARRIVAL, c.cid, payload)

        with obs.span("train.fleet.walk"):
            for c in self.clients:
                advance(c)
        t = 0.0
        interval_gauge = obs.get_registry().gauge("train.interval")
        while vc:
            ev = vc.pop()
            t, cid, payload = ev.t, ev.cid, ev.payload
            if any(e.params is None for e in payload):
                self._resolve_pending()
            with obs.span("train.fleet.sync"):
                c = self.clients[cid]
                sync_round = c.local_round - 1
                self._merge_window(payload, [cid] * len(payload),
                                   sync_round=sync_round, compensated=True)
                m.n_syncs += 1
                obs.count("train.syncs")
                obs.count("train.learners_merged", len(payload))
                err = self._val_err()
                eng.scheduler.observe(err)
                delta = self._learners.n - c.last_merged_idx
                pkg = delta * 16 + cfg.header_bytes
                m.downlink_bytes += pkg
                m.n_messages += 1
                self._catch_up_client(c)
                c.known_interval = eng.scheduler.current
                interval_gauge.set(eng.scheduler.current)
                eng._maybe_publish(t)
                eng._record(t, err=err)
                if not finished[cid]:
                    advance(c)
        obs.count("train.events", vc.n_popped)
        m.sim_time_s = max(t, max(c.clock for c in self.clients))

    def _prepare_sync(self, c) -> Tuple[float, List[_FleetEntry]]:
        """Fleet mirror of the engine's ``_prepare_sync``.  The relevance
        filter needs the buffered alphas, so an enabled filter forces the
        pending fits to resolve first (the filter is off in the shipped
        fleet scenarios — it would serialize the waves)."""
        cfg, m = self.cfg, self.m
        if cfg.relevance_filter > 0 and len(c.buffer) > 1:
            if any(e.params is None for e in c.buffer.entries):
                self._resolve_pending()
            now = c.local_round - 1
            entries = c.buffer.entries
            w = [abs(e.alpha) * staleness_scale(
                    max(0, now - e.round_stamp), cfg.compensation)
                 for e in entries]
            cut = cfg.relevance_filter * max(w)
            kept = [e for e, wi in zip(entries, w) if wi >= cut]
            c.buffer.entries = kept if kept else entries[-1:]
        nbytes = (len(c.buffer) * self._entry_bytes + cfg.header_bytes)
        payload = c.buffer.flush()
        arrival = c.clock + c.behavior.link(c.clock).tx_time(nbytes)
        m.uplink_bytes += nbytes
        m.n_messages += 1
        return arrival, payload
