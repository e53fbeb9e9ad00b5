"""The fleet profile's server math per arrival against the per-arrival
arithmetic it replaced.

A fit wave tabulates, once, each stump's votes on the held-out rows and
its server alpha before compensation; an arrival reads them, the
validation error is one count, and the capped catch-up picks its learners
from the owner column with array operations.  ``_PerArrival`` below keeps
the per-arrival form: every merge re-scores its stumps on the validation
and test rows and re-derives their alphas, every sync recomputes the
validation error from the margins, and the catch-up scans the owners
backwards in Python.  Both run the same float32 NumPy arithmetic on the
same operands, so every margin, alpha, distribution and error must be
equal to the bit, in both layouts and both modes.
"""
import numpy as np
import pytest

from repro import obs
from repro.configs.paper_fedboost import FedBoostConfig, SchedulerConfig
from repro.core import FederatedBoostEngine
from repro.core import fleet
from repro.core.compensation import staleness_scale

_F32 = np.float32


class _PerArrival(fleet.FleetCore):
    """The fleet core with the server math recomputed at every arrival."""

    def __init__(self, eng) -> None:
        super().__init__(eng)
        self.xv = np.asarray(eng.data["val"][0], _F32)
        self.xt = np.asarray(eng.data["test"][0], _F32)
        self._lf, self._lt, self._lp, self._la = [], [], [], []

    def _merge_window(self, entries, owners, sync_round, compensated):
        if not entries:
            return
        eng, K = self.eng, len(entries)
        f = np.array([e.params["feature"] for e in entries], np.int64)
        thr = np.array([e.params["threshold"] for e in entries], _F32)
        pol = np.array([e.params["polarity"] for e in entries], _F32)
        a = np.empty(K, _F32)
        for lo in range(0, K, fleet.SERVER_CHUNK):
            s = slice(lo, min(lo + fleet.SERVER_CHUNK, K))
            a[s] = self._server_alphas_of(f[s], thr[s], pol[s])
        if compensated:
            scale = np.array(
                [staleness_scale(max(0, sync_round - e.round_stamp),
                                 self.cfg.compensation) for e in entries],
                _F32)
            a = a * scale
        for lo in range(0, K, fleet.SERVER_CHUNK):
            s = slice(lo, min(lo + fleet.SERVER_CHUNK, K))
            hv = pol[s] * np.sign(self.xv[:, f[s]] - thr[s] + 1e-12)
            ht = pol[s] * np.sign(self.xt[:, f[s]] - thr[s] + 1e-12)
            self.Mval += hv @ a[s]
            self.Mtest += ht @ a[s]
        for e, owner, ai in zip(entries, owners, a.tolist()):
            eng.ensemble.add(e.params, ai)
            eng._owners.append(owner)
            eng._round_stamps.append(e.round_stamp)
            self._lf.append(e.params["feature"])
            self._lt.append(e.params["threshold"])
            self._lp.append(e.params["polarity"])
            self._la.append(ai)
        # the loops and the fleet-wide catch-up read the learner columns
        self._learners.extend(f=f, thr=thr, pol=pol, alpha=a, owner=owners)
        self.m.learners_merged += K

    def _server_alphas_of(self, f, thr, pol):
        h = pol[None, :] * np.sign(self.xv[:, f] - thr[None, :] + 1e-12)
        pred = np.where(h > 0, 1.0, -1.0).astype(_F32)
        yv = self.yv[:, None]
        miss = pred != yv
        if self.cfg.balanced_init:
            pos, neg = yv > 0, yv < 0
            ep = np.sum(miss & pos, axis=0) / max(float(pos.sum()), 1.0)
            en = np.sum(miss & neg, axis=0) / max(float(neg.sum()), 1.0)
            eps = np.clip(0.5 * (ep + en), 0.02, 0.98)
        else:
            eps = np.clip(miss.mean(axis=0), 0.02, 0.98)
        return (0.5 * np.log((1.0 - eps) / eps)).astype(_F32)

    def _val_err(self):
        pred = np.where(self.Mval > 0, 1.0, -1.0)
        return float(np.mean(pred != self.yv))

    def _catch_up_client(self, c):
        lo, hi = c.last_merged_idx, len(self._lf)
        cap = self.cfg.catch_up_cap
        owners = self.eng._owners
        if cap is None:
            idxs = [i for i in range(lo, hi) if owners[i] != c.cid]
        else:
            idxs = _reverse_scan(owners, lo, c.cid, cap)
        c.last_merged_idx = hi
        if not idxs:
            return
        b = c.cid
        f = np.array([self._lf[i] for i in idxs], np.int64)
        thr = np.array([self._lt[i] for i in idxs], _F32)
        pol = np.array([self._lp[i] for i in idxs], _F32)
        a = np.array([self._la[i] for i in idxs], _F32)
        rows = (slice(self.off[b], self.off[b + 1]) if self.packed
                else b)
        X, Y = self.shards.X, self.shards.Y
        h = pol[None, :] * np.sign(X[rows][:, f] - thr[None, :] + 1e-12)
        wgt = self.D[rows] * np.exp(-Y[rows] * (h @ a))
        Z = float(np.sum(wgt, dtype=_F32))
        self.D[rows] = wgt / (Z + 1e-30)


def _reverse_scan(owners, lo, cid, cap):
    """The newest ``cap`` learners of [lo, len(owners)) that ``cid`` does
    not own, oldest first, found by walking back from the newest."""
    idxs, i = [], len(owners) - 1
    while i >= lo and len(idxs) < cap:
        if owners[i] != cid:
            idxs.append(i)
        i -= 1
    idxs.reverse()
    return idxs


def _fleet(n_clients: int, seed: int = 0):
    """A heavy-tailed fleet (most clients hold one or two rows, a few up
    to 40) of a noisy cluster problem, with 100 validation and 61 test
    rows, so the two held-out sets differ in size."""
    rng = np.random.default_rng(seed)
    n = np.minimum(1 + rng.negative_binomial(0.0971, 0.0640, n_clients), 40)
    n[3] = 40
    R = int(n.sum()) + 161
    centers = 2.0 * rng.normal(size=(6, 8))
    assign = rng.integers(0, 6, R)
    x = (centers[assign] + rng.normal(size=(R, 8))).astype(_F32)
    label = np.array([1.0, -1.0, 1.0, -1.0, 1.0, 1.0])[assign]
    y = np.where(rng.random(R) < 0.15, -label, label).astype(_F32)
    cut = np.cumsum(n)
    o = int(cut[-1])
    return {"clients": [(x[s:e], y[s:e])
                        for s, e in zip(np.r_[0, cut[:-1]], cut)],
            "val": (x[o:o + 100], y[o:o + 100]),
            "test": (x[o + 100:], y[o + 100:])}


def _run(monkeypatch, core_cls, data, mode, packed, cap, interval=2,
         balanced=False):
    monkeypatch.setattr(fleet, "PACKED_BELOW_FILL", 2.0 if packed else 0.0)
    cfg = FedBoostConfig(n_clients=len(data["clients"]), n_rounds=4, seed=3,
                         straggler_factor=4.0, dropout_prob=0.2,
                         link_mbps=5.0, catch_up_cap=cap,
                         balanced_init=balanced,
                         scheduler=SchedulerConfig(i_init=interval))
    eng = FederatedBoostEngine(cfg, data, mode, fleet=True)
    old = obs.set_registry(obs.MetricsRegistry())
    try:
        core = core_cls(eng)
        assert core.packed == packed
        core.run()
        counters = {name: c.value for name, _, c in
                    obs.get_registry().counters()}
    finally:
        obs.set_registry(old)
    return eng, core, counters


def _bits(a):
    return np.asarray(a).tobytes()


@pytest.mark.parametrize(
    "layout, mode, cap, n_clients, interval, chunk, balanced", [
        ("padded", "enhanced", 4, 64, 3, 4096, False),
        ("padded", "enhanced", None, 128, 2, 4096, True),
        ("packed", "enhanced", 16, 256, 3, 4096, False),
        ("packed", "enhanced", None, 64, 2, 4096, True),
        ("padded", "baseline", 4, 128, 2, 4096, False),
        ("packed", "baseline", None, 64, 2, 4096, True),
        ("padded", "baseline", None, 256, 2, 48, False),
        ("packed", "baseline", 16, 128, 2, 48, False)])
def test_tabulated_server_math_matches_per_arrival_bit_for_bit(
        monkeypatch, layout, mode, cap, n_clients, interval, chunk,
        balanced):
    """Enhanced jobs merge compensated windows of ``interval`` entries (a
    sum of three or more products depends on the fold's operand layout);
    a chunk of 48 columns splits each barrier's merge, and each wave's
    tabulation, into several blocks; balanced jobs weigh the server's
    errors by class."""
    monkeypatch.setattr(fleet, "SERVER_CHUNK", chunk)
    data = _fleet(n_clients)
    packed = layout == "packed"
    windows, merge = [], fleet.FleetCore._merge_window

    def recording_merge(core, entries, *args, **kw):
        merge(core, entries, *args, **kw)
        # every row is released when its learner merges
        windows.append((len(entries), all(e.votes is None
                                          for e in entries)))

    monkeypatch.setattr(fleet.FleetCore, "_merge_window", recording_merge)
    eng_t, core_t, _ = _run(monkeypatch, fleet.FleetCore, data, mode,
                            packed, cap, interval, balanced)
    monkeypatch.setattr(fleet.FleetCore, "_merge_window", merge)
    eng_r, core_r, _ = _run(monkeypatch, _PerArrival, data, mode, packed,
                            cap, interval, balanced)
    m_t, m_r = eng_t.metrics, eng_r.metrics
    assert m_t.val_error_curve == m_r.val_error_curve
    assert _bits(core_t.Mval) == _bits(core_r.Mval)
    assert _bits(core_t.Mtest) == _bits(core_r.Mtest)
    assert _bits(core_t.D) == _bits(core_r.D)
    assert eng_t.ensemble.alphas == eng_r.ensemble.alphas
    assert eng_t.ensemble.learners == eng_r.ensemble.learners
    assert eng_t._owners == eng_r._owners
    assert eng_t._round_stamps == eng_r._round_stamps
    for col in ("f", "thr", "pol", "alpha", "owner"):
        assert _bits(core_t._learners[col]) == _bits(
            core_r._learners[col]), col
    for k in ("learners_merged", "n_syncs", "uplink_bytes",
              "downlink_bytes", "n_messages", "rounds_unavailable",
              "sim_time_s", "final_val_error", "final_test_error",
              "final_test_recall"):
        assert getattr(m_t, k) == getattr(m_r, k), k
    assert all(released for _, released in windows)
    # what the cases are there to exercise
    assert m_t.rounds_unavailable > 0
    K = max(k for k, _ in windows)
    if mode == "baseline":
        # one record per barrier, and one for the late flush
        assert len(m_t.val_error_curve) == 4 + 1
        assert K > chunk or chunk > n_clients
    assert K >= (3 if mode == "baseline" else interval)


def _owner_windows():
    """(owners, lo, cid) windows: the client's own learners inside the
    newest entries, spread through the window, absent, and a window
    shorter than the cap."""
    rng = np.random.default_rng(7)
    others = rng.integers(1, 50, 60).tolist()
    tail_own = others[:52] + [0, 9, 0, 0, 9, 0, 3, 0]
    spread = list(others)
    for i in (5, 17, 40, 58):
        spread[i] = 0
    return [(tail_own, 10, 0), (tail_own, 55, 0), (spread, 0, 0),
            (others, 0, 0), (spread, 50, 0), (tail_own, 60, 0),
            ([0, 0, 0, 0], 0, 0), (others[:5] + [0], 2, 0)]


@pytest.mark.parametrize("cap", [None, 1, 16])
@pytest.mark.parametrize("w", range(len(_owner_windows())))
def test_catch_up_picks_the_reverse_scans_learners(cap, w):
    owners, lo, cid = _owner_windows()[w]
    own_max = sum(o == cid for o in owners)
    want = ([i for i in range(lo, len(owners)) if owners[i] != cid]
            if cap is None else _reverse_scan(owners, lo, cid, cap))
    got = fleet._newest_foreign(np.asarray(owners, np.int64), lo, cid,
                                cap, own_max)
    assert got.tolist() == want


@pytest.mark.parametrize("layout", ["padded", "packed"])
@pytest.mark.parametrize("mode", ["baseline", "enhanced"])
def test_every_fitted_stump_is_tabulated(monkeypatch, layout, mode):
    """``train.fleet.vote_rows`` counts the stumps each wave tabulates:
    every fit, so every merged learner was read from the table."""
    _, _, counters = _run(monkeypatch, fleet.FleetCore, _fleet(64), mode,
                          layout == "packed", 4)
    assert counters["train.fits"] > 0
    assert counters["train.fleet.vote_rows"] == counters["train.fits"]


@pytest.mark.parametrize("balanced", [False, True])
def test_counted_errors_hold_for_any_labels(balanced):
    """Validation labels other than -1 and +1 (which every vote misses)
    give the same validation error and server alphas as the
    per-arrival expressions."""
    data = _fleet(64)
    x, _ = data["val"]
    rng = np.random.default_rng(5)
    data["val"] = (x, rng.choice(np.array([-1.0, 1.0, 0.0, 2.0], _F32),
                                 len(x)))
    cfg = FedBoostConfig(n_clients=64, n_rounds=2, seed=3,
                         balanced_init=balanced)
    core = _PerArrival(FederatedBoostEngine(cfg, data, "baseline",
                                            fleet=True))
    f = rng.integers(0, 8, 50)
    thr = rng.normal(size=50).astype(_F32)
    pol = np.where(rng.random(50) < 0.5, 1.0, -1.0).astype(_F32)
    h = pol[:, None] * np.sign(core.xv[:, f].T - thr[:, None] + 1e-12)
    assert _bits(core._server_alphas(h)) == _bits(
        core._server_alphas_of(f, thr, pol))
    for _ in range(20):
        core.Mval = rng.normal(size=len(x)).astype(_F32)
        assert fleet.FleetCore._val_err(core) == core._val_err()
