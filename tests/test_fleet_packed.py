"""The fleet profile's packed layout against its padded one, on the same
heavy-tailed fleet: most clients hold one or two rows, a few hold dozens.

Both runs see the same clients, rows and behaviours; only the layout of
the shards differs, and the layout is steered here through the fill below
which the fleet packs (a fleet chooses it from its own shape).  The
accounting (syncs, rounds, bytes) is the same integer bookkeeping on both
layouts and must be equal.  The learning must agree as far as floating
point lets it: the packed path sums each client's rows in segment order
(``np.add.reduceat``), the padded one sums whole padded rows pairwise, and
the ragged fit sums in row order where the batched one contracts; a
float32 sum of at most a hundred weights in [0, 1] is then off by a few
units in the last place (6e-8 each), so eps, alphas and distributions
agree within 1e-6 — after several waves of updates and normalizations, a
margin of about 16 units — and the stumps are equal wherever the
reference's best stump beats every other by more than 1e-6.  Where it
does not, two stumps tie and either layout may take either; the tied
clients' learners then reach every client through the catch-ups, so the
waves after are not compared.  Alphas agree within 16 times that margin:
near eps = 1e-6 the log in the vote weight magnifies eps's error.
"""
import numpy as np
import pytest

from repro.configs.paper_fedboost import FedBoostConfig, SchedulerConfig
from repro.core import FederatedBoostEngine
from repro.core import fleet

TIE = 1e-6            # a best stump closer than this to another is a tie
TOL = 1e-6            # eps and distributions (module docstring)


def sent140_like_counts(n_clients: int, seed: int = 0) -> np.ndarray:
    """LEAF Sent140's per-device counts, 1 + a negative binomial with mean
    1.42 and variance 4.71**2, capped at 40 rows, with two clients of 40
    and 33 rows so that some clients span several row blocks."""
    rng = np.random.default_rng(seed)
    n = np.minimum(1 + rng.negative_binomial(0.0971, 0.0640, n_clients), 40)
    n[3], n[10] = 40, 33
    return n


def heavy_tailed_fleet(n_clients: int, n_features: int = 8, seed: int = 0):
    """Rows of the repository's cluster problem dealt by the counts above,
    with 100 rows each held out for validation and for test."""
    rng = np.random.default_rng(seed)
    n = sent140_like_counts(n_clients, seed)
    R = int(n.sum()) + 200
    centers = 2.0 * rng.normal(size=(6, n_features))
    assign = rng.integers(0, 6, R)
    x = (centers[assign] + rng.normal(size=(R, n_features))).astype(
        np.float32)
    label = np.array([1.0, -1.0, 1.0, -1.0, 1.0, 1.0])[assign]
    y = np.where(rng.random(R) < 0.15, -label, label).astype(np.float32)
    cut = np.cumsum(n)
    clients = [(x[s:e], y[s:e]) for s, e in zip(np.r_[0, cut[:-1]], cut)]
    o = int(cut[-1])
    return {"clients": clients, "val": (x[o:o + 100], y[o:o + 100]),
            "test": (x[o + 100:o + 200], y[o + 100:o + 200])}


def _rows(core, b):
    return (core.D[core.off[b]:core.off[b + 1]] if core.packed
            else core.D[b, :core.n_valid[b]])


def _job(monkeypatch, data, mode: str, packed: bool, n_rounds: int,
         balanced: bool):
    """One fleet job on ``data``; returns the metrics and, per fit wave,
    the slots, each slot's distribution before the fit, the stumps, eps,
    alphas and each slot's distribution after the update."""
    monkeypatch.setattr(fleet, "PACKED_BELOW_FILL", 2.0 if packed else 0.0)
    waves = []
    update = fleet.FleetCore._local_update

    def record(core, slots, f, thr, pol):
        assert core.packed == packed
        before = [_rows(core, b).copy() for b in slots]
        eps, alpha = update(core, slots, f, thr, pol)
        waves.append({"slots": slots.copy(), "D0": before, "f": f.copy(),
                      "thr": thr.copy(), "pol": pol.copy(),
                      "eps": eps.copy(), "alpha": alpha.copy(),
                      "D1": [_rows(core, b).copy() for b in slots]})
        return eps, alpha

    monkeypatch.setattr(fleet.FleetCore, "_local_update", record)
    cfg = FedBoostConfig(n_clients=len(data["clients"]), n_rounds=n_rounds,
                         seed=3, straggler_factor=4.0, dropout_prob=0.2,
                         link_mbps=5.0, catch_up_cap=4,
                         balanced_init=balanced,
                         scheduler=SchedulerConfig(i_init=2))
    m = FederatedBoostEngine(cfg, data, mode, fleet=True).run()
    monkeypatch.setattr(fleet.FleetCore, "_local_update", update)
    return m, waves


def _errors(x, y, D, T: int = 16):
    """Every stump's weighted error on one client's rows, in float64:
    (2, F, T) for polarity +1 and -1, and the (F, T) grid."""
    qs = np.linspace(0.0, 1.0, T + 2)[1:-1]
    grid = np.quantile(x.astype(np.float64), qs, axis=0).T.astype(
        np.float32)
    side = np.where(x[:, :, None] > grid[None], 1.0, -1.0)
    err = np.einsum("n,nft->ft", D.astype(np.float64),
                    (side != y[:, None, None]).astype(np.float64))
    return np.stack([err, 1.0 - err]), grid


def _stump_error(err, grid, f, thr, pol):
    t = int(np.argmin(np.abs(grid[f] - thr)))
    return err[0 if pol > 0 else 1, f, t]


@pytest.mark.parametrize("balanced", [False, True],
                         ids=["uniform", "balanced"])
@pytest.mark.parametrize("n_rounds", [2, 4], ids=["2rounds", "4rounds"])
@pytest.mark.parametrize("mode", ["baseline", "enhanced"])
def test_packed_job_matches_padded_job(monkeypatch, mode, n_rounds,
                                       balanced):
    data = heavy_tailed_fleet(64)
    m_pad, w_pad = _job(monkeypatch, data, mode, False, n_rounds, balanced)
    m_pk, w_pk = _job(monkeypatch, data, mode, True, n_rounds, balanced)
    for k in ("n_syncs", "uplink_bytes", "downlink_bytes", "n_messages",
              "learners_merged", "rounds_unavailable", "sim_time_s"):
        assert getattr(m_pad, k) == getattr(m_pk, k), k
    assert len(w_pad) == len(w_pk)
    compared, tied = 0, False
    for a, b in zip(w_pad, w_pk):
        assert np.array_equal(a["slots"], b["slots"])
        for j, cid in enumerate(a["slots"].tolist()):
            np.testing.assert_allclose(b["D0"][j], a["D0"][j], atol=TOL)
            x, y = data["clients"][cid]
            err, grid = _errors(x, y, a["D0"][j])
            best = np.sort(err.ravel())
            same = (a["f"][j] == b["f"][j] and a["thr"][j] == b["thr"][j]
                    and a["pol"][j] == b["pol"][j])
            if best[1] - best[0] > TIE:
                assert same, (cid, a["f"][j], b["f"][j])
            if not same:
                # a tie: the packed stump is as good as the padded one
                assert abs(_stump_error(err, grid, b["f"][j], b["thr"][j],
                                        b["pol"][j]) - best[0]) <= TIE
                tied = True
                continue
            compared += 1
            assert abs(a["eps"][j] - b["eps"][j]) <= TOL
            assert abs(a["alpha"][j] - b["alpha"][j]) <= 16 * TOL
            np.testing.assert_allclose(b["D1"][j], a["D1"][j], atol=TOL)
        if tied:
            # the tied clients' learners reach every client's catch-up,
            # so no later wave can be compared
            break
    assert compared >= len(data["clients"])
    if not tied:
        assert m_pad.final_val_error == m_pk.final_val_error


@pytest.mark.parametrize("counts, packed", [
    (sent140_like_counts(64), True),
    (np.full(64, 12), False),
    (np.r_[np.full(60, 12), [30, 2, 5, 1]], False)],
    ids=["heavy_tail", "even_split", "femnist_like"])
def test_the_layout_follows_the_fleet_shape(counts, packed):
    """A fleet whose padded stack would be mostly padding packs; an even
    split, or one whose stack is mostly real rows, stacks."""
    rng = np.random.default_rng(1)
    data = heavy_tailed_fleet(len(counts))
    x = rng.normal(size=(int(counts.sum()), 8)).astype(np.float32)
    y = np.where(rng.random(len(x)) < 0.5, 1.0, -1.0).astype(np.float32)
    cut = np.cumsum(counts)
    data["clients"] = [(x[s:e], y[s:e])
                       for s, e in zip(np.r_[0, cut[:-1]], cut)]
    cfg = FedBoostConfig(n_clients=len(counts), n_rounds=2, seed=3)
    core = fleet.FleetCore(FederatedBoostEngine(cfg, data, "baseline",
                                                fleet=True))
    assert core.packed == packed
    if packed:
        assert core.X.shape[0] % fleet.ROW_ALIGN == 0
        assert core.off[-1] == counts.sum() and core.THR is None
        assert core._THRd.shape == (len(counts), 16, 128)
    else:
        assert core.X.shape == (len(counts), counts.max(), 8)
