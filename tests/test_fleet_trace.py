"""Phase spans, transfer counters and wave staging of the vectorized fleet
profile: every ``train.fleet.*`` span of a job, no span or point per client,
the host-to-device byte counter against the sum computed from the shapes,
and the fit waves that read the stack and grids the threshold launch left
on the device, which give the same learners bit for bit as waves gathered
on the host."""
import weakref

import numpy as np
import pytest

from repro import obs
from repro.configs.paper_fedboost import (DomainConfig, FedBoostConfig,
                                          SchedulerConfig)
from repro.core import FederatedBoostEngine
from repro.core import fleet
from repro.data import make_domain_data
from repro.launch.obs_report import check_trace

PHASES = {"train.fleet.run", "train.fleet.stack", "train.fleet.thresholds",
          "train.fleet.grid_wait", "train.fleet.walk", "train.fit_batch",
          "train.fleet.gather", "train.fleet.h2d", "train.fleet.fit",
          "train.fleet.local_update", "train.fleet.sync",
          "train.fleet.finish"}
PER_CLIENT_POINTS = {"train.client_round", "train.stall", "train.trigger"}


def _engine(n_clients: int, mode: str = "enhanced", n_rounds: int = 2):
    """A fleet of ``n_clients`` devices of about 12 rows and 8 features
    with dropouts, through 2-round buffers: at two rounds every client
    syncs once and every wave is the whole fleet; at more, the waves after
    a sync hold only the clients that synced."""
    dom = DomainConfig(name="mobile", n_samples=12 * n_clients,
                       n_features=8, n_clients=n_clients, noniid_alpha=0.5,
                       label_imbalance=0.5, noise=0.15, straggler_factor=4.0,
                       dropout_prob=0.2, link_mbps=5.0)
    data = make_domain_data(dom, seed=0, partitioner="iid")
    cfg = FedBoostConfig(n_clients=n_clients, n_rounds=n_rounds, seed=3,
                         straggler_factor=dom.straggler_factor,
                         dropout_prob=dom.dropout_prob,
                         link_mbps=dom.link_mbps, catch_up_cap=4,
                         scheduler=SchedulerConfig(i_init=2))
    return FederatedBoostEngine(cfg, data, mode, fleet=True), data


def _traced_job(n_clients: int, mode: str = "enhanced"):
    eng, data = _engine(n_clients, mode)
    with obs.tracing(profile_kernels=False) as tr:
        m = eng.run()
        spans = tr.finished()
        counters = {name: c.value for name, _, c in
                    obs.get_registry().counters()}
    return m, spans, counters, data


@pytest.mark.parametrize("mode", ["baseline", "enhanced"])
def test_every_fleet_phase_span_appears_and_the_trace_is_sound(mode):
    m, spans, _, _ = _traced_job(64, mode)
    names = {d["name"] for d in spans}
    assert PHASES <= names
    assert check_trace(spans) == []
    # one root per job, holding every phase
    roots = [d for d in spans if d["parent"] is None]
    assert [d["name"] for d in roots] == ["train.fleet.run"]
    # the children named for a wave sit under the wave's span
    by_id = {d["span"]: d for d in spans}
    for d in spans:
        if d["name"] in ("train.fleet.fit", "train.fleet.local_update"):
            parent = by_id[d["parent"]]["name"]
            assert parent == ("train.fit_batch" if d["name"] ==
                              "train.fleet.fit" else "train.fleet.run")
    syncs = sum(d["name"] == "train.fleet.sync" for d in spans)
    assert syncs >= m.n_syncs


@pytest.mark.parametrize("mode", ["baseline", "enhanced"])
def test_h2d_bytes_equal_the_sum_from_the_shapes(mode):
    _, spans, counters, data = _traced_job(64, mode)
    B = len(data["clients"])
    N = max(len(y) for _, y in data["clients"])
    F = data["clients"][0][0].shape[1]
    T = 16                                 # the stump grid's thresholds
    chunk = min(fleet.THRESHOLD_CHUNK, fleet._next_pow2(B))
    grids = chunk * N * F * 4 + chunk * 4  # rows (f32) and counts (i32)
    waves = [d["attrs"]["padded"] for d in spans
             if d["name"] == "train.fit_batch"]
    # every wave here is the whole fleet in order, so it reads the rows and
    # grids the threshold launch left on the device and uploads only its
    # labels and weights; a host-staged wave would add BP * (N*F + F*T) * 4
    assert chunk == max(8, fleet._next_pow2(B))
    assert counters["train.fleet.resident_waves"] == len(waves)
    wave = sum(BP * 2 * N * 4 for BP in waves)
    margins = (len(data["val"][1]) + len(data["test"][1])) * 4
    assert counters["train.fleet.h2d_bytes"] == grids + wave + margins
    assert counters["train.fleet.slots_launched"] == sum(waves)
    assert counters["train.fleet.rows_launched"] == sum(waves) * N
    rows = sum(len(y) for _, y in data["clients"])
    assert counters["train.fleet.real_rows"] == 2 * rows   # two rounds
    assert counters["train.fits"] == 2 * B
    # a wave gathers its labels and weights, its update the fitted column,
    # the labels and the weights: five (B, N) f32 copies, no pad (B = 64)
    assert counters["train.fleet.gather_bytes"] == len(waves) * 5 * B * N * 4


def test_no_span_is_per_client():
    """Doubling the fleet changes nothing but the number of syncs."""
    def phase_counts(n):
        _, spans, _, _ = _traced_job(n)
        out = {}
        for d in spans:
            if d["name"] != "train.fleet.sync":
                out[d["name"]] = out.get(d["name"], 0) + 1
        return out, sum(d["name"] == "train.fleet.sync" for d in spans)

    small, syncs_small = phase_counts(64)
    large, syncs_large = phase_counts(128)
    assert small == large
    assert sum(small.values()) == 20
    assert syncs_large > syncs_small


def test_fleet_profile_emits_no_per_client_points():
    m, spans, counters, _ = _traced_job(64)
    assert not PER_CLIENT_POINTS & {d["name"] for d in spans}
    # the clock events popped are the same with tracing off
    eng, _ = _engine(64)
    old = obs.set_registry(obs.MetricsRegistry())
    try:
        eng.run()
        untraced = obs.get_registry().counter("train.events").value
    finally:
        obs.set_registry(old)
    assert counters["train.events"] == untraced
    assert np.isfinite(m.final_val_error)


def _job(monkeypatch, n_clients: int, mode: str, n_rounds: int = 2):
    """One fleet job; returns the core, its metrics, its counters, each
    wave's (eps, alpha) and weak references to the resident arrays taken
    before the run."""
    waves = []
    update = fleet.FleetCore._local_update

    def record(core, slots, f, thr, pol):
        eps, alpha = update(core, slots, f, thr, pol)
        waves.append((slots.copy(), eps.copy(), alpha.copy()))
        return eps, alpha

    monkeypatch.setattr(fleet.FleetCore, "_local_update", record)
    eng, _ = _engine(n_clients, mode, n_rounds)
    old = obs.set_registry(obs.MetricsRegistry())
    try:
        core = fleet.FleetCore(eng)
        refs = [weakref.ref(a) for a in (core.shards._Xd, core.shards._THRd)
                if a is not None]
        core.run()
        counters = {name: c.value for name, _, c in
                    obs.get_registry().counters()}
    finally:
        obs.set_registry(old)
    monkeypatch.setattr(fleet.FleetCore, "_local_update", update)
    return core, eng.metrics, counters, waves, refs


def _assert_same_learning(a, b):
    core_a, m_a, _, waves_a, _ = a
    core_b, m_b, _, waves_b, _ = b
    for col in ("f", "thr", "pol", "alpha", "owner"):
        assert np.array_equal(core_a._learners[col],
                              core_b._learners[col]), col
    assert len(waves_a) == len(waves_b)
    for (sa, ea, aa), (sb, eb, ab) in zip(waves_a, waves_b):
        assert np.array_equal(sa, sb)
        assert np.array_equal(ea, eb)
        assert np.array_equal(aa, ab)
    assert np.array_equal(core_a.D, core_b.D)
    assert m_a.final_val_error == m_b.final_val_error
    assert m_a.final_test_error == m_b.final_test_error


@pytest.mark.parametrize("mode", ["baseline", "enhanced"])
def test_resident_waves_match_host_staged_waves_bit_for_bit(monkeypatch,
                                                            mode):
    resident = _job(monkeypatch, 64, mode)
    # a threshold chunk below the fleet: several chunks, nothing resident
    monkeypatch.setattr(fleet, "THRESHOLD_CHUNK", 32)
    host = _job(monkeypatch, 64, mode)
    _assert_same_learning(resident, host)
    c_res, c_host = resident[2], host[2]
    assert c_res["train.fit_batches"] == 2
    assert c_res["train.fleet.resident_waves"] == c_res["train.fit_batches"]
    assert c_host.get("train.fleet.resident_waves", 0) == 0
    assert c_host["train.fit_batches"] == c_res["train.fit_batches"]
    # the resident path uploads no rows and no grids with its waves
    assert c_res["train.fleet.h2d_bytes"] < c_host["train.fleet.h2d_bytes"]
    assert host[4] == []                  # nothing was kept on the device
    assert len(resident[4]) == 2
    for ref in resident[4]:               # released by the end of run()
        assert ref() is None
    for core, *_ in (resident, host):
        assert core.shards._Xd is None and core.shards._THRd is None


def test_partial_waves_take_the_host_path(monkeypatch):
    """At four rounds through 2-round buffers the first two waves are the
    whole fleet and the waves after a sync hold only the clients that
    synced: those gather on the host and still match the host-only run."""
    resident = _job(monkeypatch, 64, "enhanced", n_rounds=4)
    monkeypatch.setattr(fleet, "THRESHOLD_CHUNK", 32)
    host = _job(monkeypatch, 64, "enhanced", n_rounds=4)
    _assert_same_learning(resident, host)
    c = resident[2]
    partial = sum(len(s) < 64 for s, _, _ in resident[3])
    assert partial > 0
    assert c["train.fleet.resident_waves"] == 2
    assert c["train.fit_batches"] == 2 + partial
    assert host[2].get("train.fleet.resident_waves", 0) == 0


@pytest.mark.parametrize("slots", [np.arange(63), np.arange(64)[::-1],
                                   np.r_[1, 0, np.arange(2, 64)]],
                         ids=["partial", "reversed", "swapped"])
def test_only_the_whole_fleet_in_order_reads_the_resident_stack(slots):
    core = fleet.FleetCore(_engine(64)[0])
    assert core.shards._Xd.shape[0] == 64 and core.shards._THRd.shape[0] == 64
    assert core.shards.wave(np.arange(64)) == (64, True)
    assert core.shards.wave(slots) == (max(8, fleet._next_pow2(
        len(slots))), False)


@pytest.mark.parametrize("mode", ["baseline", "enhanced"])
@pytest.mark.parametrize("layout", ["padded", "packed"])
def test_the_benchmark_taps_see_every_wave(monkeypatch, layout, mode):
    """The benchmark's drivers follow a job by wrapping
    ``FleetCore._local_update`` on the class and the layout's weak fit on
    ``repro.models.weak``, as below: each must be called through those
    names exactly once per fit wave, resident or gathered on the host."""
    import repro.models.weak as weak
    packed = layout == "packed"
    monkeypatch.setattr(fleet, "PACKED_BELOW_FILL", 2.0 if packed else 0.0)
    name = "fit_stump_ragged" if packed else "fit_stump_batched"
    fit, update = getattr(weak, name), fleet.FleetCore._local_update
    calls, layouts = {"fit": 0, "update": 0}, set()

    def fit_tap(*args, **kw):
        calls["fit"] += 1
        return fit(*args, **kw)

    def update_tap(core, slots, f, thr, pol):
        calls["update"] += 1
        layouts.add(core.packed)
        return update(core, slots, f, thr, pol)

    monkeypatch.setattr(weak, name, fit_tap)
    monkeypatch.setattr(fleet.FleetCore, "_local_update", update_tap)
    eng, _ = _engine(64, mode, n_rounds=4)
    old = obs.set_registry(obs.MetricsRegistry())
    try:
        eng.run()
        reg = obs.get_registry()
        waves = reg.counter("train.fit_batches").value
        resident = reg.counter("train.fleet.resident_waves").value
    finally:
        obs.set_registry(old)
    assert layouts == {packed}
    assert resident > 0
    if mode == "enhanced":            # waves after a sync are partial
        assert resident < waves
    assert calls == {"fit": waves, "update": waves}


def test_a_fleet_below_eight_clients_keeps_the_host_path(monkeypatch):
    """Four clients grid in a chunk of 4 but launch waves of 8 slots."""
    core, _, counters, _, _ = _job(monkeypatch, 4, "baseline")
    assert counters["train.fit_batches"] == 2
    assert counters.get("train.fleet.resident_waves", 0) == 0


def test_a_fleet_off_the_power_of_two_reads_the_padded_chunk(monkeypatch):
    """100 clients grid in a chunk of 128 with 28 zero slots; the waves pad
    to the same 128 and read that chunk as it is."""
    resident = _job(monkeypatch, 100, "baseline")
    monkeypatch.setattr(fleet, "THRESHOLD_CHUNK", 64)
    host = _job(monkeypatch, 100, "baseline")
    _assert_same_learning(resident, host)
    assert resident[2]["train.fleet.resident_waves"] == 2
    assert host[2].get("train.fleet.resident_waves", 0) == 0


def _packed_engine(n_clients: int, mode: str = "enhanced"):
    """A fleet of LEAF Sent140's shape: most devices hold one or two rows,
    a few dozens, so the padded stack would be mostly padding and the
    fleet packs."""
    rng = np.random.default_rng(0)
    n = np.minimum(1 + rng.negative_binomial(0.0971, 0.0640, n_clients), 40)
    n[1] = 40
    R = int(n.sum())
    x = rng.normal(size=(R + 40, 8)).astype(np.float32)
    y = np.where(rng.random(R + 40) < 0.5, 1.0, -1.0).astype(np.float32)
    cut = np.cumsum(n)
    data = {"clients": [(x[s:e], y[s:e])
                        for s, e in zip(np.r_[0, cut[:-1]], cut)],
            "val": (x[R:R + 20], y[R:R + 20]),
            "test": (x[R + 20:], y[R + 20:])}
    cfg = FedBoostConfig(n_clients=n_clients, n_rounds=2, seed=3,
                         dropout_prob=0.2, catch_up_cap=4,
                         scheduler=SchedulerConfig(i_init=2))
    return FederatedBoostEngine(cfg, data, mode, fleet=True), data


@pytest.mark.parametrize("mode", ["baseline", "enhanced"])
def test_packed_fleet_spans_and_counters_add_up(mode):
    """The packed layout emits train.fleet.pack in place of the stack, and
    its counters add up: every wave here is every client in order, so it
    reads the resident rows and grids; the rows it launches hold every
    real row and the alignment padding, no more."""
    eng, data = _packed_engine(64, mode)
    with obs.tracing(profile_kernels=False) as tr:
        eng.run()
        spans = tr.finished()
        counters = {name: c.value for name, _, c in
                    obs.get_registry().counters()}
    names = {d["name"] for d in spans}
    assert "train.fleet.pack" in names and "train.fleet.stack" not in names
    assert PHASES - {"train.fleet.stack"} <= names
    assert check_trace(spans) == []
    B = len(data["clients"])
    rows = sum(len(y) for _, y in data["clients"])
    Rp = -(-rows // fleet.ROW_ALIGN) * fleet.ROW_ALIGN
    waves = counters["train.fit_batches"]
    assert waves == 2 and counters["train.fleet.resident_waves"] == waves
    assert counters["train.fleet.real_rows"] == waves * rows
    assert counters["train.fleet.packed_rows_launched"] == waves * Rp
    assert (counters["train.fleet.packed_rows_launched"]
            >= counters["train.fleet.real_rows"])
    assert counters["train.fleet.segments_launched"] == waves * B
    assert counters["train.fits"] == waves * B
    assert "train.fleet.slots_launched" not in counters
    # the grid launch's rows (f32), row clients (i32) and counts (i32),
    # each wave's labels and weights, the margins: no grid and no rows
    # go up with a wave
    margins = (len(data["val"][1]) + len(data["test"][1])) * 4
    assert counters["train.fleet.h2d_bytes"] == (
        Rp * 8 * 4 + Rp * 4 + B * 4 + waves * 2 * Rp * 4 + margins)
