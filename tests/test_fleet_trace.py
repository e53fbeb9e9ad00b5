"""Phase spans and transfer counters of the vectorized fleet profile: every
``train.fleet.*`` span of a job, no span or point per client, and the
host-to-device byte counter against the sum computed from the shapes."""
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


def _engine(n_clients: int, mode: str = "enhanced"):
    """A fleet of ``n_clients`` devices of about 12 rows and 8 features,
    two rounds through 2-round buffers (every client syncs once)."""
    dom = DomainConfig(name="mobile", n_samples=12 * n_clients,
                       n_features=8, n_clients=n_clients, noniid_alpha=0.5,
                       label_imbalance=0.5, noise=0.15, straggler_factor=4.0,
                       dropout_prob=0.2, link_mbps=5.0)
    data = make_domain_data(dom, seed=0, partitioner="iid")
    cfg = FedBoostConfig(n_clients=n_clients, n_rounds=2, seed=3,
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
    wave = sum(BP * (N * F + 2 * N + F * T) * 4 for BP in waves)
    margins = (len(data["val"][1]) + len(data["test"][1])) * 4
    assert counters["train.fleet.h2d_bytes"] == grids + wave + margins
    assert counters["train.fleet.slots_launched"] == sum(waves)
    assert counters["train.fleet.rows_launched"] == sum(waves) * N
    rows = sum(len(y) for _, y in data["clients"])
    assert counters["train.fleet.real_rows"] == 2 * rows   # two rounds
    assert counters["train.fits"] == 2 * B
    assert counters["train.fleet.gather_bytes"] > 0


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
