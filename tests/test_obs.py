"""repro.obs: tracer nesting/ring/export, the disabled no-op fast path,
metrics-registry instruments, reservoir thinning under soak, weighted
fleet percentiles over thinned per-tenant reservoirs, traced serving
latency decomposition, traced engine runs, and kernel-profiling hooks."""
import dataclasses
import json
import random

import numpy as np
import pytest

from repro import obs
from repro.configs.paper_fedboost import FedBoostConfig
from repro.core import FederatedBoostEngine
from repro.data import make_domain_data
from repro.kernels.dispatch import (KernelPolicy, bucket_label,
                                    calibration_check, dispatch)
from repro.launch.obs_report import (aggregate, check_trace, folded_stacks,
                                     phase_breakdown, self_times)
from repro.obs.registry import (Histogram, MetricsRegistry, percentile,
                                weighted_percentile)
from repro.serve import BatchConfig, EnsembleRegistry, EnsembleServer
from repro.serve.metrics import ServeMetrics
from repro.sim.scenarios import DOMAINS


# ------------------------------------------------------------------ tracer

def test_span_nesting_parent_ids_and_two_clocks():
    with obs.tracing() as tr:
        with obs.span("outer", sim_t=10.0, scenario="s") as outer:
            with obs.span("inner") as inner:
                obs.point("leaf", sim_t0=10.5, sim_t1=10.5, k=1)
            outer.end(sim_t=12.0)
        spans = {d["name"]: d for d in tr.finished()}
    assert spans["inner"]["parent"] == spans["outer"]["span"]
    assert spans["leaf"]["parent"] == spans["inner"]["span"]
    assert spans["outer"]["parent"] is None
    assert spans["outer"]["sim_t0"] == 10.0
    assert spans["outer"]["sim_t1"] == 12.0
    assert spans["leaf"]["attrs"] == {"k": 1}
    # wall-clock containment: children close before their parent
    assert spans["outer"]["t0"] <= spans["inner"]["t0"]
    assert spans["inner"]["t1"] <= spans["outer"]["t1"]
    assert check_trace(list(spans.values())) == []


def test_disabled_span_is_shared_noop():
    # the hot-path guarantee: while tracing is off, every span request
    # returns the *same* object — no allocation, attrs dropped silently
    assert not obs.enabled()
    assert obs.span("x", sim_t=1.0, big="attr") is obs.NULL_SPAN
    assert obs.point("y") is obs.NULL_SPAN
    assert obs.span("x").set(a=1).end_sim(2.0) is obs.NULL_SPAN
    with obs.span("ctx") as sp:
        assert sp is obs.NULL_SPAN
    assert not obs.profiling_enabled()


def test_disabled_span_stays_noop_after_a_profiler_mirror():
    with obs.tracing(profile_kernels=False, profiler=True):
        assert obs.span("on") is not obs.NULL_SPAN
    assert obs.span("x", sim_t=1.0) is obs.NULL_SPAN
    assert obs.configure(trace=False, profiler=True) is None
    try:
        assert obs.span("x") is obs.NULL_SPAN
        assert obs.point("y") is obs.NULL_SPAN
    finally:
        obs.disable()


def _host_events(trace_dir):
    """Names of every event of the trace at ``trace_dir``."""
    from pathlib import Path

    from jax.profiler import ProfileData
    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    pd = ProfileData.from_file(str(path))
    return [ev.name for plane in pd.planes for line in plane.lines
            for ev in line.events]


def test_profiler_mirror_puts_spans_in_the_device_trace(tmp_path):
    """With ``profiler=True`` a span shows up by name among the host
    events of the ``jax.profiler`` trace, an abandoned child closing with
    its parent; points (instants) are not mirrored, and a tracer without
    the mirror adds nothing."""
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path / "on"))
    try:
        with obs.tracing(profile_kernels=False, profiler=True) as tr:
            with obs.span("mirror.outer"):
                with obs.span("mirror.inner"):
                    jnp.ones(8).sum().block_until_ready()
                obs.point("mirror.instant")
            with obs.span("mirror.parent"):
                obs.span("mirror.abandoned")   # closes with its parent
            spans = tr.finished()
    finally:
        jax.profiler.stop_trace()
    names = _host_events(tmp_path / "on")
    for name in ("mirror.outer", "mirror.inner", "mirror.parent",
                 "mirror.abandoned"):
        assert names.count(name) == 1, name
    assert "mirror.instant" not in names
    assert {d["name"] for d in spans} == {"mirror.outer", "mirror.inner",
                                          "mirror.instant", "mirror.parent"}

    jax.profiler.start_trace(str(tmp_path / "off"))
    try:
        with obs.tracing(profile_kernels=False):
            with obs.span("plain.span"):
                pass
    finally:
        jax.profiler.stop_trace()
    assert "plain.span" not in _host_events(tmp_path / "off")


def test_tracing_scope_restores_previous_state():
    assert not obs.enabled()
    reg_before = obs.get_registry()
    with obs.tracing() as tr:
        assert obs.enabled() and obs.get_tracer() is tr
        assert obs.profiling_enabled()
        assert obs.get_registry() is not reg_before   # fresh, isolated
        with pytest.raises(ValueError):
            with obs.tracing():                       # nested scope is fine
                raise ValueError("boom")
        assert obs.get_tracer() is tr                 # inner scope restored
    assert not obs.enabled() and not obs.profiling_enabled()
    assert obs.get_registry() is reg_before


def test_span_error_attr_and_abandoned_children():
    with obs.tracing() as tr:
        with pytest.raises(RuntimeError):
            with obs.span("parent"):
                obs.span("orphan")        # never ended by its owner
                raise RuntimeError("die")
        spans = {d["name"]: d for d in tr.finished()}
    assert spans["parent"]["attrs"]["error"] == "RuntimeError"
    assert "orphan" not in spans          # abandoned, not mis-parented
    # the stack recovered: a new root is a root, not a child of the orphan
    with obs.tracing() as tr:
        with obs.span("p"):
            obs.span("dangling")
        with obs.span("q"):
            pass
        spans = {d["name"]: d for d in tr.finished()}
    assert spans["q"]["parent"] is None


def test_ring_bounds_memory_and_counts_drops():
    with obs.tracing(ring=16) as tr:
        for i in range(50):
            obs.point("e", i=i)
        assert len(tr) == 16
        assert tr.dropped == 34
        assert tr.started == 50
        assert [d["attrs"]["i"] for d in tr.finished()] == list(range(34, 50))


def test_jsonl_export_roundtrip(tmp_path):
    with obs.tracing() as tr:
        with obs.span("a", sim_t=0.5, tenant="m"):
            obs.point("b", x=1.5)
        path = tr.export_jsonl(tmp_path / "trace.jsonl")
    back = obs.load_jsonl(path)
    assert back == tr.finished()
    # every line is standalone JSON (streaming consumers): one meta header
    # carrying the ring accounting, then one line per span
    lines = (tmp_path / "trace.jsonl").read_text().strip().splitlines()
    assert len(lines) == 3
    meta = json.loads(lines[0])["meta"]
    assert meta == {"schema": 2, "dropped": 0, "started": 2, "exported": 2}
    assert all(json.loads(ln)["name"] in ("a", "b") for ln in lines[1:])
    meta2, spans = obs.load_trace(path)
    assert meta2 == meta and spans == back


# ---------------------------------------------------------------- registry

def test_registry_instruments_get_or_create_and_snapshot():
    reg = MetricsRegistry()
    reg.counter("c", tenant="a").inc()
    reg.counter("c", tenant="a").inc(2.0)
    reg.counter("c", tenant="b").inc()
    assert reg.counter("c", tenant="a").value == 3.0
    g = reg.gauge("g")
    g.set(5.0)
    g.max(3.0)          # below: no-op
    g.max(9.0)
    assert g.value == 9.0
    h = reg.histogram("h", unit="s")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    assert h.mean == 2.5 and h.p50 == 2.0
    snap = reg.snapshot()
    assert snap["counters"]["c{tenant=a}"] == 3.0
    assert snap["counters"]["c{tenant=b}"] == 1.0
    assert snap["gauges"]["g"] == 9.0
    assert snap["histograms"]["h{unit=s}"]["count"] == 4
    # label order never splits an instrument
    reg.counter("k", a="1", b="2").inc()
    reg.counter("k", b="2", a="1").inc()
    assert reg.counter("k", a="1", b="2").value == 2.0


def test_registry_save_is_json(tmp_path):
    reg = MetricsRegistry()
    reg.counter("train.fits").inc(7)
    path = reg.save(tmp_path / "m.json")
    doc = json.loads((tmp_path / "m.json").read_text())
    assert path.endswith("m.json")
    assert doc["counters"]["train.fits"] == 7.0


def test_reservoir_soak_bounded_memory_and_quantile_tolerance():
    # 100k lognormal samples through a 4096-slot reservoir: memory stays
    # bounded and the thinned quantiles track the full stream
    rng = random.Random(7)
    h = Histogram(reservoir=4096)
    full = []
    for _ in range(100_000):
        v = rng.lognormvariate(0.0, 1.0)
        full.append(v)
        h.observe(v)
    assert len(h.values) == 4096              # hard memory bound
    assert h.count == 100_000
    assert h.weight_per_sample == pytest.approx(100_000 / 4096)
    assert h.mean == pytest.approx(sum(full) / len(full))   # exact (sum/count)
    for q in (50.0, 90.0, 99.0):
        true = percentile(full, q)
        assert h.percentile(q) == pytest.approx(true, rel=0.15), q


def test_tenant_metrics_soak_bounded_memory_and_quantiles():
    # the same guarantee through the TenantMetrics view: 100k completions
    # for one tenant thin into one bounded reservoir whose quantiles
    # track the full latency stream
    rng = random.Random(11)
    m = ServeMetrics()
    full = []
    for _ in range(100_000):
        v = rng.lognormvariate(-6.0, 0.5)        # ~2.5ms lognormal latencies
        full.append(v)
        m.record_completion("t", v, staleness_s=0.0, version=1)
    t = m.tenant("t")
    assert len(t.latencies) == 4096              # bounded under the soak
    assert t.completed == 100_000
    for q, got in ((50.0, t.p50), (99.0, t.p99)):
        assert got == pytest.approx(percentile(full, q), rel=0.15), q
    assert m.fleet_percentile(50.0) == pytest.approx(
        percentile(full, 50.0), rel=0.15)


def test_histogram_extend_merges_totals_and_stays_bounded():
    a, b = Histogram(reservoir=64), Histogram(reservoir=64)
    for i in range(100):
        a.observe(1.0)
        b.observe(3.0)
    a.extend(b)
    assert a.count == 200 and a.sum == pytest.approx(400.0)
    assert len(a.values) == 64


def test_weighted_percentile_table_driven():
    cases = [
        # (pairs, q, want)
        ([(1.0, 1.0), (2.0, 1.0), (3.0, 1.0)], 50.0, 2.0),   # unit = plain
        ([(1.0, 1.0), (2.0, 1.0), (3.0, 1.0)], 100.0, 3.0),
        ([(10.0, 9.0), (99.0, 1.0)], 50.0, 10.0),
        ([(10.0, 9.0), (99.0, 1.0)], 90.0, 10.0),
        ([(10.0, 9.0), (99.0, 1.0)], 95.0, 99.0),
        ([(5.0, 0.0), (7.0, 2.0)], 50.0, 7.0),   # zero weights dropped
        ([], 99.0, 0.0),
    ]
    for pairs, q, want in cases:
        assert weighted_percentile(pairs, q) == want, (pairs, q)
    # agrees exactly with percentile() under unit weights
    rng = random.Random(3)
    vals = [rng.random() for _ in range(257)]
    for q in (1.0, 50.0, 99.0):
        assert (weighted_percentile([(v, 1.0) for v in vals], q)
                == percentile(vals, q))


def test_fleet_percentile_weights_thinned_tenant_reservoirs():
    # the bias this fixes: a hot tenant's reservoir is thinned (4096 kept
    # of 99k) while a cold tenant's 1k all fit, so naively concatenating
    # reservoirs gives the cold tenant ~20% of the merged sample instead
    # of its true 1% of traffic — and its slow requests swamp the p99
    m = ServeMetrics()
    for _ in range(99_000):
        m.record_completion("hot", 0.001, staleness_s=0.0, version=1)
    for _ in range(1_000):
        m.record_completion("cold", 0.100, staleness_s=0.0, version=1)
    naive = percentile(m.all_latencies(), 99.0)
    assert naive == pytest.approx(0.100)           # the documented bias
    assert m.fleet_percentile(99.0) == pytest.approx(0.001)   # weighted: fixed
    # true stream p99: 99k fast + 1k slow -> the 99th sits in the fast mass
    assert m.report()["p99_ms"] == pytest.approx(1.0)
    # per-tenant quantiles are unaffected either way
    assert m.tenant("cold").p99 == pytest.approx(0.100)


def test_weight_per_sample_tracks_stream_not_reservoir():
    h = Histogram(reservoir=64)
    for _ in range(64):
        h.observe(1.0)
    assert h.weight_per_sample == 1.0             # nothing thinned yet
    for _ in range(640 - 64):
        h.observe(1.0)
    assert h.weight_per_sample == pytest.approx(10.0)
    assert Histogram().weight_per_sample == 0.0   # empty: no weight


def test_latency_pairs_survive_merge_of_merges():
    """Folding already-folded per-host registries must not double-weight
    thinned reservoirs.  ``Histogram.extend`` keeps only every 8th incoming
    sample once full, so a second-level fold re-thins the first fold's
    survivors; ``latency_pairs`` taken *before* each merge carries the
    exact weights, and fleet quantiles from the concatenated pairs match
    the true stream regardless of fold depth."""
    rng = random.Random(7)
    hosts = []
    stream: list = []
    for h in range(4):
        m = ServeMetrics()
        # hosts see very different traffic volumes and latency regimes
        n = 6_000 * (h + 1)
        base = 0.001 * (h + 1)
        for _ in range(n):
            v = base * (1.0 + 0.1 * rng.random())
            m.record_completion("t", v, staleness_s=0.0, version=1)
            stream.append(v)
        hosts.append(m)
    true_p99 = percentile(stream, 99.0)
    # exact-weight pairs concatenated across hosts, pre-merge
    pairs = [p for m in hosts for p in m.latency_pairs()]
    flat = weighted_percentile(pairs, 99.0)
    assert abs(flat - true_p99) / true_p99 < 0.05
    # a two-level fold: (h0+h1) and (h2+h3), then the fold-of-folds.
    # the merged histogram's single weight_per_sample can no longer
    # distinguish the hosts, and re-thinning dropped samples unevenly
    lvl1a, lvl1b = ServeMetrics(), ServeMetrics()
    lvl1a.tenant("t").merge_from(hosts[0].tenant("t"))
    lvl1a.tenant("t").merge_from(hosts[1].tenant("t"))
    lvl1b.tenant("t").merge_from(hosts[2].tenant("t"))
    lvl1b.tenant("t").merge_from(hosts[3].tenant("t"))
    top = ServeMetrics()
    top.tenant("t").merge_from(lvl1a.tenant("t"))
    top.tenant("t").merge_from(lvl1b.tenant("t"))
    # totals stay exact through any fold depth
    assert top.completed == len(stream)
    # and the pre-merge pairs remain the trustworthy quantile source:
    # they must beat (or match) the merged reservoir's estimate
    merged_err = abs(top.fleet_percentile(99.0) - true_p99)
    assert abs(flat - true_p99) <= merged_err + 1e-12


def test_sharded_report_percentiles_come_from_premerge_pairs():
    """ShardedEnsembleServer.report folds per-host metrics; its fleet
    p50/p99 must come from the pre-merge per-host pairs, not from the
    merged (re-thinned) reservoir."""
    from repro.serve import (BatchConfig, GossipConfig, ShardCluster,
                             ShardedEnsembleServer)
    from repro.serve.metrics import weighted_percentile as wp
    cluster = ShardCluster(3, GossipConfig(seed=0))
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    for i in range(4):
        p = np.zeros((4, 4), np.float32)
        p[:, 0] = rng.randint(0, 8, size=4)
        p[:, 1] = rng.randn(4)
        p[:, 2] = 1.0
        cluster.publish_packed(f"tenant-{i}", jnp.asarray(p),
                               jnp.asarray(rng.rand(4) + 0.1))
    cluster.run_until_quiescent()
    server = ShardedEnsembleServer(cluster, BatchConfig(max_batch=8),
                                   service_model=lambda n: 1e-3 + 1e-4 * n)
    t = 0.0
    for i in range(60):
        t += rng.exponential(1.0 / 200.0)
        server.submit(f"tenant-{i % 4}", rng.randn(8).astype(np.float32), t)
    server.drain()
    rep = server.report()
    pairs = server.metrics.latency_pairs()
    for s in server.servers.values():
        pairs.extend(s.metrics.latency_pairs())
    assert rep["p50_ms"] == 1e3 * wp(pairs, 50.0)
    assert rep["p99_ms"] == 1e3 * wp(pairs, 99.0)
    assert rep["completed"] == 60


def test_tenant_metrics_view_and_merge():
    m = ServeMetrics()
    m.record_submit(0.0, depth=3)
    m.record_completion("a", 0.01, staleness_s=2.0, version=3)
    m.record_completion("a", 0.03, staleness_s=4.0, version=2)  # stale pub
    m.record_rejected("a")
    t = m.tenant("a")
    assert t.completed == 2 and t.rejected == 1
    assert t.last_version == 3                  # max, not last-write
    assert t.mean_staleness == pytest.approx(3.0)
    assert t.latencies == [0.01, 0.03]
    other = ServeMetrics()
    other.record_completion("a", 0.05, staleness_s=0.0, version=5)
    t.merge_from(other.tenant("a"))
    assert t.completed == 3 and t.last_version == 5
    assert sorted(t.latencies) == [0.01, 0.03, 0.05]


# ----------------------------------------------------- serving decomposition

def _stump_registry(T=4, F=6, seed=0):
    rng = np.random.RandomState(seed)
    params = np.zeros((T, 4), np.float32)
    params[:, 0] = rng.randint(0, F, size=T)
    params[:, 1] = rng.randn(T)
    params[:, 2] = np.where(rng.rand(T) > 0.5, 1.0, -1.0)
    reg = EnsembleRegistry()
    import jax.numpy as jnp
    reg.publish_packed("t", jnp.asarray(params),
                       jnp.asarray(rng.rand(T).astype(np.float32) + 0.1),
                       clock=0.0)
    return reg


def test_traced_serve_request_decomposition_sums_to_latency():
    reg = _stump_registry()
    cfg = BatchConfig(adaptive=False, fixed_window_units=2,
                      base_window_s=1e-3, max_batch=4)
    with obs.tracing() as tr:
        server = EnsembleServer(reg, cfg, service_model=lambda n: 1e-3)
        rng = np.random.RandomState(1)
        out = []
        t = 0.0
        for i in range(40):
            t += float(rng.exponential(1e-3))
            out += server.submit("t", rng.randn(6), now=t)[1]
        out += server.drain()
        spans = tr.finished()
    assert len(out) == 40
    reqs = [d for d in spans if d["name"] == "serve.request"]
    batches = [d for d in spans if d["name"] == "serve.batch"]
    kernels = [d for d in spans if d["name"] == "serve.kernel"]
    assert len(reqs) == 40 and batches and kernels
    for r in reqs:
        a = r["attrs"]
        # the exact decomposition: batching wait + queueing behind the
        # in-flight batch + the batch's own service time == latency
        assert a["batch_s"] >= 0 and a["queue_s"] >= 0 and a["kernel_s"] > 0
        assert (a["batch_s"] + a["queue_s"] + a["kernel_s"]
                == pytest.approx(a["latency_s"], abs=1e-9))
        # and the span's simulated interval is that same latency
        assert (r["sim_t1"] - r["sim_t0"]
                == pytest.approx(a["latency_s"], abs=1e-9))
    # request points nest under their dispatching batch span
    batch_ids = {d["span"] for d in batches}
    assert all(r["parent"] in batch_ids for r in reqs)
    assert check_trace(spans) == []


def test_traced_serve_metrics_and_registry_counters():
    reg = _stump_registry()
    with obs.tracing():
        server = EnsembleServer(reg, BatchConfig(max_batch=8),
                                service_model=lambda n: 1e-4)
        rng = np.random.RandomState(2)
        for i in range(10):
            server.submit("t", rng.randn(6), now=1e-3 * i)
        server.drain()
        greg = obs.get_registry()
        # the engine-side counters live on the *global* registry; the
        # server's ServeMetrics counters live on its private one
        assert server.metrics.registry is not greg
        assert server.metrics.completed == 10
    assert server.metrics.n_batches > 0


# ------------------------------------------------------------ traced engine

def _tiny_engine(mode="enhanced"):
    dom = dataclasses.replace(DOMAINS["edge_vision"], n_samples=400,
                              n_clients=4)
    data = make_domain_data(dom, seed=0)
    cfg = FedBoostConfig(n_clients=4, n_rounds=4, seed=0)
    return FederatedBoostEngine(cfg, data, mode)


@pytest.mark.parametrize("mode", ["baseline", "enhanced"])
def test_traced_engine_run_emits_train_spans(mode):
    with obs.tracing(profile_kernels=False) as tr:
        m = _tiny_engine(mode).run()
        spans = tr.finished()
        reg = obs.get_registry()
        fits = reg.counter("train.fits").value
    names = {d["name"] for d in spans}
    assert "train.fit" in names
    assert ("train.round" if mode == "baseline" else "train.sync") in names
    assert fits > 0
    assert m.final_val_error <= 0.5
    # fit spans carry the virtual clock and client id
    fit = next(d for d in spans if d["name"] == "train.fit")
    assert fit["sim_t0"] is not None and "cid" in fit["attrs"]
    sync = next(d for d in spans
                if d["name"] in ("train.round", "train.sync"))
    assert sync["sim_t1"] is not None and sync["sim_t1"] >= sync["sim_t0"]
    assert check_trace(spans) == []


def test_untraced_engine_run_leaves_no_spans():
    obs.disable()
    before = obs.get_registry().counter("train.fits").value
    _tiny_engine().run()
    # counters still accumulate (always cheap); no tracer was installed
    assert obs.get_registry().counter("train.fits").value > before
    assert obs.get_tracer() is None


# --------------------------------------------------------- kernel profiling

def test_dispatch_profiling_records_launches_and_wall_time():
    rng = np.random.RandomState(0)
    args = (rng.randn(1, 4, 8).astype(np.float32),    # xsel (B, T, N)
            rng.randn(1, 4).astype(np.float32),
            np.sign(rng.randn(1, 4)).astype(np.float32),
            rng.rand(1, 4).astype(np.float32))
    with obs.tracing() as tr:
        out = dispatch("stump_vote_batched", args, backend="xla")
        out2 = dispatch("stump_vote_batched", args, backend="xla")
        reg = obs.get_registry()
        walls = [(labels, h) for name, labels, h in reg.histograms()
                 if name == "kernel.wall_s"]
        compiles = [(labels, h) for name, labels, h in reg.histograms()
                    if name == "kernel.compile_s"]
        counters = [(labels, c) for name, labels, c in reg.counters()
                    if name == "kernel.launches"]
        spans = tr.finished()
    assert out.shape == out2.shape == (1, 8)
    # first-seen (kernel, bucket, backend) launch pays jit trace/compile
    # and lands in kernel.compile_s; the repeat is steady state -> wall_s
    assert len(walls) == 1 and len(compiles) == 1 and len(counters) == 1
    labels, h = walls[0]
    assert labels["kernel"] == "stump_vote_batched"
    assert labels["backend"] == "xla"
    assert h.count == 1 and h.sum > 0
    clabels, ch = compiles[0]
    assert clabels == labels
    assert ch.count == 1 and ch.sum > 0
    assert counters[0][1].value == 2    # launches counts both
    ksp = next(d for d in spans if d["name"].startswith("kernel."))
    assert ksp["name"] == "kernel.stump_vote_batched"
    assert ksp["attrs"]["bucket"] == labels["bucket"]


def test_first_seen_split_is_per_kernel_bucket_backend():
    """The compile_s split keys on (kernel, bucket, backend): a counting
    backend stub shows exactly one compile observation per distinct
    bucket, with repeats all landing in wall_s."""
    from repro.kernels.dispatch import BACKENDS

    class CountingBackend:
        name = "counting"
        calls = 0

        def available(self):
            return True

        def run(self, kernel, *args, **kwargs):
            CountingBackend.calls += 1
            return np.zeros((args[0].shape[0], args[0].shape[2]),
                            np.float32)

    rng = np.random.RandomState(1)

    def mk(B, T, N):
        return (rng.randn(B, T, N).astype(np.float32),
                rng.randn(B, T).astype(np.float32),
                np.sign(rng.randn(B, T)).astype(np.float32),
                rng.rand(B, T).astype(np.float32))

    BACKENDS["counting"] = CountingBackend()
    try:
        with obs.tracing():
            small, big = mk(1, 4, 8), mk(1, 4, 600)
            for _ in range(3):
                dispatch("stump_vote_batched", small, backend="counting")
            dispatch("stump_vote_batched", big, backend="counting")
            reg = obs.get_registry()
            compiles = [(labels, h) for name, labels, h
                        in reg.histograms() if name == "kernel.compile_s"]
            walls = [(labels, h) for name, labels, h in reg.histograms()
                     if name == "kernel.wall_s"]
    finally:
        BACKENDS.pop("counting")
    assert CountingBackend.calls == 4
    # two buckets -> two first-seen compile observations, one each
    assert len(compiles) == 2
    assert all(h.count == 1 for _, h in compiles)
    # only the small bucket repeated -> one wall_s series with 2 obs
    assert len(walls) == 1 and walls[0][1].count == 2


def test_dispatch_unprofiled_records_nothing():
    obs.disable()
    reg = MetricsRegistry()
    old = obs.set_registry(reg)
    try:
        rng = np.random.RandomState(0)
        args = (rng.randn(1, 4, 8).astype(np.float32),
                rng.randn(1, 4).astype(np.float32),
                np.sign(rng.randn(1, 4)).astype(np.float32),
                rng.rand(1, 4).astype(np.float32))
        dispatch("stump_vote_batched", args, backend="xla")
        assert len(reg) == 0
    finally:
        obs.set_registry(old)


def test_calibration_check_flags_stale_winner():
    reg = MetricsRegistry()
    bucket = (128, 8, 8)
    bl = bucket_label(bucket)
    for _ in range(20):
        reg.histogram("kernel.wall_s", kernel="k", bucket=bl,
                      backend="mosaic").observe(5e-3)    # calibrated winner
        reg.histogram("kernel.wall_s", kernel="k", bucket=bl,
                      backend="xla").observe(1e-3)       # actually faster
    pol = KernelPolicy(table={("k", bucket): "mosaic"}, env_var=None)
    flags = calibration_check(policy=pol, registry=reg)
    assert len(flags) == 1
    assert flags[0]["calibrated"] == "mosaic"
    assert flags[0]["observed_best"] == "xla"
    assert flags[0]["observed_best_p50_s"] < flags[0]["calibrated_p50_s"]
    # the flag carries per-backend observation counts for triage
    assert flags[0]["counts"] == {"mosaic": 20, "xla": 20}
    # agreeing observations -> no flag
    pol_ok = KernelPolicy(table={("k", bucket): "xla"}, env_var=None)
    assert calibration_check(policy=pol_ok, registry=reg) == []
    # single-backend observations are skipped, not flagged
    reg2 = MetricsRegistry()
    reg2.histogram("kernel.wall_s", kernel="k", bucket=bl,
                   backend="mosaic").observe(5e-3)
    assert calibration_check(policy=pol, registry=reg2) == []


@pytest.mark.parametrize("n_obs,min_count,expect_flag", [
    (4, 5, False),     # below the default floor -> too noisy, skipped
    (5, 5, True),      # at the floor -> counted
    (2, 2, True),      # caller-lowered floor
    (19, 20, False),   # caller-raised floor
])
def test_calibration_check_min_count_floor(n_obs, min_count, expect_flag):
    """Histograms with fewer than min_count observations per backend are
    p50-unstable and must not generate drift flags."""
    reg = MetricsRegistry()
    bucket = (128, 8, 8)
    bl = bucket_label(bucket)
    for _ in range(n_obs):
        reg.histogram("kernel.wall_s", kernel="k", bucket=bl,
                      backend="mosaic").observe(5e-3)
        reg.histogram("kernel.wall_s", kernel="k", bucket=bl,
                      backend="xla").observe(1e-3)
    pol = KernelPolicy(table={("k", bucket): "mosaic"}, env_var=None)
    flags = calibration_check(policy=pol, registry=reg,
                              min_count=min_count)
    assert (len(flags) == 1) is expect_flag
    if expect_flag:
        assert flags[0]["counts"] == {"mosaic": n_obs, "xla": n_obs}


# ----------------------------------------------------------------- reporter

def _mk(name, span, parent, t0, t1, **attrs):
    return {"name": name, "span": span, "parent": parent, "t0": t0,
            "t1": t1, "sim_t0": None, "sim_t1": None, "attrs": attrs}


def test_report_self_times_and_folded_stacks():
    spans = [
        _mk("train.round", 1, None, 0.0, 10.0),
        _mk("train.fit", 2, 1, 1.0, 4.0),
        _mk("train.fit", 3, 1, 5.0, 9.0),
        _mk("serve.batch", 4, None, 20.0, 21.0),
    ]
    self_s = self_times(spans)
    assert self_s[1] == pytest.approx(3.0)      # 10 - (3 + 4)
    assert self_s[2] == pytest.approx(3.0)
    agg = {a["name"]: a for a in aggregate(spans)}
    assert agg["train.fit"]["count"] == 2
    assert agg["train.fit"]["total_s"] == pytest.approx(7.0)
    phases = {ns: (sec, n) for ns, sec, n in phase_breakdown(spans)}
    assert phases["train"][0] == pytest.approx(10.0)
    assert phases["serve"][0] == pytest.approx(1.0)
    folded = dict(folded_stacks(spans))
    assert folded["train.round;train.fit"] == pytest.approx(7e6)
    assert folded["train.round"] == pytest.approx(3e6)
    assert folded["serve.batch"] == pytest.approx(1e6)


def test_check_trace_catches_violations():
    ok = [_mk("a", 1, None, 0.0, 2.0), _mk("b", 2, 1, 0.5, 1.5)]
    assert check_trace(ok) == []
    assert check_trace([_mk("a", 1, None, 2.0, 1.0)])          # t1 < t0
    assert check_trace([_mk("a", 1, None, 0.0, None)])         # unended
    assert check_trace([_mk("a", 1, None, 0.0, 1.0),
                        _mk("a", 1, None, 0.0, 1.0)])          # dup id
    assert check_trace([_mk("a", 1, None, 0.0, 1.0),
                        _mk("b", 2, 1, 0.5, 5.0)])             # escapes parent
    bad_req = _mk("serve.request", 2, 1, 0.5, 0.6,
                  batch_s=0.1, queue_s=0.1, kernel_s=0.1, latency_s=0.5)
    assert check_trace([_mk("serve.batch", 1, None, 0.0, 1.0), bad_req])
