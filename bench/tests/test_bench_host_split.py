"""The exact split of device-idle time by host span, on hand-made gaps and
spans and on traces recorded here on the CPU, and a rehearsal of the
fleet job measurement at a tiny size."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness, host_split as hs, trace as tr
from bench.drivers import fleet

CPU_OPS = tr.DeviceLines(plane=r"^/host:CPU$", line=r"^tf_XLA",
                         skip=r"^ThreadpoolListener|^end: ")


def test_one_gap_across_two_adjacent_spans_is_split_between_them():
    spans = [(0.0, 10.0, tr.WINDOW), (1.0, 4.0, "train.a"),
             (4.0, 8.0, "train.b")]
    assert hs.split_idle([(3.0, 6.0)], spans) == pytest.approx(
        {"train.a": 1.0, "train.b": 2.0})


def test_a_nested_span_takes_its_part_from_its_parent():
    spans = [(0.0, 10.0, tr.WINDOW), (1.0, 9.0, "bench.job.run"),
             (2.0, 5.0, "train.fleet.sync"),
             (3.0, 4.0, "train.fleet.gather")]
    got = hs.split_idle([(0.5, 9.5)], spans)
    assert got == pytest.approx({tr.IDLE_HOST: 1.0, "bench.job.run": 5.0,
                                 "train.fleet.sync": 2.0,
                                 "train.fleet.gather": 1.0})
    assert sum(got.values()) == pytest.approx(9.0)


@pytest.mark.parametrize("gaps,want", [
    ([], {}),
    ([(1.0, 2.0)], {"train.a": 1.0}),
    # two devices idle at once count twice
    ([(1.0, 2.0), (1.5, 2.5)], {"train.a": 2.0}),
    # a span that starts with its parent and ends first is the inner one
    ([(0.0, 1.0)], {"train.a": 1.0}),
])
def test_split_counts_every_idle_device(gaps, want):
    spans = [(0.0, 3.0, "train.run"), (0.0, 2.5, "train.a")]
    assert hs.split_idle(gaps, spans) == pytest.approx(want)


def test_h2d_needed_counts_rows_once_and_weights_once_a_wave():
    # a FEMNIST job: 464,410 real rows of 784 features, two full waves
    rows = 464410
    assert hs.h2d_needed(rows, 784, [rows, rows]) == 4 * (
        rows * 785 + 2 * rows)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A traced window in which the program's span holds a host-only sleep
    inside the benchmark's job span, between two bursts of device work."""
    d = tmp_path_factory.mktemp("trace")
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((384, 384))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(d))
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        with jax.profiler.TraceAnnotation("bench.job.run"):
            for _ in range(3):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("train.fleet.sync"):
                time.sleep(0.05)
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    return tr.find_xplane(d)


def test_a_program_span_inside_a_bench_span_names_the_gap(recorded):
    s = hs.summarize(recorded, 1, lines=CPU_OPS)
    assert max(s.gap_s, key=s.gap_s.get) == "train.fleet.sync"
    assert s.gap_s["train.fleet.sync"] >= 0.045
    assert s.idle_span_s["train.fleet.sync"] >= 0.045
    # the split accounts for every idle second of the window
    idle = s.window_s - s.busy_s
    assert sum(s.idle_span_s.values()) == pytest.approx(idle, rel=1e-6)
    assert sum(s.gap_s.values()) == pytest.approx(idle, rel=1e-6)
    assert 0 < s.share(["train.fleet.sync"]) < 100.0
    # the benchmark's own reduction sees the same window; it sums a
    # device's lines where the split takes their union (on a TPU a device
    # has one line of ops, on the CPU one per XLA thread)
    base = tr.summarize(recorded, 1, lines=CPU_OPS)
    assert base.window_s == pytest.approx(s.window_s)
    assert 0.0 < s.busy_s <= base.busy_s * (1 + 1e-9)


def test_fleet_job_split_rehearsal():
    """Four jobs of a tiny fleet cell on the CPU: the program's spans are
    mirrored into the trace, hold the idle time, and the transfer counter
    gives the share of the bytes the work needs."""
    c = {**harness.load_config("femnist_fleet"), "devices": 64,
         "features": 8, "samples_per_device_mean": 12.0,
         "samples_per_device_stdev": 4.7, "check_clients_per_job": 16}
    c["samples"] = int(fleet.device_counts(c).sum())
    wl = harness.load_workload("femnist_fleet.train")
    cell = fleet.build(c, wl, 2**32 + 5, jax.devices("cpu")[:1], 0.0)
    try:
        out = hs.measure(cell, 1, lines=CPU_OPS)
    finally:
        cell.free()
    assert len(out["untraced_s"]) == 2
    plain, traced = out["profiler"], out["profiler_obs"]
    assert not any(n.startswith("train.") for n in plain["idle_span_s"])
    assert {"train.fleet.stack", "train.fleet.sync"} <= set(
        traced["idle_span_s"])
    for rec in (plain, traced):
        idle = rec["window_s"] - rec["busy_s"]
        assert sum(rec["idle_span_s"].values()) == pytest.approx(idle)
    assert (traced["staging_idle"] + traced["host_math_idle"]
            <= traced["program_idle"] <= traced["idle_share"] + 1e-9)
    assert traced["ring"]["dropped"] == 0
    assert traced["spans"]["train.fleet.run"] == 1
    assert traced["spans"]["train.fit_batch"] == 2
    # 64 clients' rows of 8 features once, their weights once a wave,
    # against the whole padded stack sent three times
    assert traced["h2d_needed_bytes"] == 4 * c["samples"] * (8 + 1 + 2)
    assert 0.0 < traced["h2d_fill"] < 100.0
