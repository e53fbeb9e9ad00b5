"""A rehearsal of every cell on the CPU at a tiny size: the harness drives
each driver through set-up, a short window and the comparison with the
reference, with the chip check steered from here (CPU devices are handed
in), and the result line and the per-layer readers are checked."""
import json
import os
import subprocess
import sys
import time
import types

import jax
import numpy as np
import pytest

from bench import harness, trace as tr
from bench.drivers import fleet

ROOT = harness.ROOT
CPU_OPS = tr.DeviceLines(plane=r"^/host:CPU$", line=r"^tf_XLA",
                         skip=r"^ThreadpoolListener|^end: ")
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def tiny_fleet() -> dict:
    """FEMNIST's shape at a CPU's size: 64 devices of about 12 rows, 8
    features; ``samples`` is what the fixed counts sum to."""
    c = {**harness.load_config("femnist_fleet"), "devices": 64,
         "features": 8, "samples_per_device_mean": 12.0,
         "samples_per_device_stdev": 4.7, "check_clients_per_job": 16}
    return {**c, "samples": int(fleet.device_counts(c).sum())}


TINY = {"femnist_fleet.train": {"config": tiny_fleet()}}
LAYERS = {"femnist_fleet.train": {"idle_share.train", "wave_fill.fleet",
                                  "row_fill.fleet"}}
E2E = {"femnist_fleet.train": {"train_round_s", "setup_s"}}


@pytest.fixture
def no_cache(monkeypatch):
    """No persistent compilation cache in a test process."""
    monkeypatch.setattr(harness, "use_compile_cache", lambda jax: "off")


def rehearse(cell: str, trace: bool, overrides=None, log=lambda s: None):
    result, checks = harness.run_cell(
        cell, 2**32 + 17, 1.0, trace, devices=jax.devices("cpu")[:1],
        overrides=overrides or TINY[cell], device_lines=CPU_OPS, log=log)
    line = json.loads(json.dumps(result))
    return line, checks


def check_line(line: dict, trace: bool):
    want = KEYS + (["breakdown"] if trace else []) + ["checks"]
    assert list(line) == want
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 1
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"}
    for name, c in line["checks"].items():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]


@pytest.mark.parametrize("cell", sorted(TINY))
def test_cell_rehearsal(cell, no_cache):
    logged = []
    line, checks = rehearse(cell, False, log=logged.append)
    check_line(line, False)
    assert set(line["metrics"]) == E2E[cell]
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert checks and all(c.ok for c in checks)
    # set-up's direct calls compiled every program the window runs
    assert any(s.startswith("window: 0 programs") for s in logged), logged


@pytest.mark.parametrize("cell", sorted(TINY))
def test_traced_cell_rehearsal(cell, no_cache):
    line, _ = rehearse(cell, True)
    check_line(line, True)
    # the readers found their spans and counters; the roofline shares
    # need the chip's peaks and stay silent here
    assert set(line["metrics"]) == LAYERS[cell]
    assert 0.0 < line["device"]["busy_s"] < line["device"]["window_s"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0.0 < line["metrics"]["row_fill.fleet"]["value"] < 100.0


def test_device_counts_follow_the_source():
    """The full-size counts have LEAF FEMNIST's mean and spread, sum to
    the configuration's ``samples``, and are the same in every run."""
    c = harness.load_config("femnist_fleet")
    counts = fleet.device_counts(c)
    assert len(counts) == c["devices"] and counts.min() >= 1
    assert counts.sum() == c["samples"]
    assert counts.mean() == pytest.approx(c["samples_per_device_mean"],
                                          rel=0.01)
    assert counts.std() == pytest.approx(c["samples_per_device_stdev"],
                                         rel=0.02)
    assert np.array_equal(counts, fleet.device_counts(c))


def test_every_seed_gets_the_same_work():
    """Seeds deal the same counts to the devices in different orders."""
    c = tiny_fleet()
    counts = fleet.device_counts(c)
    x = np.arange(c["samples"] * 2, dtype=np.float32).reshape(-1, 2)
    y = np.ones(c["samples"], np.float32)
    runs = [fleet.deal(x, y, counts, np.random.default_rng(s))
            for s in (1, 2**31 + 5, 2**40)]
    sizes = [np.array([len(b) for _, b in r]) for r in runs]
    for s, r in zip(sizes, runs):
        assert np.array_equal(np.sort(s), np.sort(counts))
        assert np.array_equal(np.concatenate([a for a, _ in r]), x)
    assert not np.array_equal(sizes[0], sizes[1])


def test_window_starts_no_job_it_cannot_finish():
    """Jobs run back to back while the time left holds one more as long
    as the last; the first always runs."""
    cell = fleet.FleetCell.__new__(fleet.FleetCell)
    cell.rounds, cell.fit_shapes, cell.wave_rows = 2, [], []
    cell.setup_note = ""
    cell.job = lambda: (time.sleep(0.3), types.SimpleNamespace(n_syncs=1))[1]
    t0 = time.perf_counter()
    win = cell.window(1.0)
    assert win.attempted == 3 and time.perf_counter() - t0 < 1.0
    assert win.metrics["train_round_s"] == pytest.approx(0.15, rel=0.3)
    assert cell.window(0.1).attempted == 1


@pytest.mark.parametrize("cell", sorted(TINY))
def test_no_result_without_the_chip(cell):
    """Off the chip the command exits non-zero and prints no result."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", cell,
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert out.returncode == 2
    assert "no tpu found" in out.stderr
    assert out.stdout.strip() == ""


def test_every_cell_and_metric_has_its_files():
    spec = harness.load_spec()
    for c in spec["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert harness.load_config(c["name"])["reduced"] == c["reduced"]
    for w in spec["workloads"]:
        wl = harness.load_workload(w["name"])
        assert wl["config"] == w["config"] and wl["chips"] == w["chips"]
        assert (ROOT / "bench" / "drivers" / f"{wl['driver']}.py").is_file()
    e2e = {m["name"] for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        reader = harness.load_reader(m["name"])
        assert reader.MOVES == m["moves"] and m["moves"] in e2e
