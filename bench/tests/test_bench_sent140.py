"""A rehearsal of the Sent140 cell on the CPU at a tiny size (traced and
not): the harness drives the ragged-fleet driver through set-up, a short
window and the comparison with the reference, with CPU devices handed in;
and the full-size per-device counts against the source."""
import json

import jax
import numpy as np
import pytest

from bench import harness, trace as tr
from bench.drivers import ragged_fleet

CELL = "sent140_fleet.train"
CPU_OPS = tr.DeviceLines(plane=r"^/host:CPU$", line=r"^tf_XLA",
                         skip=r"^ThreadpoolListener|^end: ")


def tiny_sent140() -> dict:
    """Sent140's shape at a CPU's size: 96 devices of LEAF's count
    distribution (mostly one row, the largest 27), 8 features, 5% of the
    rows held out each so that the server has rows to score; the clients
    followed are 16 drawn and the 4 largest."""
    c = {**harness.load_config("sent140_fleet"), "devices": 96,
         "features": 8, "val_frac": 0.05, "test_frac": 0.05,
         "check_clients_per_job": 16, "check_largest": 4}
    return {**c, "samples": int(ragged_fleet.device_counts(c).sum())}


TINY = {"config": tiny_sent140()}


@pytest.fixture
def no_cache(monkeypatch):
    """No persistent compilation cache in a test process."""
    monkeypatch.setattr(harness, "use_compile_cache", lambda jax: "off")


def rehearse(trace: bool, log=lambda s: None):
    result, checks = harness.run_cell(
        CELL, 2**32 + 29, 1.0, trace, devices=jax.devices("cpu")[:1],
        overrides=TINY, device_lines=CPU_OPS, log=log)
    return json.loads(json.dumps(result)), checks


@pytest.mark.parametrize("trace", [False, True], ids=["timed", "traced"])
def test_sent140_rehearsal(trace, no_cache, capsys):
    logged = []
    line, checks = rehearse(trace, logged.append)
    want = ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line) == want + (["breakdown"] if trace else []) + ["checks"]
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["checks"]) == {"fit_err_gap", "eps_gap", "dist_l1"}
    assert checks and all(c.ok for c in checks)
    # the comparison's note counts the fits the predictor scores worse
    assert "fleet check:" in capsys.readouterr().out
    if trace:
        # the roofline share needs the chip's peaks and stays silent
        assert set(line["metrics"]) == {"pack_fill.sent140"}
        fill = line["metrics"]["pack_fill.sent140"]["value"]
        assert 0.0 < fill <= 100.0
        assert 0.0 < line["device"]["busy_s"] < line["device"]["window_s"]
    else:
        assert set(line["metrics"]) == {"train_round_s", "setup_s"}
        assert all(m["value"] > 0 for m in line["metrics"].values())
        assert any(s.startswith("window: 0 programs") for s in logged), logged


def test_the_fleet_packs_and_follows_its_largest_devices(no_cache):
    """The tiny fleet takes the packed layout (the fits counted are the
    ragged scan's), and every job follows a drawn group and the largest
    devices, each padded to its own largest count."""
    cell = ragged_fleet.build(TINY["config"], harness.load_workload(CELL),
                              7, jax.devices("cpu")[:1], 1.0)
    try:
        win = cell.window(0.1)
        info = cell.layer_info()
    finally:
        cell.free()
    assert 2 * win.attempted == len(cell.jobs) >= 2
    assert info["ragged_fits"] and len(info["ragged_fits"]) == len(
        info["wave_rows"])
    assert info["counters"]["real_rows"] == sum(r for _, r in
                                                info["wave_rows"])
    for drawn, largest in zip(cell.jobs[::2], cell.jobs[1::2]):
        assert len(drawn["cid"]) == 16
        assert np.array_equal(largest["cid"], cell.largest)
        assert largest["rows"] == cell.n_rows.max()
        assert drawn["rows"] == cell.n_rows[drawn["cid"]].max()
        # every job's waves reach every followed client
        assert all(len(w["who"]) == 16 for w in drawn["waves"])


def test_device_counts_follow_the_source():
    """The full-size counts sum to the configuration's 39,645 rows, have
    LEAF Sent140's mean 2.42 and stdev 4.71 within 1%, 76-77% of the
    devices hold one row, and every run gets the same counts."""
    c = harness.load_config("sent140_fleet")
    counts = ragged_fleet.device_counts(c)
    assert len(counts) == c["devices"] == 16384
    assert counts.sum() == c["samples"] == 39645
    assert counts.mean() == pytest.approx(c["samples_per_device_mean"],
                                          rel=0.01)
    assert counts.std() == pytest.approx(c["samples_per_device_stdev"],
                                         rel=0.01)
    assert 0.76 <= np.mean(counts == 1) <= 0.77
    assert counts.max() == 97
    assert np.array_equal(counts, ragged_fleet.device_counts(c))
