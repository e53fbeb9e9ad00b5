"""The comparison that decides ``correct`` has to fail when the timed path
is broken.  Each test drives a whole run of a cell on the CPU at a tiny
size (the chip check steered from here) with one fault planted under the
timed path, and sees ``correct`` come out false; and the control, the
reference in bfloat16 put in the program's place, must fail the same
comparison."""
import importlib

import jax
import numpy as np
import pytest

from bench import harness
from bench.tests.test_bench_cells import TINY

ROOT = harness.ROOT


@pytest.fixture
def no_cache(monkeypatch):
    monkeypatch.setattr(harness, "use_compile_cache", lambda jax: "off")


def run(cell: str) -> dict:
    result, _ = harness.run_cell(cell, 2**31 + 99, 1.0, False,
                                 devices=jax.devices("cpu")[:1],
                                 overrides=TINY[cell], log=lambda s: None)
    return result


def failed(result: dict):
    return [k for k, c in result["checks"].items()
            if not c["value"] <= c["limit"]]


# ------------------------------------------------------------------ fleet
def fleet_unchanged(monkeypatch):
    """The local update reports its stump but leaves D as it was."""
    from repro.core import fleet
    orig = fleet.FleetCore._local_update

    def broken(self, slots, f, thr, pol):
        before = self.D.copy()
        out = orig(self, slots, f, thr, pol)
        self.D = before
        return out
    monkeypatch.setattr(fleet.FleetCore, "_local_update", broken)


def fleet_half_rows(monkeypatch):
    """Every fit sees only the first half of each client's rows."""
    import repro.models.weak as weak
    orig = weak.fit_stump_batched

    def broken(x, y, w, thr, **kw):
        keep = np.arange(w.shape[1]) < (w.shape[1] + 1) // 2
        w = np.where(keep[None, :], np.asarray(w), 0.0)
        w = w / np.maximum(w.sum(axis=1, keepdims=True), 1e-30)
        return orig(x, y, jax.numpy.asarray(w, np.float32), thr, **kw)
    monkeypatch.setattr(weak, "fit_stump_batched", broken)


def fleet_flipped(monkeypatch):
    """The fit's answer altered where it is produced: polarity flipped."""
    import repro.models.weak as weak
    orig = weak.fit_stump_batched

    def broken(*a, **kw):
        out = dict(orig(*a, **kw))
        out["polarity"] = -out["polarity"]
        return out
    monkeypatch.setattr(weak, "fit_stump_batched", broken)


def fleet_lost_between_waves(monkeypatch):
    """Each wave's update is right when it is written, but the next wave
    starts again from the distribution before it."""
    from repro.core import fleet
    orig = fleet.FleetCore._local_update

    def broken(self, slots, f, thr, pol):
        if hasattr(self, "_before"):
            self.D = self._before
        self._before = self.D.copy()
        return orig(self, slots, f, thr, pol)
    monkeypatch.setattr(fleet.FleetCore, "_local_update", broken)


@pytest.mark.parametrize("fault", [fleet_unchanged, fleet_half_rows,
                                   fleet_flipped, fleet_lost_between_waves],
                         ids=lambda f: f.__name__)
def test_fleet_fault_is_not_correct(fault, monkeypatch, no_cache):
    fault(monkeypatch)
    result = run("femnist_fleet.train")
    assert result["correct"] is False and failed(result)


# --------------------------------------------------------------- controls
@pytest.mark.parametrize("cell", sorted(TINY))
def test_control_is_not_correct(cell, no_cache):
    wl = harness.load_workload(cell)
    cfg = {**harness.load_config(wl["config"]),
           **TINY[cell].get("config", {})}
    wl = {**wl, **TINY[cell].get("workload", {})}
    driver = importlib.import_module(f"bench.drivers.{wl['driver']}")
    cell_ = driver.build(cfg, wl, 2**31 + 7, jax.devices("cpu")[:1], 1.0)
    cell_.window(1.0)
    cell_.free()
    assert all(c.ok for c in cell_.verify())
    assert not all(c.ok for c in cell_.control())
