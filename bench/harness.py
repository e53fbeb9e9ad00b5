"""One benchmark run: set up a cell, measure it, check it, print the result.

The harness knows no cell by name.  It reads the cell's workload file, the
configuration that file names, and ``BENCHMARK.json`` for the metrics that
apply to the cell; the workload's ``driver`` key names the module under
``bench/drivers/`` that builds and drives the system, and each per-layer
metric is read by ``bench/metrics/<name>.py``.

A driver module exposes ``build(config, workload, seed, devices)``, which
does all set-up (data, the system under test, warm-up of every shape the
window uses) and returns a cell object with:

* ``window(seconds) -> Window``: the measured window;
* ``layer_info() -> dict``: shapes and counts the per-layer readers need;
* ``free()``: drop the program's state before the reference runs;
* ``verify() -> [Check]``: compare what the window produced with the plain
  reference (``bench/reference.py``).
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
SRC = ROOT / "src"


class BenchError(Exception):
    """The run cannot be made here (no chip, a missing file)."""


@dataclasses.dataclass
class Window:
    """What one measured window gives: the end-to-end values by metric
    name, the requests or jobs attempted and failed, and lines for the
    log (lateness, sizes) that stay out of the result line."""
    metrics: Dict[str, float]
    attempted: int
    failed: int
    notes: List[str] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Check:
    """One number compared with the reference, beside its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


# ------------------------------------------------------------------ files
def load_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing file {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_workload(name: str) -> dict:
    return load_json(BENCH / "workloads" / f"{name}.json")


def load_config(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def load_spec() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def applies(metric: dict, cell: str, reported: Sequence[str] = ()) -> bool:
    """Whether ``metric`` of BENCHMARK.json is read in ``cell``: listed
    under its ``workloads``, or, without that key, in every cell that
    reports the end-to-end metric it moves (end-to-end metrics without
    the key apply everywhere)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in reported


def load_reader(name: str):
    """The per-layer reader ``bench/metrics/<name>.py`` (names carry
    dots, so it is loaded from its path)."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no reader for per-layer metric {name!r}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------------ seeds
def sub_seeds(seed: int, n: int) -> List[int]:
    """``n`` 32-bit seeds derived from the run's seed, which may be any
    whole number (the driver's exceed 32 bits)."""
    return [int(s) for s in np.random.SeedSequence(int(seed)).generate_state(n)]


# ----------------------------------------------------------------- device
def require_chips(jax, chips: int, platform: str = "tpu") -> list:
    """The first ``chips`` devices, or BenchError when JAX finds no
    accelerator of ``platform`` or fewer chips than the cell needs."""
    devices = jax.devices()
    if devices[0].platform != platform:
        raise BenchError(f"no {platform} found (JAX sees "
                         f"{devices[0].platform}); the benchmark runs only "
                         f"on the chip")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices[:chips]


def use_compile_cache(jax) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else ``.jax_cache/`` at the checkout root (a fixed path: the
    path is part of an entry's key).  Every program is cached, however
    short its compile, so a second run of a cell compiles nothing."""
    import os
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events: a program compiled or loaded inside the window shows here.
    On the TPU the compile event also fires for a program loaded from the
    persistent cache, with the load's duration."""

    def __init__(self, jax) -> None:
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.compiles, self.compile_s, self.cache_hits


class GcLog:
    """Pauses of Python's garbage collector while it is open: a stall of
    the host loop that the device trace does not name."""

    def __init__(self) -> None:
        self.pauses: List[float] = []
        self._t0 = None
        gc.callbacks.append(self._callback)

    def _callback(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append(time.perf_counter() - self._t0)

    def close(self) -> str:
        gc.callbacks.remove(self._callback)
        p = self.pauses
        return (f"gc: {len(p)} collections in the window, longest "
                f"{1e3 * max(p, default=0.0):.3f} ms, total "
                f"{1e3 * sum(p):.3f} ms")


def annotate(name: str):
    """A host span in the profiler's trace (no cost while not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def device_record(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


# -------------------------------------------------------------------- run
def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: Optional[float] = None, devices=None,
             overrides: Optional[dict] = None, device_lines=None,
             log: Callable[[str], None] = print):
    """Run one cell and return ``(result, checks)``: the result line as a
    dict, and the compared numbers.

    ``devices``, ``overrides`` (merged into the configuration and the
    workload) and ``device_lines`` (which trace lines are device ops) let
    a test drive a cell at a tiny size off the chip; a run from the
    command line leaves them unset."""
    t_start = time.perf_counter() if t_start is None else t_start
    wl = load_workload(workload)
    cfg = load_config(wl["config"])
    if overrides:
        cfg = {**cfg, **overrides.get("config", {})}
        wl = {**wl, **overrides.get("workload", {})}
    spec = load_spec()

    import jax
    if devices is None:
        devices = require_chips(jax, int(wl["chips"]))
    cache = use_compile_cache(jax)
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    from bench import trace as tr
    from bench.peaks import peaks_for

    peaks = peaks_for(devices[0].device_kind, devices[0].platform)
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}; compile cache {cache}")
    clog = CompileLog(jax)
    if trace:
        seconds = min(seconds, float(wl.get("trace_seconds", seconds)))
    driver = importlib.import_module(f"bench.drivers.{wl['driver']}")
    cell = driver.build(cfg, wl, seed, devices, seconds)
    try:
        setup_s = time.perf_counter() - t_start
        c0 = clog.snapshot()
        log(f"setup: {setup_s:.3f} s, {c0[0]} programs compiled or loaded "
            f"({c0[1]:.3f} s), {c0[2]} of them from the persistent cache")

        e2e = [m for m in spec["end_to_end"] if applies(m, workload)]
        reported = [m["name"] for m in e2e]
        summary = None
        gclog = GcLog()
        if trace:
            tmp = Path(tempfile.mkdtemp(prefix="bench_trace_"))
            try:
                jax.profiler.start_trace(str(tmp))
                try:
                    with annotate("bench.window"):
                        win = cell.window(seconds)
                finally:
                    jax.profiler.stop_trace()
                summary = tr.summarize(tr.find_xplane(tmp), len(devices),
                                       lines=device_lines or tr.TPU_OPS)
            finally:
                shutil.rmtree(tmp, ignore_errors=True)
        else:
            win = cell.window(seconds)
        c1 = clog.snapshot()
        for note in win.notes + [gclog.close()]:
            log(note)
        log(f"window: {c1[0] - c0[0]} programs compiled or loaded inside "
            f"the window, {c1[2] - c0[2]} of them from the persistent cache")
        device = device_record(devices)
        info = cell.layer_info()

        metrics: Dict[str, dict] = {}
        if trace:
            ctx = LayerContext(trace=summary, info=info, peaks=peaks)
            for m in spec["per_layer"]:
                if not applies(m, workload, reported):
                    continue
                value = load_reader(m["name"]).read(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": float(value),
                                          "unit": m["unit"]}
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
        else:
            for m in e2e:
                value = (setup_s if m["name"] == "setup_s"
                         else win.metrics.get(m["name"]))
                if value is not None:
                    metrics[m["name"]] = {"value": float(value),
                                          "unit": m["unit"]}

        cell.free()
        t_check = time.perf_counter()
        checks = verify(cell)
        log(f"check: {time.perf_counter() - t_check:.3f} s to compare the "
            f"window's output with the reference")
    finally:
        cell.free()
    result = {"correct": all(c.ok for c in checks) and bool(checks),
              "attempted": int(win.attempted), "failed": int(win.failed),
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = summary.breakdown()
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result, checks


def verify(cell) -> List[Check]:
    """The cell's comparison with the reference; a comparison that raises
    is a failed one, reported as such."""
    import traceback
    try:
        return list(cell.verify())
    except Exception:                        # noqa: BLE001 — report it
        traceback.print_exc()
        return [Check("reference_comparison_failed", 1.0, 0.0)]


@dataclasses.dataclass
class LayerContext:
    """What a per-layer reader may read: the device trace summary, the
    cell's shapes and counts, and the device's peaks."""
    trace: object
    info: dict
    peaks: dict


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    say = lambda s: print(s, flush=True)
    try:
        result, checks = run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_start=t_start, log=say)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for c in checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
