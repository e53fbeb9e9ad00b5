#!/usr/bin/env python3
"""Where a fleet job's device-idle time goes, by what the host was doing.

For each seed it builds the fleet cell as a run of the benchmark does,
then runs four whole jobs: one untraced, one under the profiler alone,
one under the profiler with the program's obs spans mirrored into the
profiler's trace (``obs.tracing(profile_kernels=False, profiler=True)``),
and one untraced again.  The traced windows' device-idle seconds are
split exactly at host-span boundaries, each piece put down to the
innermost ``bench.*`` or ``train.*`` span covering it.

    python3 bench/host_split.py --workload femnist_fleet.train \\
        --seeds 101,102,103

Prints one JSON line per seed: the job times (what tracing costs), the
idle split, the program's transfer counters and the shares they give.
Runs only on the chip, like the benchmark itself.
"""
import contextlib
import dataclasses
import json
import re
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

if __name__ == "__main__":
    # the checkout root, not this directory, leads the path: bench/trace.py
    # must not shadow the standard library's trace module
    sys.path[0] = str(Path(__file__).resolve().parents[1])

from bench import trace as tr  # noqa: E402

# host spans read: the benchmark's own and the program's
PREFIXES = ("bench.", "train.")
# the program's spans whose idle seconds are staging (host copies and
# transfers) and host math (numpy updates, the server loop, timing walks)
STAGING = ("train.fleet.stack", "train.fleet.gather", "train.fleet.h2d",
           "train.fleet.grid_wait")
HOST_MATH = ("train.fleet.local_update", "train.fleet.sync",
             "train.fleet.walk", "train.fleet.finish")
F32 = 4


def split_idle(gaps: Sequence[Tuple[float, float]],
               spans: Sequence[Tuple[float, float, str]]) -> Dict[str, float]:
    """Idle seconds of ``gaps`` split exactly at the boundaries of the host
    ``spans``: each piece goes to the innermost covering span (the one
    that started last, the shorter of two that started together), or to
    ``IDLE_HOST`` where none covers it.  Gaps may overlap (one list per
    device, concatenated); a stretch where k devices are idle counts k
    times.  The window span names nothing."""
    events = []
    for i, (s, e, name) in enumerate(spans):
        if name != tr.WINDOW:
            events += [(s, 1, i), (e, -1, i)]
    for g0, g1 in gaps:
        events += [(g0, 2, -1), (g1, -2, -1)]
    events.sort(key=lambda ev: ev[0])
    active: Dict[int, Tuple[float, float, int]] = {}
    idle, prev = 0, None
    out: Dict[str, float] = {}
    for t, kind, i in events:
        if idle and t > prev:
            name = (spans[max(active.values())[2]][2] if active
                    else tr.IDLE_HOST)
            out[name] = out.get(name, 0.0) + idle * (t - prev)
        prev = t
        if kind == 1:
            s, e, _ = spans[i]
            active[i] = (s, -e, i)
        elif kind == -1:
            active.pop(i, None)
        else:
            idle += kind // 2
    return out


@dataclasses.dataclass
class HostSplit:
    """One traced window's device-idle seconds by host span, averaged
    over the devices: ``idle_span_s`` split exactly at span boundaries,
    ``gap_s`` each idle gap whole under the span that holds its
    midpoint."""
    window_s: float
    busy_s: float
    idle_span_s: Dict[str, float]
    gap_s: Dict[str, float]

    def share(self, names: Sequence[str]) -> float:
        """Percent of the window idle under the spans ``names``."""
        return 100.0 * sum(self.idle_span_s.get(n, 0.0)
                           for n in names) / self.window_s


def read(path: Path, lines: tr.DeviceLines = tr.TPU_OPS):
    """The device-op intervals of the trace at ``path`` by device (a
    device's lines together), and its ``bench.*`` and ``train.*`` host
    spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    plane_rx, line_rx = re.compile(lines.plane), re.compile(lines.line)
    skip_rx = re.compile(lines.skip)
    devices: Dict[str, List[Tuple[float, float]]] = {}
    host: List[Tuple[float, float, str]] = []
    for plane in pd.planes:
        is_dev = bool(plane_rx.search(plane.name))
        for line in plane.lines:
            dev_line = is_dev and bool(line_rx.search(line.name))
            for ev in line.events:
                s = ev.start_ns * 1e-9
                e = s + ev.duration_ns * 1e-9
                if dev_line:
                    if ev.duration_ns > 0 and not skip_rx.search(ev.name):
                        devices.setdefault(plane.name, []).append((s, e))
                elif ev.name.startswith(PREFIXES):
                    host.append((s, e, ev.name))
    return devices, host


def summarize(path: Path, n_devices: int,
              lines: tr.DeviceLines = tr.TPU_OPS) -> HostSplit:
    """Split the idle time of the ``bench.window`` span of the trace at
    ``path`` (the whole trace where it is missing) by host span."""
    devices, host = read(path, lines)
    wins = [(s, e) for s, e, n in host if n == tr.WINDOW]
    if wins:
        lo, hi = wins[0]
    else:
        every = [iv for evs in devices.values() for iv in evs] + [
            (s, e) for s, e, _ in host]
        lo, hi = min(s for s, _ in every), max(e for _, e in every)
    busy, gaps = 0.0, []
    for evs in devices.values():
        merged = tr.union([(max(s, lo), min(e, hi)) for s, e in evs
                           if e > lo and s < hi])
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2])
                 if g1 > g0]
    n = max(n_devices, 1)
    gap_s: Dict[str, float] = {}
    labels = tr.label_instants([0.5 * (g0 + g1) for g0, g1 in gaps], host)
    for (g0, g1), lab in zip(gaps, labels):
        gap_s[lab] = gap_s.get(lab, 0.0) + (g1 - g0) / n
    idle = {k: v / n for k, v in split_idle(gaps, host).items()}
    return HostSplit(window_s=hi - lo, busy_s=busy / n, idle_span_s=idle,
                     gap_s=gap_s)


def h2d_needed(rows: int, features: int, wave_rows: Sequence[int]) -> int:
    """Host-to-device bytes a job's work needs: each real row's features
    and label once a job, and its weight once for each fit wave that fits
    its client (``wave_rows``: the real rows of each wave)."""
    return F32 * (rows * (features + 1) + sum(wave_rows))


def measure(cell, n_devices: int, lines: tr.DeviceLines = tr.TPU_OPS
            ) -> dict:
    """Four jobs of ``cell`` (a fleet cell after set-up): untraced, under
    the profiler, under the profiler with obs spans mirrored, untraced."""
    import jax
    import numpy as np
    from repro import obs
    from bench.harness import annotate

    def job():
        t0 = time.perf_counter()
        cell.job()
        return time.perf_counter() - t0

    out = {"untraced_s": [job()]}
    for mode in ("profiler", "profiler_obs"):
        # the driver's taps give each wave's real rows, from the shapes
        cell.recording = True
        cell.jobs.append({"cid": np.zeros(0, np.int64), "waves": []})
        w0 = len(cell.wave_rows)
        with tempfile.TemporaryDirectory(prefix="host_split_") as d:
            jax.profiler.start_trace(d)
            try:
                with contextlib.ExitStack() as stack:
                    tracer = (stack.enter_context(obs.tracing(
                        profile_kernels=False, profiler=True))
                        if mode == "profiler_obs" else None)
                    with annotate("bench.window"):
                        job_s = job()
                    counters = {name: c.value for name, _, c in
                                obs.get_registry().counters()
                                if name.startswith("train.")}
                    spans = tracer.finished() if tracer else []
                    meta = tracer.meta() if tracer else {}
            finally:
                jax.profiler.stop_trace()
            split = summarize(tr.find_xplane(Path(d)), n_devices, lines)
        cell.recording = False
        rec = {"job_s": job_s, "window_s": split.window_s,
               "busy_s": split.busy_s,
               "idle_share": 100.0 * (1.0 - split.busy_s / split.window_s),
               "idle_span_s": split.idle_span_s, "gap_s": split.gap_s}
        if tracer is not None:
            clients = cell.data["clients"]
            needed = h2d_needed(sum(len(y) for _, y in clients),
                                clients[0][0].shape[1],
                                [r for _, r in cell.wave_rows[w0:]])
            rec.update(
                staging_idle=split.share(STAGING),
                host_math_idle=split.share(HOST_MATH),
                program_idle=split.share([n for n in split.idle_span_s
                                          if n.startswith("train.")]),
                h2d_needed_bytes=needed,
                h2d_fill=100.0 * needed / counters["train.fleet.h2d_bytes"],
                counters=counters, ring=meta,
                spans={n: sum(d["name"] == n for d in spans)
                       for n in sorted({d["name"] for d in spans})})
        out[mode] = rec
    out["untraced_s"].append(job())
    return out


def main(argv=None) -> int:
    import argparse
    import importlib
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    from bench import harness
    wl = harness.load_workload(args.workload)
    cfg = harness.load_config(wl["config"])
    import jax
    try:
        devices = harness.require_chips(jax, int(wl["chips"]))
    except harness.BenchError as e:
        print(f"host_split: {e}", file=sys.stderr)
        return 2
    harness.use_compile_cache(jax)
    sys.path.insert(0, str(harness.SRC))
    driver = importlib.import_module(f"bench.drivers.{wl['driver']}")
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = driver.build(cfg, wl, seed, devices, 0.0)
        try:
            out = {"seed": seed, **measure(cell, len(devices))}
        finally:
            cell.free()
        print(json.dumps(out), flush=True)
        del cell
    return 0


if __name__ == "__main__":
    sys.exit(main())
