"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device
numbers: busy and idle time, time per device op, and the longest idle
gaps named by what the host was doing.

``jax.profiler.ProfileData`` reads the file with nothing but JAX.  Device
ops are the events of the lines that ``DeviceLines`` selects (on a TPU the
``XLA Ops`` line of each ``/device:TPU:<n>`` plane); host spans are the
``bench.*`` ``TraceAnnotation`` events that the benchmark's own files put
around their calls into each layer.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

# host annotations the reduction reads: bench.window bounds the traced
# window, the others name what the host was doing in a device gap
ANNOTATION_PREFIX = "bench."
WINDOW = "bench.window"
IDLE_HOST = "host outside any bench span"


@dataclasses.dataclass(frozen=True)
class DeviceLines:
    """Which planes and lines hold device ops, and which of their events
    are bookkeeping rather than work."""
    plane: str = r"^/device:TPU:\d+$"
    line: str = r"^XLA Ops$"
    skip: str = r"^$"


TPU_OPS = DeviceLines()


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merge overlapping ``(start, end)`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclasses.dataclass
class TraceSummary:
    """Device numbers of one traced window, averaged over the devices the
    cell uses.  Times are seconds."""
    window_s: float
    busy_s: float
    n_devices: int
    op_s: Dict[str, float]           # op name -> seconds per device
    gap_s: Dict[str, float]          # host span -> idle seconds per device
    host_s: Dict[str, float]         # host span -> total seconds

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, pattern: str) -> float:
        """Device seconds of the ops whose name (see ``op_name``) matches
        ``pattern``."""
        rx = re.compile(pattern)
        return sum(s for name, s in self.op_s.items() if rx.search(name))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gap_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def op_name(event_name: str) -> str:
    """A device op's HLO instruction name and result type, without its
    operands or layout: ``"%copy.11 = f32[8,4]{1,0} copy(...)"`` ->
    ``"copy.11 f32[8,4]"``.  Names without ``" = "`` pass unchanged."""
    if " = " not in event_name:
        return event_name
    name, rest = event_name.split(" = ", 1)
    return f"{name.lstrip('%')} {re.sub(r'{[^}]*}', '', rest.split(' ')[0])}"


def label_instants(instants: Sequence[float],
                   spans: Sequence[Tuple[float, float, str]]) -> List[str]:
    """For each instant, the innermost host span covering it (the covering
    span that started last), by one sweep over starts, ends and instants;
    ``IDLE_HOST`` where none covers it.  The window span names nothing."""
    events = []
    for i, (s, e, name) in enumerate(spans):
        if name != WINDOW:
            events += [(s, 0, i), (e, 2, i)]
    events += [(t, 1, j) for j, t in enumerate(instants)]
    events.sort()
    active: Dict[int, float] = {}
    out = [IDLE_HOST] * len(instants)
    for t, kind, i in events:
        if kind == 0:
            active[i] = t
        elif kind == 2:
            active.pop(i, None)
        elif active:
            out[i] = spans[max(active, key=active.get)][2]
    return out


def summarize(path: Path, n_devices: int,
              lines: DeviceLines = TPU_OPS) -> TraceSummary:
    """Reduce the trace at ``path``.  The window is the ``bench.window``
    host span (the whole trace where it is missing); busy time is the
    union of device-op intervals inside it, per device, averaged over the
    ``n_devices`` the cell uses."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    plane_rx, line_rx = re.compile(lines.plane), re.compile(lines.line)
    skip_rx = re.compile(lines.skip)
    devices: Dict[str, List[Tuple[float, float, str]]] = {}
    host: List[Tuple[float, float, str]] = []
    for plane in pd.planes:
        is_dev = bool(plane_rx.search(plane.name))
        for line in plane.lines:
            dev_line = is_dev and bool(line_rx.search(line.name))
            key = f"{plane.name}#{line.name}"
            names: Dict[str, str] = {}
            for ev in line.events:
                name = ev.name
                if dev_line:
                    name = names.get(name) or names.setdefault(
                        name, op_name(name))
                s = ev.start_ns * 1e-9
                e = s + ev.duration_ns * 1e-9
                if dev_line:
                    if ev.duration_ns > 0 and not skip_rx.search(name):
                        devices.setdefault(key, []).append((s, e, name))
                elif name.startswith(ANNOTATION_PREFIX):
                    host.append((s, e, name))
    wins = [(s, e) for s, e, n in host if n == WINDOW]
    if wins:
        lo, hi = wins[0]
    else:
        every = [iv for evs in devices.values() for iv in evs] + host
        lo = min(s for s, _, _ in every)
        hi = max(e for _, e, _ in every)
    window_s = hi - lo
    op_s: Dict[str, float] = {}
    gap_s: Dict[str, float] = {}
    busy = 0.0
    gaps: List[Tuple[float, float]] = []
    for evs in devices.values():
        inside = [(max(s, lo), min(e, hi), n) for s, e, n in evs
                  if e > lo and s < hi]
        for s, e, n in inside:
            op_s[n] = op_s.get(n, 0.0) + (e - s)
        merged = union([(s, e) for s, e, _ in inside])
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2])
                 if g1 > g0]
    labels = label_instants([0.5 * (g0 + g1) for g0, g1 in gaps], host)
    for (g0, g1), lab in zip(gaps, labels):
        gap_s[lab] = gap_s.get(lab, 0.0) + (g1 - g0)
    n = max(n_devices, 1)
    host_s: Dict[str, float] = {}
    for s, e, name in host:
        host_s[name] = host_s.get(name, 0.0) + (e - s)
    return TraceSummary(
        window_s=window_s, busy_s=busy / n, n_devices=n_devices,
        op_s={k: v / n for k, v in op_s.items()},
        gap_s={k: v / n for k, v in gap_s.items()}, host_s=host_s)
