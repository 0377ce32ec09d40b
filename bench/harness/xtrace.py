"""Reduce a JAX profiler trace (``.xplane.pb``) to the device's busy and
idle time, the device time of named operations, and the longest idle gaps,
each named by the benchmark's own host annotation that covers it.

Device operations are the events on the ``XLA Ops`` line of each TPU
plane (``/device:TPU:<n>``).  The window is the host event the harness
opens around the traced slice (``bench.window``); every interval is cut
to it.  Busy time is the union of the operations' intervals.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
#: prefix of the harness's own host annotations
HOST_PREFIX = "bench."


@dataclass
class Reduced:
    window_s: float
    #: seconds in which an operation ran, averaged over the devices
    busy_s: float
    #: device seconds per operation name, averaged over the devices
    ops: dict[str, float] = field(default_factory=dict)
    #: (name of the host annotation that covers it, seconds), longest first
    gaps: list[tuple[str, float]] = field(default_factory=list)
    devices: int = 0

    def op_seconds(self, substrings) -> tuple[float, int]:
        """Device seconds of the operations whose name holds any of
        ``substrings``, and how many names matched."""
        subs = [s.lower() for s in substrings]
        hits = {n: s for n, s in self.ops.items()
                if any(x in n.lower() for x in subs)}
        return sum(hits.values()), len(hits)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.gaps[:top]]}


def find_trace(directory: Path) -> Path | None:
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    return found[-1] if found else None


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _events(plane, line_name: str | None = None):
    """(name, start, end) of the events on ``plane``; an operation's name
    is its HLO name, the text before `` = `` (``%gemm.1``)."""
    for line in plane.lines:
        if line_name is not None and line.name != line_name:
            continue
        for ev in line.events:
            yield (ev.name.split(" = ", 1)[0], float(ev.start_ns),
                   float(ev.start_ns + ev.duration_ns))


def reduce(path: Path) -> Reduced | None:
    """The reduction of the trace at ``path``; None where it holds no TPU
    operation."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    host: list[tuple[str, float, float]] = []
    devices = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            host += [e for e in _events(plane) if e[0].startswith(HOST_PREFIX)]
    per_device = []
    for plane in devices:
        lines = {line.name for line in plane.lines}
        ops = list(_events(plane, OPS_LINE if OPS_LINE in lines else None))
        if ops:
            per_device.append(ops)
    if not per_device:
        return None
    windows = [(a, b) for n, a, b in host if n == WINDOW]
    if windows:
        w0, w1 = max(windows, key=lambda w: w[1] - w[0])
    else:
        w0 = min(a for ops in per_device for _, a, _ in ops)
        w1 = max(b for ops in per_device for _, _, b in ops)
    spans = [(n, a, b) for n, a, b in host if n != WINDOW]

    busy = 0.0
    totals: dict[str, float] = {}
    gaps: list[tuple[str, float]] = []
    for i, ops in enumerate(per_device):
        clipped = [(n, max(a, w0), min(b, w1)) for n, a, b in ops
                   if b > w0 and a < w1]
        for n, a, b in clipped:
            totals[n] = totals.get(n, 0.0) + (b - a)
        merged = _union([(a, b) for _, a, b in clipped])
        busy += sum(b - a for a, b in merged)
        if i == 0:
            edges = [w0] + [x for ab in merged for x in ab] + [w1]
            for a, b in zip(edges[::2], edges[1::2]):
                if b > a:
                    gaps.append((_cover(spans, a, b), (b - a) / 1e9))
    n = len(per_device)
    gaps.sort(key=lambda g: -g[1])
    return Reduced(window_s=(w1 - w0) / 1e9, busy_s=busy / n / 1e9,
                   ops={k: v / n / 1e9 for k, v in totals.items()},
                   gaps=gaps, devices=n)


def _cover(spans, a: float, b: float) -> str:
    """The innermost host annotation that overlaps [a, b] most."""
    best, best_key = "unannotated", (0.0, 0.0)
    for name, s, e in spans:
        overlap = min(b, e) - max(a, s)
        if overlap > 0:
            key = (overlap, -(e - s))
            if key > best_key:
                best, best_key = name, key
    return best
