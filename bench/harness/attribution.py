"""Where a campaign's idle device time went, read from the program's own
spans in the profiler's trace.

While telemetry is enabled the program writes each of its spans into the
running profiler's trace (``repro.telemetry.trace``), on the host plane
beside the harness's ``bench.*`` annotations and on the clock of the
device's operations.  This reader takes the operations of the first TPU
device inside ``bench.window``, cut to it as ``xtrace.reduce`` cuts them,
and gives each idle nanosecond to the innermost program span open on the
host at that instant: its layer is ``compile``, ``measure`` or
``session``, and time under no program span is ``unspanned``.  The four
add up to the device's idle time in the window.

It also pairs each ``kernel.measure`` span of the trace with the same
span in the program's ring buffer, which holds the objective (``best_s``)
set when the span closed, to compare the objective with the device time
of the calls it timed.

A trace that holds no program span (a program that does not write its
spans into the profiler) gives no attribution, and its metrics no value.
"""

from __future__ import annotations

import functools
import statistics
from dataclasses import dataclass
from pathlib import Path

from . import xtrace
from .cell import STATE

COMPILE = frozenset({"kernel.build", "kernel.lower", "kernel.compile"})
MEASURE = frozenset({"kernel.measure"})
#: categories of the session loop's spans
SESSION = frozenset({"session", "journal", "tuner", "pool"})
LAYERS = ("compile", "measure", "session", "unspanned")


def layer_of(name: str) -> str | None:
    """The layer of a program span, None for any other host event."""
    if name in COMPILE:
        return "compile"
    if name in MEASURE:
        return "measure"
    category, dot, _ = name.partition(".")
    if dot and category in SESSION:
        return "session"
    return None


def idle_by_layer(window: tuple[float, float], ops, spans) -> dict:
    """Idle time of the device in ``window``, by the layer of the innermost
    program span open at each instant.

    ``ops`` are the (start, end) of the device's operations and ``spans``
    the (name, start, end) of host events, all on one clock; events that
    are not program spans are left out, and where none is open the time is
    ``unspanned``.  The innermost open span is the one that started last.
    """
    w0, w1 = window
    edges = [w0] + [x for ab in _busy(window, ops) for x in ab] + [w1]
    program = [(s, e, layer) for n, s, e in spans
               if (layer := layer_of(n)) is not None]
    out = dict.fromkeys(LAYERS, 0.0)
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        inside = [p for p in program if p[1] > a and p[0] < b]
        cuts = sorted({a, b} | {x for s, e, _ in inside for x in (s, e)
                                if a < x < b})
        for x, y in zip(cuts, cuts[1:]):
            open_ = [(s, -e, layer) for s, e, layer in inside
                     if s <= x and e >= y]
            out[max(open_)[2] if open_ else "unspanned"] += y - x
    return out


@dataclass
class Attribution:
    window_s: float
    #: idle seconds of the device by layer (LAYERS)
    idle_s: dict
    #: program spans in the window: (name, start ns, end ns, arguments)
    spans: list
    #: the device's busy intervals in the window, merged (ns)
    busy: list

    def idle_share(self, layer: str) -> float:
        """Percent of the window the device sat idle under ``layer``."""
        return 100.0 * self.idle_s[layer] / self.window_s

    def durations_s(self, name: str) -> list[float]:
        return [(e - s) / 1e9 for n, s, e, _ in self.spans if n == name]

    def busy_s(self, start: float, end: float) -> float:
        """Seconds the device was busy between ``start`` and ``end``."""
        return sum(max(0.0, min(b, end) - max(a, start))
                   for a, b in self.busy) / 1e9

    def objective_excess(self, ring: list[dict]) -> float | None:
        """Median over measured configs, in percent, of how far the
        objective (the best timed call, on the host's clock) lies above
        the device time of one call inside the config's ``kernel.measure``
        span.  The n-th span of the trace is paired with the n-th of the
        ring buffer ``ring``; a pairing whose keys differ gives None."""
        traced = [(s, e, args) for n, s, e, args in self.spans
                  if n in MEASURE]
        kept = sorted((r for r in ring if r["name"] in MEASURE),
                      key=lambda r: r["ts"])
        if not traced or len(traced) != len(kept):
            return None
        excess = []
        for (s, e, args), r in zip(traced, kept):
            rargs = r.get("args", {})
            if args.get("key") is None or args.get("key") != rargs.get("key"):
                return None
            best, calls = rargs.get("best_s"), args.get("calls")
            device = self.busy_s(s, e)
            if best is None or not calls or device <= 0:
                continue
            excess.append(100.0 * (best / (device / calls) - 1.0))
        return statistics.median(excess) if excess else None


def reduce(path: Path) -> Attribution | None:
    """The attribution of the trace at ``path``; None where it holds no TPU
    operation, no ``bench.window`` or no program span in the window."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    host, ops = [], None
    for plane in data.planes:
        if xtrace.DEVICE_PLANE.match(plane.name):
            lines = {line.name for line in plane.lines}
            ops = ops or [(a, b) for _, a, b in xtrace._events(
                plane, xtrace.OPS_LINE if xtrace.OPS_LINE in lines else None)]
        if plane.name.startswith("/host:"):
            host += [(ev.name, float(ev.start_ns),
                      float(ev.start_ns + ev.duration_ns), ev)
                     for line in plane.lines for ev in line.events]
    windows = [(a, b) for n, a, b, _ in host if n == xtrace.WINDOW]
    if not ops or not windows:
        return None
    window = max(windows, key=lambda w: w[1] - w[0])
    w0, w1 = window
    spans = [(n, a, b, dict(ev.stats) if n in MEASURE else {})
             for n, a, b, ev in host
             if layer_of(n) is not None and b > w0 and a < w1]
    if not spans:
        return None
    spans.sort(key=lambda sp: sp[1])
    idle = idle_by_layer(window, ops, [sp[:3] for sp in spans])
    return Attribution(window_s=(w1 - w0) / 1e9,
                       idle_s={k: v / 1e9 for k, v in idle.items()},
                       spans=spans, busy=_busy(window, ops))


def _busy(window, ops) -> list[tuple[float, float]]:
    """The union of the operations' intervals, cut to ``window``."""
    w0, w1 = window
    return xtrace._union([(max(a, w0), min(b, w1)) for a, b in ops
                          if b > w0 and a < w1])


@functools.lru_cache(maxsize=1)
def _reduce_cached(path: str, mtime_ns: int) -> Attribution | None:
    return reduce(Path(path))


def of(ctx, root: Path) -> Attribution | None:
    """The attribution of the run's trace, which ``cell.py`` writes under
    ``root``; read once per run.  None where the run made no device trace."""
    if ctx.trace is None:
        return None
    path = xtrace.find_trace(Path(root) / STATE / ctx.cell["name"]
                             / "profile")
    if path is None:
        return None
    return _reduce_cached(str(path), path.stat().st_mtime_ns)
