"""Arithmetic that several per-layer metric readers share."""

from __future__ import annotations


def union_s(spans: list[dict], names) -> float:
    """Seconds covered by the union of the spans named ``names``."""
    iv = sorted((s["ts"], s["ts"] + s["dur"]) for s in spans
                if s["name"] in names)
    total, end = 0.0, float("-inf")
    for a, b in iv:
        if b > end:
            total += b - max(a, end)
            end = b
    return total / 1e6


def durations_s(spans: list[dict], name: str) -> list[float]:
    return [s["dur"] / 1e6 for s in spans if s["name"] == name]


def idle_share(ctx) -> float | None:
    """Percent of the traced window in which no operation ran on the
    device."""
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def roofline_share(ctx, kernel: str) -> float | None:
    """Percent of the least time the chip could take for one call of
    ``kernel`` (its work over the peaks), out of the device time one call
    of it took in the trace.  None where the cell runs another kernel or
    the trace holds none of its operations."""
    if ctx.kernel != kernel or ctx.trace is None or not ctx.peaks:
        return None
    calls = ctx.window.calls
    seconds, matched = ctx.trace.op_seconds(ctx.trace_names)
    if not calls or not matched or seconds <= 0:
        return None
    least = max(ctx.flops / ctx.peaks["bf16_flops_per_s"],
                ctx.hbm_bytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / calls)
