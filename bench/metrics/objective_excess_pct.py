"""Median over a campaign's measured configs of how far the objective (the
best of the timed calls on the host's clock, best_s on the program's
kernel.measure span) lies above the device time of one call inside that
span, in percent."""

from pathlib import Path

from harness.attribution import of

ROOT = Path(__file__).resolve().parents[2]


def read(ctx):
    a = of(ctx, ROOT)
    return None if a is None else a.objective_excess(ctx.spans)
