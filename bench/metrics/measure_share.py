"""Percent of a campaign's window spent in the program's kernel.measure
spans (core/problem.py MeasuredProblem.evaluate)."""

from harness.readers import union_s


def read(ctx):
    if not ctx.window.trials or ctx.window.seconds <= 0:
        return None
    return 100.0 * union_s(ctx.spans, ("kernel.measure",)) / ctx.window.seconds
