"""Percent of a deployed kernel's traced window in which no operation ran
on the device."""

from harness.readers import idle_share


def read(ctx):
    return idle_share(ctx) if ctx.traffic["kind"] == "closed_loop" else None
