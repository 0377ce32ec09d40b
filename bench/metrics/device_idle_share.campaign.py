"""Percent of a campaign's traced window in which no operation ran on the
device."""

from harness.readers import idle_share


def read(ctx):
    return idle_share(ctx) if ctx.traffic["kind"] == "sessions" else None
