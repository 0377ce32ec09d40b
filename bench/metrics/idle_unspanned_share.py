"""Percent of a campaign's traced window in which the device sat idle
while the host was inside no program span (only the harness's bench.*
annotations): the part of the idle time the program's tracing misses."""

from pathlib import Path

from harness.attribution import of

ROOT = Path(__file__).resolve().parents[2]


def read(ctx):
    a = of(ctx, ROOT)
    return None if a is None else a.idle_share("unspanned")
