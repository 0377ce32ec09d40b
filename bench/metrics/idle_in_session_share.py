"""Percent of a campaign's traced window in which the device sat idle
while the host was in the session loop: inside a session.*, journal.*,
tuner.* or pool.* span and no kernel.* span within it."""

from pathlib import Path

from harness.attribution import of

ROOT = Path(__file__).resolve().parents[2]


def read(ctx):
    a = of(ctx, ROOT)
    return None if a is None else a.idle_share("session")
