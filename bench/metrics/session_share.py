"""Percent of a campaign's window spent in the session loop: the union of
the program's session.ask, session.tell, journal.append and
journal.publish spans (orchestrator/runner.py, orchestrator/store.py)."""

from harness.readers import union_s

NAMES = ("session.ask", "session.tell", "journal.append", "journal.publish")


def read(ctx):
    if not ctx.window.trials or ctx.window.seconds <= 0:
        return None
    return 100.0 * union_s(ctx.spans, NAMES) / ctx.window.seconds
