"""Percent of the window's kernel.compile spans that ran ahead: compiles
started on the compile thread (argument ``ahead`` 1) while the device
measured the config before, out of every compile of a config.

Read from the program's ring buffer, and only in a run that traced a
device: off the chip no compile can hide behind a device's measurement,
and a rehearsal's traced line holds the ring buffer's older metrics
alone."""


def read(ctx):
    if ctx.trace is None:
        return None
    compiles = [s for s in ctx.spans if s["name"] == "kernel.compile"]
    if not compiles:
        return None
    ahead = sum(1 for s in compiles if s.get("args", {}).get("ahead") == 1)
    return 100.0 * ahead / len(compiles)
