"""Mean seconds of the program's kernel.lower span in the trace: the
tracing and lowering of a config's kernel, which hold the interpreter
lock, out of its compile (kernel.build)."""

from pathlib import Path

from harness.attribution import of

ROOT = Path(__file__).resolve().parents[2]


def read(ctx):
    a = of(ctx, ROOT)
    d = [] if a is None else a.durations_s("kernel.lower")
    return sum(d) / len(d) if d else None
