"""Percent of a campaign's traced window in which the device sat idle
while the host was inside the program's kernel.build, kernel.lower or
kernel.compile span (kernels/common.py compile_kernel)."""

from pathlib import Path

from harness.attribution import of

ROOT = Path(__file__).resolve().parents[2]


def read(ctx):
    a = of(ctx, ROOT)
    return None if a is None else a.idle_share("compile")
