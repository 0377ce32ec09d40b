"""Mean seconds of the program's kernel.build span (the compile in
kernels/common.py compile_kernel), over configs compiled and refused."""

from harness.readers import durations_s


def read(ctx):
    d = durations_s(ctx.spans, "kernel.build")
    return sum(d) / len(d) if d else None
