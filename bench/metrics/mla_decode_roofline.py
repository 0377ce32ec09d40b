"""MLA decode's share of its roofline: the least time its work needs at the
chip's peaks (work/mla_decode.py, peaks.json) over the device time of one
call in the trace."""

from harness.readers import roofline_share


def read(ctx):
    return roofline_share(ctx, "mla_decode")
