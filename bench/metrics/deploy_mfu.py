"""The deployed call's share of the chip's bf16 peak: the algorithm's
operations times the calls, over the window on the host clock, over the
peak.  It bounds a gain whichever kernel the call runs."""


def read(ctx):
    w = ctx.window
    if ctx.traffic["kind"] != "closed_loop" or not ctx.peaks or not w.calls:
        return None
    return 100.0 * ctx.flops * w.calls / w.seconds \
        / ctx.peaks["bf16_flops_per_s"]
