"""Share of the traced window in which no program ran on the device, %."""

from chipbench import reduce


def read(ctx):
    if ctx.trace is None or ctx.trace_window is None:
        return None
    w0, w1 = ctx.trace_window
    if w1 <= w0:
        return None
    return 100.0 * (1.0 - reduce.busy_s(ctx.trace, w0, w1) / (w1 - w0))
