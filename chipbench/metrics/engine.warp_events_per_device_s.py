"""Scheduled warp macro-ops per device second of the family program.

The work is counted from the streams (the reference walk's op rows of
every cell simulated), so any engine that simulates the same streams
does the same work; the time is the device time of the program named
below in the profiler trace.
"""

from chipbench import reduce

PROGRAM = "jit__simulate_one"


def read(ctx):
    if ctx.trace is None or ctx.trace_window is None:
        return None
    t = reduce.program_seconds(ctx.trace, *ctx.trace_window).get(PROGRAM)
    if not t:
        return None
    return ctx.stream_rows() / t
