"""Share of the window, from its start to its last return, in which no
launch held the device, %: 100 x (1 - the ``device_inflight`` stage's
seconds / the window). Read inside the program, so it needs no profiler
trace; the holds include transfers, so this is a lower bound on the
device's idle share."""


def read(ctx):
    s, n = ctx.stages.get("device_inflight", (0.0, 0))
    if not n or not ctx.requests:
        return None
    window = max(r.t_done for r in ctx.requests) - ctx.t0
    if window <= 0:
        return None
    return 100.0 * (1.0 - s / window)
