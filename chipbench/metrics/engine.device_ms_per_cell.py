"""Host ms the device was held per cell simulated in the window: the
``device_inflight`` stage (each launch's hold from dispatch until its
results are back, less what earlier holds covered), an upper bound on
device busy time that includes transfers."""


def read(ctx):
    s, n = ctx.stages.get("device_inflight", (0.0, 0))
    cells = ctx.cells_simulated()
    if not n or not cells:
        return None
    return s * 1e3 / cells
