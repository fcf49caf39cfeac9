"""Device launches of the study engine (``_pallas.launch_count()``) per
cell simulated in the window."""


def read(ctx):
    cells = ctx.cells_simulated()
    if not cells:
        return None
    return ctx.launches / cells
