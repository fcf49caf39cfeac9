"""Host us the device was held per loop step of the window's launches:
the ``device_inflight`` stage over the steps ``_pallas.loop_steps``
counts for the window's launches (each launch's outer steps plus inner
trips, counted on the device). None where the program counts no loop
steps."""


def read(ctx):
    from repro.core.warpsim import _pallas

    steps_of = getattr(_pallas, "loop_steps", None)
    s, n = ctx.stages.get("device_inflight", (0.0, 0))
    if steps_of is None or not n or not ctx.launches:
        return None
    steps = steps_of(last=ctx.launches)
    if not steps:
        return None
    return s * 1e6 / steps
