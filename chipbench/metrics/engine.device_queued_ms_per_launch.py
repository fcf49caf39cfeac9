"""Mean host ms a launch's device hold overlapped holds that ended
before it (the ``device_queued`` stage): time spent behind other
launches."""


def read(ctx):
    s, n = ctx.stages.get("device_queued", (0.0, 0))
    if not n:
        return None
    return s * 1e3 / n
