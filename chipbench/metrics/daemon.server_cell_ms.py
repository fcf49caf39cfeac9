"""Mean host ms the daemon spent on a ``GET /cell``, from routing
through the written JSON response (obs stage ``server/cell``)."""


def read(ctx):
    s, n = ctx.stages.get("server/cell", (0.0, 0))
    if not n:
        return None
    return s * 1e3 / n
