"""Mean host ms of the daemon's ``cache_get`` stage (obs
``warpsim_stage_seconds``) in the window."""


def read(ctx):
    s, n = ctx.stages.get("cache_get", (0.0, 0))
    if not n:
        return None
    return s * 1e3 / n
