"""Host ms in the ``trace_build`` stage (obs ``warpsim_stage_seconds``)
per trace family the window simulated."""


def read(ctx):
    s, _n = ctx.stages.get("trace_build", (0.0, 0))
    fams = ctx.families()
    if not fams or not _n:
        return None
    return s * 1e3 / fams
