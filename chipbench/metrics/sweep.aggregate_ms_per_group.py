"""Host ms in the ``aggregate`` stage less the ``trace_build`` stage it
nests, per expansion group (trace family x expansion key) simulated."""


def read(ctx):
    agg, n = ctx.stages.get("aggregate", (0.0, 0))
    tb, _ = ctx.stages.get("trace_build", (0.0, 0))
    groups = ctx.groups()
    if not groups or not n:
        return None
    return (agg - tb) * 1e3 / groups
