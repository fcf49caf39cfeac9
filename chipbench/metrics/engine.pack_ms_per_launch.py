"""Mean host ms of the ``pallas_pack`` stage: padding and stacking a
launch's units and finding its compiled program."""


def read(ctx):
    s, n = ctx.stages.get("pallas_pack", (0.0, 0))
    if not n:
        return None
    return s * 1e3 / n
