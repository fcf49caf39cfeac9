"""KiB of one unit's loop-carried state in the largest bucket the
window's launches ran (``_pallas.state_bytes``): ready and next times,
issue and controller frees, L1 tags, ticks and fills, tick counters and
the SW+ outstanding table. None where the program does not count it."""


def read(ctx):
    from repro.core.warpsim import _pallas

    state_of = getattr(_pallas, "state_bytes", None)
    if state_of is None or not ctx.launches:
        return None
    state = state_of(last=ctx.launches)
    if not state:
        return None
    return state / 1024
