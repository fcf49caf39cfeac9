"""Cells returned per second: every cell of every study the window
returned, over the time from the window's start to its last return."""

from chipbench import stats


def read(ctx):
    if ctx.traffic["kind"] != "studies":
        return None
    return stats.rate(((r.t_done, r.amount) for r in ctx.studies), ctx.t0)
