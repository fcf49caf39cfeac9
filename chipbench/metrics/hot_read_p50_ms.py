"""Median latency of every cell read in the window, in ms. A read that
failed counts as slower than any that came back."""

from chipbench import stats


def read(ctx):
    if ctx.traffic["kind"] != "cell_reads" or not ctx.requests:
        return None
    return stats.percentile(
        [float("inf") if r.failed else (r.t_done - r.t_issue) * 1e3
         for r in ctx.requests], 50)
