"""Completion time of the longs per thousand of their prompt tokens: the
sum, over every long that arrived in the window, of arrival to finish on
the measured clock, over the sum of their prompt tokens, times 1000.  A
long weighs by its size, so the number does not jump with which sizes a
window holds, and it rises when longs wait behind shorts."""
from benchlib import stats


def read(ctx):
    longs = stats.longs(ctx)
    tokens = sum(r.input_len for r in longs)
    return 1e3 * sum(stats.jct(ctx, longs)) / tokens if tokens else None
