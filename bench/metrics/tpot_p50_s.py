"""Median of (finish - first token) / (tokens - 1) over every request that
finished in the window with two or more tokens, measured clock."""
from benchlib import stats


def read(ctx):
    return stats.pct(stats.tpot(ctx), 50)
