"""Median over every long that arrived in the window, arrival to finish,
measured clock."""
from benchlib import stats


def read(ctx):
    return stats.pct(stats.jct(ctx, stats.longs(ctx)), 50)
