"""95th percentile over every short (prompt under the long threshold) that
arrived in the window, arrival to first served token, measured clock."""
from benchlib import stats


def read(ctx):
    return stats.pct(stats.ttft(ctx, stats.shorts(ctx)), 95)
