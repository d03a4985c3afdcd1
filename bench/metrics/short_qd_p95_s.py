"""95th percentile over the shorts that arrived in the window of prefill
start less arrival, measured clock: the queue wait PecSched preempts for."""
from benchlib import stats


def read(ctx):
    return stats.pct(stats.queue_delay(ctx, stats.shorts(ctx)), 95)
