"""Median milliseconds per layer of long-prompt prefill quanta on one
engine (`EngineBackend.sp_timings[1]`: host clock around
`block_until_ready` of each quantum)."""
import numpy as np


def read(ctx):
    t = ctx.backend.sp_timings.get(1)
    return 1e3 * float(np.median(t)) if t else None
