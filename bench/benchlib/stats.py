"""Latencies on the measured virtual clock, read from the policy's request
records after the window.  A request that arrived in the window and had
not reached the event when it closed enters at its age then, so a stall
shows in the tail instead of dropping out of it.  Percentiles interpolate
linearly between ranks, as `repro.core.metrics` does (`np.percentile`)."""
from __future__ import annotations

from typing import List, Optional

import numpy as np


def pct(values: List[float], q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(values, np.float64), q)) \
        if len(values) else None


def _at(t: Optional[float], v_end: float) -> float:
    return v_end if t is None or t > v_end else t


def shorts(ctx):
    return [r for r in ctx.window.requests if r.input_len < ctx.long_threshold]


def longs(ctx):
    return [r for r in ctx.window.requests
            if r.input_len >= ctx.long_threshold]


def ttft(ctx, reqs) -> List[float]:
    return [_at(r.first_token, ctx.window.v_end) - r.arrival for r in reqs]


def queue_delay(ctx, reqs) -> List[float]:
    return [_at(r.prefill_start, ctx.window.v_end) - r.arrival for r in reqs]


def jct(ctx, reqs) -> List[float]:
    return [_at(r.finish, ctx.window.v_end) - r.arrival for r in reqs]


def tpot(ctx) -> List[float]:
    """(finish - first token) / (tokens - 1) of every request that finished
    in the window with at least two output tokens."""
    return [(r.finish - r.first_token) / (r.output_len - 1)
            for r in ctx.window.requests
            if r.finish is not None and r.output_len >= 2]


def summary(ctx) -> dict:
    """Sample counts and percentiles of each latency, for the run's log."""
    out = {}
    for name, vals in (("ttft_short", ttft(ctx, shorts(ctx))),
                       ("tpot", tpot(ctx)),
                       ("jct_long", jct(ctx, longs(ctx))),
                       ("qd_short", queue_delay(ctx, shorts(ctx)))):
        out[name] = {"n": len(vals), **{f"p{q}": pct(vals, q)
                                        for q in (50, 80, 90, 95)}}
    return out
