"""`tpot_p50_s` with each request's own last token in place of its finish:
the median of (last token - first token) / (tokens - 1) over every request
that finished in the window with two or more tokens, where the last
token's time is `EngineBackend.token_times[rid][-1]` (a decode batch's
finish is its longest member's), measured clock.  The run's log gives the
95th percentile.  Nothing is read from a program without token times."""
import sys

from benchlib import stats


def read(ctx):
    times = getattr(ctx.backend, "token_times", None)
    if times is None:
        return None
    vals = [(times[r.rid][-1] - r.first_token) / (r.output_len - 1)
            for r in ctx.window.requests
            if r.finish is not None and r.output_len >= 2 and r.rid in times]
    if not vals:
        return None
    print(f"tpot_own_p50_s: {len(vals)} requests, p95 "
          f"{stats.pct(vals, 95):.6f} s", file=sys.stderr)
    return stats.pct(vals, 50)
