"""Share of the longs' prefill time spent suspended by preemption (§5.1):
over the longs that began prefill in the window, the sum of
`Request.suspended_s` (a suspension still open at the close counts to
then) over the sum of prefill start to first token (or the close),
measured clock.  Nothing is read from a program whose requests do not
record their suspensions."""
import sys

from benchlib import stats


def read(ctx):
    v_end = ctx.window.v_end
    longs = [r for r in stats.longs(ctx) if r.prefill_start is not None]
    if not longs or not hasattr(longs[0], "suspended_s"):
        return None
    held = sum(r.suspended_s + (v_end - r.paused_at
                                if r.paused_at is not None else 0.0)
               for r in longs)
    span = sum((v_end if r.first_token is None else min(r.first_token, v_end))
               - r.prefill_start for r in longs)
    if span <= 0:
        return None
    print(f"long_suspended_share: {len(longs)} longs, "
          f"{sum(r.n_preemptions for r in longs)} preemptions, "
          f"{held:.4f} s suspended of {span:.4f} s in prefill",
          file=sys.stderr)
    return 100.0 * held / span
