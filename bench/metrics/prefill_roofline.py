"""Least time of the prefill programs' work (each quantum's layers and
each first-token head, FLOPs and bytes from their shapes, the larger of
the compute and memory bounds) over the device time of those programs
(`_prefill_slice`, `_finalize`) in the trace."""
import sys

from benchlib import flops

PROGRAMS = ("_prefill_slice", "_finalize")


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    dev = ctx.trace.program_s(PROGRAMS)
    if not dev:
        return None
    m, t, pk = ctx.dims, ctx.taps, ctx.peak
    least, bound = 0.0, {"compute": 0.0, "memory": 0.0}
    calls = [flops.roofline_s(flops.prefill_flops(m, S, n, P),
                              flops.prefill_bytes(m, S, n, P), pk)
             for S, n, P in t.prefill_quanta]
    calls += [flops.roofline_s(flops.head_flops(m), flops.head_bytes(m), pk)
              for _ in t.finalize]
    for s, b in calls:
        least += s
        bound[b] += s
    print(f"prefill_roofline: least {least:.6f} s ({bound['compute']:.6f} "
          f"compute-bound, {bound['memory']:.6f} memory-bound) over "
          f"{dev:.6f} device s", file=sys.stderr)
    return 100.0 * least / dev
