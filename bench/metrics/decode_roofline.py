"""Least time of the decode steps' work (weights, the KV of the active
slots' live lengths, the new KV; FLOPs likewise; the larger bound) over
the device time of the decode program (`_decode`) in the trace."""
import sys

from benchlib import flops

PROGRAMS = ("_decode",)


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    dev = ctx.trace.program_s(PROGRAMS)
    if not dev:
        return None
    m, pk = ctx.dims, ctx.peak
    least, bound = 0.0, {"compute": 0.0, "memory": 0.0}
    for _, lens in ctx.taps.decode_steps:
        s, b = flops.roofline_s(flops.decode_flops(m, lens),
                                flops.decode_bytes(m, lens), pk)
        least += s
        bound[b] += s
    print(f"decode_roofline: least {least:.6f} s ({bound['compute']:.6f} "
          f"compute-bound, {bound['memory']:.6f} memory-bound) over "
          f"{dev:.6f} device s", file=sys.stderr)
    return 100.0 * least / dev
