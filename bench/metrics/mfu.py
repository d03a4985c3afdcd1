"""Model FLOPs of the work served in the traced window (prefill quanta,
first-token heads, decode steps, counted from the shapes the taps
recorded) over window seconds x peak bf16 FLOP/s x chips."""
from benchlib import flops


def served_flops(ctx) -> float:
    m, t = ctx.dims, ctx.taps
    f = sum(flops.prefill_flops(m, S, n, P) for S, n, P in t.prefill_quanta)
    f += flops.head_flops(m) * len(t.finalize)
    f += sum(flops.decode_flops(m, lens) for _, lens in t.decode_steps)
    return f


def read(ctx):
    if ctx.peak is None or not ctx.window.wall_s:
        return None
    return 100.0 * served_flops(ctx) / (
        ctx.window.wall_s * ctx.peak["flops_bf16"] * ctx.chips)
