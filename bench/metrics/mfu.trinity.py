"""Model FLOPs of the afmoe work served in the traced window (prefill
quanta, first-token heads, decode steps; held experts only, windowed
attention pairs capped at the window; `benchlib/flops_afmoe.py`) over
window seconds x peak bf16 FLOP/s x chips.  Nothing for another model."""
from benchlib import flops_afmoe as fa


def read(ctx):
    m = fa.of_ctx(ctx)
    if m is None or ctx.peak is None or not ctx.window.wall_s:
        return None
    t = ctx.taps
    f = sum(fa.prefill_flops(m, S, n, P) for S, n, P in t.prefill_quanta)
    f += fa.head_flops(m) * len(t.finalize)
    f += sum(fa.decode_flops(m, lens) for _, lens in t.decode_steps)
    return 100.0 * f / (ctx.window.wall_s * ctx.peak["flops_bf16"]
                        * ctx.chips)
