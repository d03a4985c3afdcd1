"""Windowed layers' KV blocks the migrations into decode slots held, over
what a full-length reservation of the same requests would hold on those
layers: the program's counters `admit/blocks_window` and
`admit/blocks_full` (layer-blocks after each admit's reservation), the
latter scaled from the full layers to as many windowed ones.  Nothing is
read from a program without those counters."""
from benchlib import flops_afmoe as fa


def read(ctx):
    m = fa.of_ctx(ctx)
    tr = getattr(ctx.backend, "obs", None)
    if m is None or tr is None:
        return None
    c = tr.counters
    full, win = c.get("admit/blocks_full", 0), c.get("admit/blocks_window",
                                                     0)
    nw = sum(m.windowed)
    if not full or not nw:
        return None
    return 100.0 * (win / nw) / (full / (m.layers - nw))
