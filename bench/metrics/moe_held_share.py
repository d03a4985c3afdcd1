"""Share of the (token, chosen expert) pairs of the MoE layers that
landed on an expert this chip holds: the program's counter
`moe/held_assignments` over `moe/token_layers` x experts per token, both
over prefill quanta and decode steps.  Uniform routing gives held /
router experts (16 / 128 = 12.5%).  Nothing is read from a program
without those counters."""
from benchlib import flops_afmoe as fa


def read(ctx):
    m = fa.of_ctx(ctx)
    tr = getattr(ctx.backend, "obs", None)
    if m is None or tr is None:
        return None
    c = tr.counters
    n = c.get("moe/token_layers", 0)
    if not n:
        return None
    return 100.0 * c.get("moe/held_assignments", 0) / (n * m.top_k)
