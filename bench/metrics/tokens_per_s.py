"""Prompt tokens prefilled plus tokens generated in the window, over the
window's wall seconds (host clock): how much serving one chip does.

A prefill still running when the window closes counts the share of its
prompt that its finished layers cover, so a long prompt adds its work as
its quanta run and not all at once when it serves its first token."""


def read(ctx):
    win = ctx.window
    done = sum(ctx.by_rid[rid].prompt_len + len(t)
               for rid, t in win.served.items() if t)
    running = sum(ctx.by_rid[rid].prompt_len * layers / win.n_layers
                  for rid, layers in win.prefill_layers.items())
    return (done + running) / win.wall_s
