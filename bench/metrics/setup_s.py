"""Process start to window start: weights, traffic, compile or cache load,
warm-up (host clock)."""


def read(ctx):
    return ctx.setup_s
