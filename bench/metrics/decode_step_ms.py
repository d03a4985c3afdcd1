"""Mean wall milliseconds per `ReplicaEngine.decode_iteration`, from the
harness's tap (the call ends in host ints, so it is synchronous)."""


def read(ctx):
    steps = ctx.taps.decode_steps
    return 1e3 * sum(s for s, _ in steps) / len(steps) if steps else None
