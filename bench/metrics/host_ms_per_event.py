"""Host milliseconds per simulator event that were not spent waiting on the
chip: window wall time less the backend's measured chip work
(`EngineBackend.measured_s`), over the events the loop applied."""


def read(ctx):
    w = ctx.window
    if not w.n_events:
        return None
    return 1e3 * (w.wall_s - w.measured_s) / w.n_events
