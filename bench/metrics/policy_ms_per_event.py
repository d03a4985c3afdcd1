"""Policy milliseconds per event: `Simulator.sched_time` (the wall time of
the policy's callbacks, dispatch passes and fleet steps, less the host
seconds spent inside the backend calls they make, `EngineBackend.host_s`)
over the events the loop applied.  Nothing is read from a program whose
backend does not count its own host seconds."""
import sys


def read(ctx):
    be = ctx.backend
    sim = getattr(be, "sim", None)
    if sim is None or not hasattr(be, "host_s") or not sim.n_events:
        return None
    print(f"policy_ms_per_event: policy {sim.sched_time:.4f} s, backend "
          f"{sim.backend_time:.4f} s, over {sim.n_events} events",
          file=sys.stderr)
    return 1e3 * sim.sched_time / sim.n_events
