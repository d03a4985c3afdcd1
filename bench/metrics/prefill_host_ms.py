"""Mean host milliseconds per prefill quantum: the program's
`prefill_quantum` span (`ReplicaEngine.prefill_quantum`: the dispatch of
`_prefill_slice`, then the split of its KV into one array per layer), from
the backend's span aggregates (`EngineBackend.obs`).  The run's log gives
the mean of each child span.  Nothing is read from a program without
those spans."""
import sys

CHILDREN = ("prefill_quantum/program", "prefill_quantum/kv_split")


def read(ctx):
    tr = getattr(ctx.backend, "obs", None)
    agg = tr.spans.get("prefill_quantum") if tr is not None else None
    if agg is None or not agg.n:
        return None
    kids = ", ".join(f"{c} {tr.mean_ms(c):.4f}" for c in CHILDREN)
    print(f"prefill_host_ms: {agg.n} quanta, mean ms {kids}, self "
          f"{1e3 * agg.self_s / agg.n:.4f}", file=sys.stderr)
    return 1e3 * agg.total_s / agg.n
