"""Mean host milliseconds per KV migration into a decode slot (§5.2): the
program's `admit` span (`ReplicaEngine.admit`: stack the prefill's
per-layer KV, then write it into the paged pool), from the backend's span
aggregates (`EngineBackend.obs`).  The run's log gives the children and
the tokens moved per migration.  Nothing is read from a program without
those spans."""
import sys

CHILDREN = ("admit/assemble", "admit/pool_write")


def read(ctx):
    tr = getattr(ctx.backend, "obs", None)
    agg = tr.spans.get("admit") if tr is not None else None
    if agg is None or not agg.n:
        return None
    kids = ", ".join(f"{c} {tr.mean_ms(c):.4f}" for c in CHILDREN)
    print(f"kv_migrate_ms: {agg.n} migrations, "
          f"{tr.counters['admit/tokens'] / agg.n:.1f} tokens each, mean ms "
          f"{kids}", file=sys.stderr)
    return 1e3 * agg.total_s / agg.n
