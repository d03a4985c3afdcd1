"""Host milliseconds per decode step before the chip has the step's
program: the program's `decode_iteration/prep` span (token vector, dense
view, slot lengths and their transfers) plus `decode_iteration/program`
(the dispatch of `_decode`), over the `decode_iteration` spans, from the
backend's span aggregates (`EngineBackend.obs`).  The run's log gives the
other children and the active slots per step.  Nothing is read from a
program without those spans."""
import sys

BEFORE = ("decode_iteration/prep", "decode_iteration/program")
AFTER = ("decode_iteration/view", "decode_iteration/append",
         "decode_iteration/readback")


def read(ctx):
    tr = getattr(ctx.backend, "obs", None)
    step = tr.spans.get("decode_iteration") if tr is not None else None
    if step is None or not step.n:
        return None
    before = sum(tr.spans[n].total_s for n in BEFORE if n in tr.spans)
    view = tr.spans.get("decode_iteration/view")
    kids = ", ".join(f"{c} {tr.mean_ms(c):.4f}" for c in BEFORE + AFTER)
    print(f"decode_host_ms: {step.n} steps ({view.n if view else 0} view "
          f"rebuilds), {tr.counters['decode_iteration/slots'] / step.n:.3f}"
          f" active slots per step, mean ms {kids}, step "
          f"{tr.mean_ms('decode_iteration'):.4f}", file=sys.stderr)
    return 1e3 * before / step.n
