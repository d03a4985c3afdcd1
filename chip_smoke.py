"""Bring-up check of the PecSched serving path on a TPU.

    python chip_smoke.py             # one chip: the served path, Mistral-7B widths
    python chip_smoke.py --chips 4   # four chips: gang-SP prefill vs one chip
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny   # rehearsal at a tiny size

One chip: `make_policy("pecsched")` drives `Simulator` -> `EngineBackend` ->
`ReplicaEngine` through `MiniCluster` on the measured clock, with one
general and one dedicated decode engine.  A long prompt is preempted by
shorts that arrive inside its prefill window; its tokens must equal an
uninterrupted run's.  One short's logits after prefill and after each
paged decode step are compared with `models.model.forward` on float32
copies of the weights.

Four chips (`--chips 4`): a long prefill that the policy gang-schedules at
degree 4 on a (2, 2) mesh, compared with the same prompt on one chip.

The last line of standard output is one JSON object,
`{"ok": true, "device": {...}}`, printed only when every check passed on a
TPU.  Without a TPU the script exits non-zero: at once, or after every
phase has run with `--tiny`.  The weights are random, drawn from `--seed`.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import reduced_config  # noqa: E402
from repro.configs.mistral_7b import CONFIG, PIPELINE_STAGE  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.models import model as mdl  # noqa: E402
from repro.serving import MiniCluster, ServeRequest  # noqa: E402
from repro.serving.engine import ReplicaEngine  # noqa: E402
from repro.sp.gang import OUTER_AXIS, INNER_AXIS, plan_for_gang  # noqa: E402

#: the paper's long/short threshold (§6.1), in prompt tokens
LONG_THRESHOLD = 2048

#: Engine (bf16) vs float32 reference, relative L2 error of a logit vector.
#: bf16 rounds every weight and activation to 2^-8 relative; through 8
#: residual layers that moves the logits by a few percent of their norm
#: (1.2-1.4% at the tiny width on a CPU).  Weights rounded to fp8-e4m3
#: (2^-4) move them by tens of percent (22% there), so this bound fails a
#: computation below the config's bf16 (the canary phase checks that it
#: does).
LOGIT_REL_TOL = 0.05
#: Largest single-logit error, in units of the reference logits' standard
#: deviation: bf16 rounding shifts no logit by more than a small fraction
#: of the spread (0.05 at the tiny width on a CPU; the maximum over a 64x
#: larger vocabulary sits a few error-deviations further out).
LOGIT_MAX_TOL = 0.25
#: Gang SP vs one chip: two bf16 computations of the same function that
#: sum in different orders (ring attention merges per-shard softmax
#: statistics), each within a few percent of the float32 result — so the
#: same bound as against the reference, on the relative L2 of the
#: last-position logits, of the gathered KV and of one decode step.
GANG_REL_TOL = LOGIT_REL_TOL


@dataclasses.dataclass(frozen=True)
class Size:
    cfg: object
    max_slots: int
    max_len: int
    long_len: int
    short_lens: tuple
    long_threshold: int
    long_new: int = 8
    short_new: int = 4


FULL = Size(PIPELINE_STAGE, max_slots=4, max_len=4096, long_len=3584,
            short_lens=(256, 448, 256, 448), long_threshold=LONG_THRESHOLD)
#: every phase at a width a CPU runs in seconds (rehearsal only)
TINY = Size(dataclasses.replace(
                reduced_config(CONFIG, layers=8, d_model=256, n_heads=8,
                               n_kv=2, d_ff=512, vocab=512),
                dtype="bfloat16"),
            max_slots=4, max_len=256, long_len=192, short_lens=(24, 40, 24, 40),
            long_threshold=128)


class CompileCounter:
    """Counts XLA compilations, so serving windows can show they held none."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(info: dict) -> None:
    if info["platform"] != "tpu":
        sys.exit(f"FAIL: no TPU found (JAX platform {info['platform']!r}, "
                 f"{info['count']} device(s)); nothing was measured")


def check(ok: bool, what: str) -> None:
    print(f"  [{'ok' if ok else 'FAIL'}] {what}")
    if not ok:
        sys.exit(f"FAIL: {what}")


def peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak, limit = stats.get("peak_bytes_in_use"), stats.get("bytes_limit")
    if peak is None:
        return "not reported by this backend"
    return f"{peak} bytes ({peak / 2**30:.2f} GiB) of {limit} bytes"


def prompt(rng, n: int, cfg) -> np.ndarray:
    return rng.integers(0, cfg.vocab_size, n).astype(np.int32)


def logit_errors(got: np.ndarray, ref: np.ndarray):
    """(relative L2 error, largest error / reference std) per row."""
    rel = np.linalg.norm(got - ref, axis=-1) / np.linalg.norm(ref, axis=-1)
    mx = np.abs(got - ref).max(-1) / ref.std(-1)
    return rel, mx


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------
def serve_phase(size: Size, params, rng, compiles: CompileCounter):
    cfg = size.cfg
    mc = MiniCluster(cfg, params, n_engines=1, policy="pecsched",
                     max_len=size.max_len, max_slots=size.max_slots,
                     long_threshold=size.long_threshold,
                     layers_per_quantum=2, clock="measured")
    long_toks = prompt(rng, size.long_len, cfg)
    shorts = [prompt(rng, n, cfg) for n in size.short_lens]

    t0 = time.perf_counter()
    mc.backend.warmup({size.long_len, *size.short_lens}, [0])
    print(f"set-up: compile + warm-up {time.perf_counter() - t0:.3f} s "
          f"({compiles.n} XLA compilations)")
    first_logits = tap_prefill_logits(mc.backend._engine(0))

    n0 = compiles.n
    alone = ServeRequest(rid=0, arrival=0.0, tokens=long_toks,
                         max_new=size.long_new, is_long=True)
    t0 = time.perf_counter()
    mc.submit(alone)
    mc.run()
    wall_alone = time.perf_counter() - t0
    window = alone.first_token - alone.prefill_start
    alone_logits = np.asarray(first_logits.pop(0))
    print(f"serving, long alone: {size.long_len}-token prompt, prefill "
          f"window {window:.6f} s measured, {mc.backend.measured_s:.6f} s "
          f"device-bound compute, {wall_alone:.3f} s wall")

    long_req = ServeRequest(rid=0, arrival=0.0, tokens=long_toks,
                            max_new=size.long_new, is_long=True)
    short_reqs = [ServeRequest(rid=i + 1, arrival=window * f, tokens=t,
                               max_new=size.short_new)
                  for i, (f, t) in enumerate(zip((0.15, 0.3, 0.45, 0.6),
                                                 shorts))]
    t0 = time.perf_counter()
    for r in [long_req, *short_reqs]:
        mc.submit(r)
    mc.run()
    wall = time.perf_counter() - t0
    m = mc.metrics()
    stats = mc.backend.stats
    print(f"serving, long + {len(short_reqs)} shorts "
          f"({'/'.join(map(str, size.short_lens))} tokens) arriving inside "
          f"its prefill window: {mc.backend.measured_s:.6f} s device-bound "
          f"compute, {wall:.3f} s wall; short queueing delay mean "
          f"{m['short_qd_mean']:.6f} s p99 {m['short_qd_p99']:.6f} s, long "
          f"JCT {m['long_jct_mean']:.6f} s")
    print(f"preemptions: {long_req.n_preemptions} of the long "
          f"({m['preemptions']} in all); prefill quanta "
          f"{stats['prefill_quanta']}, KV migrations {stats['kv_migrations']}"
          f", short_decode batches {stats['short_decode']}")
    check(compiles.n == n0, f"no compilation inside the serving windows "
          f"({compiles.n - n0})")
    check(long_req.n_preemptions >= 1, "the long prefill was preempted")
    check(long_req.generated == alone.generated
          and len(alone.generated) == size.long_new,
          f"preempted long's {size.long_new} tokens == uninterrupted run's "
          f"{alone.generated}")
    # random weights may greedy-decode one token over and over, so the
    # tokens alone can agree by chance: the first-token logits cannot
    check(np.array_equal(np.asarray(first_logits[0]), alone_logits),
          "preempted long's first-token logits bitwise == uninterrupted "
          "run's (same programs on the same shapes)")
    check(all(len(r.generated) == size.short_new for r in short_reqs),
          f"every short generated {size.short_new} tokens")
    check(stats["short_decode"] >= 1 and not stats["short_decode_inplace"],
          "shorts decoded on the dedicated decode engine")

    # the served path's own programs, replayed for one short: its logits
    # after prefill and after each paged decode step on its served tokens
    short = short_reqs[0]
    got = engine_logits(mc.backend._engine(0), short.tokens,
                        short.generated[:-1])
    return short, got


def tap_prefill_logits(eng: ReplicaEngine) -> dict:
    """Record, by request id, the prefill logits `eng` hands the backend:
    an observer on the served path that changes nothing it computes."""
    seen = {}
    served = eng.prefill_logits

    def tapped(st):
        seen[st.rid] = out = served(st)
        return out
    eng.prefill_logits = tapped
    return seen


def canary_logits(size: Size, params, toks: np.ndarray) -> np.ndarray:
    """Prefill logits of an engine whose weights are rounded to fp8-e4m3:
    a computation below the config's bf16, which the tolerance must fail."""
    params8 = jax.tree.map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype), params)
    return engine_logits(ReplicaEngine(size.cfg, params8, max_slots=1,
                                       max_len=size.max_len), toks, [])


def engine_logits(eng: ReplicaEngine, toks: np.ndarray, feed) -> np.ndarray:
    """Logits after prefill, then after each paged decode step fed `feed`."""
    st = eng.start_prefill(-1, jnp.asarray(toks[None]))
    done = False
    while not done:
        st, done = eng.prefill_quantum(st)
    out = [eng.prefill_logits(st)[0]]
    if feed:
        slot = eng.admit(-1, st)
        for t in feed:
            out.append(eng.decode_logits({slot: int(t)})[slot])
        eng.evict(slot)
    return np.asarray(jnp.stack(out), np.float32)


def reference_phase(size: Size, params, short: ServeRequest,
                    got: np.ndarray, canary: np.ndarray) -> None:
    """`models.model.forward` on float32 copies of the weights at highest
    matmul precision, over the short's prompt and served tokens."""
    cfg = dataclasses.replace(size.cfg, dtype="float32")
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    seq = np.concatenate([short.tokens,
                          np.asarray(short.generated[:-1], np.int32)])
    with jax.default_matmul_precision("highest"):
        ref, _ = mdl.forward(cfg, params32, {"tokens": jnp.asarray(seq[None])},
                             impl="ref")
    del params32
    S = short.tokens.shape[0]
    ref = np.asarray(ref[0, S - 1:], np.float32)
    rel, mx = logit_errors(got, ref)
    for i in range(len(got)):
        step = "prefill" if i == 0 else f"decode step {i}"
        print(f"logits vs float32 reference, {step}: relative L2 "
              f"{rel[i]:.6f} (tol {LOGIT_REL_TOL}), largest "
              f"{mx[i]:.6f} std (tol {LOGIT_MAX_TOL})")
    crel, cmx = logit_errors(canary, ref[:1])
    print(f"canary, fp8-e4m3 weights, prefill: relative L2 {crel[0]:.6f}, "
          f"largest {cmx[0]:.6f} std")
    check(np.isfinite(got).all(), "engine logits finite")
    check(bool((rel <= LOGIT_REL_TOL).all() and (mx <= LOGIT_MAX_TOL).all()),
          "engine logits within tolerance after prefill and every decode step")
    check(bool(crel[0] > LOGIT_REL_TOL),
          "the relative-L2 tolerance fails the fp8-e4m3 canary")


def one_chip(size: Size, seed: int) -> None:
    compiles = CompileCounter()
    cfg = size.cfg
    params = init_params(jax.random.PRNGKey(seed), cfg)
    n_params = mdl.param_count(params)
    print(f"config: {cfg.name}: d_model {cfg.d_model}, {cfg.num_heads} heads, "
          f"{cfg.num_kv_heads} KV heads, head_dim {cfg.head_dim}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab_size}, {cfg.num_layers} of "
          f"{CONFIG.num_layers} layers (one stage of a 4-stage pipeline), "
          f"{cfg.dtype}; {n_params} parameters; engines: {size.max_slots} "
          f"slots x {size.max_len} tokens; long threshold "
          f"{size.long_threshold} tokens")
    # each phase's engines (and the fp8 weights) are freed before the next
    # phase allocates: the 8 GB of float32 weights fit beside the bf16 ones
    # only once nothing else is resident
    short, got = serve_phase(size, params, np.random.default_rng(seed),
                             compiles)
    gc.collect()
    canary = canary_logits(size, params, short.tokens)
    gc.collect()
    reference_phase(size, params, short, got, canary)
    print(f"peak_bytes_in_use: {peak_bytes()}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def rel_l2(a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def four_chips(size: Size, seed: int) -> None:
    cfg = size.cfg
    check(jax.device_count() >= 4, f"4 devices ({jax.device_count()} found)")
    params = init_params(jax.random.PRNGKey(seed), cfg)
    toks = prompt(np.random.default_rng(seed), size.long_len, cfg)
    # a prefill target far below one replica's prefill time makes the
    # long claim every general replica: a degree-4 fast-SP gang
    mc = MiniCluster(cfg, params, n_engines=4, policy="pecsched",
                     max_len=size.max_len, max_slots=size.max_slots,
                     long_threshold=size.long_threshold,
                     layers_per_quantum=1, clock="measured",
                     target_prefill_s=1e-6)
    t0 = time.perf_counter()
    mc.backend.warmup([size.long_len], [0])
    mc.backend.warmup_gang([size.long_len], [4],
                           cluster_input_len=size.long_len)
    print(f"set-up: compile + warm-up {time.perf_counter() - t0:.3f} s")
    req = ServeRequest(rid=0, arrival=0.0, tokens=toks, max_new=size.long_new,
                       is_long=True)
    t0 = time.perf_counter()
    mc.submit(req)
    mc.run()
    stats = mc.backend.stats
    print(f"serving: {size.long_len}-token long, {stats['gang_prefills']} "
          f"gang prefill(s), {stats['sp_prefill_quanta']} SP quanta, "
          f"{stats['gang_scatters']} KV scatter(s); "
          f"{mc.backend.measured_s:.6f} s device-bound compute, "
          f"{time.perf_counter() - t0:.3f} s wall; measured s/layer by "
          f"degree {mc.backend.sp_per_layer_s()}")
    check(stats["gang_prefills"] == 1 and 4 in mc.backend.sp_timings,
          "the policy gang-scheduled the long prefill at degree 4")
    check(len(req.generated) == size.long_new,
          f"the gang-prefilled long generated {size.long_new} tokens")

    runner = mc.backend._runner_for(4)
    mesh_shape = (runner.mesh.shape[OUTER_AXIS], runner.mesh.shape[INNER_AXIS])
    check(mesh_shape == (2, 2), f"gang mesh is (2, 2): {mesh_shape}")
    plan = plan_for_gang(cfg, size.long_len, runner.mesh)
    gst = runner.start(1, toks, plan)
    done = False
    while not done:
        gst, done = runner.quantum(gst, 4)
    g_logits = np.asarray(runner.logits(gst)[0], np.float32)
    gk, gv = runner.gather_kv(gst)

    eng = mc.backend._engine(0)
    st = eng.start_prefill(2, jnp.asarray(toks[None]))
    while st.layer < cfg.num_layers:
        st, _ = eng.prefill_quantum(st)
    s_logits = np.asarray(eng.prefill_logits(st)[0], np.float32)
    sk = np.asarray(jnp.stack(st.kv_k, 0)[:, 0], np.float32)
    sv = np.asarray(jnp.stack(st.kv_v, 0)[:, 0], np.float32)
    lrel, krel, vrel = rel_l2(g_logits, s_logits), rel_l2(gk, sk), \
        rel_l2(gv, sv)
    print(f"gang ({plan.inner_impl} inner, ring outer) vs one chip: logits "
          f"relative L2 {lrel:.6f} (tol {GANG_REL_TOL}); KV relative "
          f"L2 K {krel:.6f} V {vrel:.6f} (tol {GANG_REL_TOL})")
    check(lrel <= GANG_REL_TOL, "gang logits match the one-chip run")
    check(max(krel, vrel) <= GANG_REL_TOL, "gang KV matches the one-chip KV")

    # the scattered KV decodes: one step from both KV copies, same token
    first = int(np.argmax(s_logits))
    home = mc.backend._engine(1)
    home.scatter_kv(1, jnp.asarray(gk), jnp.asarray(gv))
    hslot = home.bind_slot(1)
    g_dec = np.asarray(home.decode_logits({hslot: first})[hslot], np.float32)
    slot = eng.admit(2, st)
    s_dec = np.asarray(eng.decode_logits({slot: first})[slot], np.float32)
    drel = rel_l2(g_dec, s_dec)
    print(f"decode step from the scattered gang KV vs the one-chip KV: "
          f"relative L2 {drel:.6f} (tol {GANG_REL_TOL})")
    check(np.isfinite(g_dec).all() and drel <= GANG_REL_TOL,
          "the scattered gang KV decodes")
    print(f"peak_bytes_in_use (device 0): {peak_bytes()}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the gang-SP phase on a 2x2 mesh")
    ap.add_argument("--tiny", action="store_true",
                    help="tiny widths, any platform; the TPU check comes "
                         "last, so without a TPU the run still fails")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    info = device_info()
    print(f"device: {info['platform']} / {info['kind']} x {info['count']}; "
          f"jax {jax.__version__}; compile cache {enable_compile_cache()}")
    if not args.tiny:
        require_tpu(info)
    size = TINY if args.tiny else FULL
    (four_chips if args.chips == 4 else one_chip)(size, args.seed)
    require_tpu(info)
    print(json.dumps({"ok": True, "device": info}))


if __name__ == "__main__":
    main()
