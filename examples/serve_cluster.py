"""End-to-end serving driver: ANY scheduling policy x ANY workload scenario
on a real-execution mini cluster.

The scheduling brain is the same `make_policy` stack the analytic simulator
runs (all ten names: fifo, fifo_noshort, reservation, priority, pecsched,
its /PE /Dis /CoL /FSP ablations and the adaptive-coordination
pecsched/coord); execution is real JAX compute on
`ReplicaEngine`s via the EngineBackend — layer-granular preemptible prefill,
KV migration to the dedicated decode engine, slot-chunked decode.  Virtual
time advances by measured compute (--clock measured, default) or by the
cost-model estimate (--clock analytic, the cross-backend parity mode).

    PYTHONPATH=src python examples/serve_cluster.py                  # compare
    PYTHONPATH=src python examples/serve_cluster.py --policy pecsched \
        --scenario bursty --smoke                                    # CI smoke
    PYTHONPATH=src python examples/serve_cluster.py --policy all \
        --scenario heavy_tail --n 32 --compare-sim

Scenario traces carry cluster-scale token counts; the backend maps them to
engine-sized prompts (log-scaled, bucketed) so every `get_scenario` workload
runs end-to-end on engines of the reduced model, on whatever device JAX
offers (`chip_smoke.py` serves the published widths on a TPU).

Long requests that the policy schedules across multiple replicas with fast
SP are GANG-scheduled: the replicas map onto a host device mesh and prefill
runs the real shard_map ring/a2a/allgather kernels (sp/gang.py), so this
driver forces a multi-device host platform by default (override by setting
XLA_FLAGS yourself).  --sp-degree caps the gang size, --prefill-target
controls how eagerly longs claim SP groups.
"""
import argparse
import copy
import dataclasses
import os
import time

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, reduced_config
from repro.core import (POLICY_NAMES, ClusterConfig, ExecutionModel,
                        Simulator, get_scenario, list_scenarios, make_policy)
from repro.core.request import Request
from repro.models import init_params
from repro.serving.backend import EngineBackend


def calibrate_rps(backend: EngineBackend, n_general: int,
                  utilization: float) -> float:
    """Measure one short prefill+decode and size the arrival rate so the
    general engines run at `utilization` x their short-service capacity
    (the engine-world analogue of workload.calibrate_short_capacity)."""
    eng = backend._engine(0)
    dt = 0.0
    for i, measure in ((-1, False), (-2, True)):    # first pass pays the jits
        warm = Request(rid=i, arrival=0.0, input_len=1000, output_len=4)
        d = backend._complete_prefill(eng, warm)
        d += backend._decode_batch(eng, [warm])
        if measure:
            dt = d
    backend.reset()
    return utilization * n_general / max(dt, 1e-6)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--policy", default="pecsched,fifo",
                    help="comma-separated make_policy names, or 'all'")
    ap.add_argument("--scenario", default="azure_default")
    ap.add_argument("--list-scenarios", action="store_true")
    ap.add_argument("--n", type=int, default=24)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engines", type=int, default=2,
                    help="general engines (one more is added as the "
                         "PecSched decode pool / extra baseline capacity)")
    ap.add_argument("--clock", choices=("measured", "analytic"),
                    default="measured")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=4)
    ap.add_argument("--utilization", type=float, default=1.2,
                    help="arrival rate as a fraction of measured short "
                         "capacity (>1 forces queueing/preemption)")
    ap.add_argument("--sp-degree", type=int, default=0,
                    help="cap on the gang-SP degree for long prefills "
                         "(0 = host device count; 1 = disable gangs)")
    ap.add_argument("--prefill-target", type=float, default=0.5,
                    help="prefill latency target (s) driving how many "
                         "replicas a long claims — tight targets form SP "
                         "gangs, the paper's §5.3 regime")
    ap.add_argument("--coordination", choices=("static", "adaptive"),
                    default="static",
                    help="adaptive swaps pecsched for pecsched/coord: the "
                         "prefill/decode split is re-evaluated at dispatch "
                         "time and replica roles flip at safe points "
                         "(§5.2 coordination); prints the role timeline")
    ap.add_argument("--trace-csv", default=None,
                    help="path for --scenario csv")
    ap.add_argument("--compare-sim", action="store_true",
                    help="also replay the trace through the analytic "
                         "SimBackend and print both")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI configuration (overrides --n)")
    args = ap.parse_args()

    if args.list_scenarios:
        for name, desc in list_scenarios().items():
            print(f"{name:15s} {desc}")
        return
    if args.smoke:
        args.n = min(args.n, 10)
    policies = POLICY_NAMES if args.policy == "all" \
        else tuple(args.policy.split(","))
    if args.coordination == "adaptive":
        # swap the static split for the coordinator; dedupe in case the
        # list already named pecsched/coord (e.g. --policy all)
        policies = tuple(dict.fromkeys(
            "pecsched/coord" if p == "pecsched" else p for p in policies))

    enable_compile_cache()
    cfg = dataclasses.replace(
        reduced_config(get_config("mistral_7b"), layers=args.layers),
        dtype="float32", sliding_window=0)
    params = init_params(jax.random.PRNGKey(0), cfg)
    # one extra replica: PecSched dedicates it to decode, the baselines get
    # it back as general capacity — total engine count is equal either way
    cc = ClusterConfig(n_nodes=1, gpus_per_node=args.engines + 1, tp=1,
                       n_short_decode_replicas=1, max_decode_concurrency=8)
    em = ExecutionModel(cfg, cc.replica_spec(),
                        target_prefill_s=args.prefill_target)
    backend = EngineBackend(cfg, params, max_len=args.max_len,
                            layers_per_quantum=1, clock=args.clock,
                            max_new_cap=args.max_new, seed=args.seed,
                            enable_sp=args.sp_degree != 1,
                            sp_degree_cap=max(args.sp_degree, 0))

    rps = calibrate_rps(backend, args.engines, args.utilization)
    kw = {"path": args.trace_csv} if args.scenario == "csv" else {}
    reqs = get_scenario(args.scenario, n_requests=args.n, seed=args.seed,
                        arrival_rps=rps, **kw)
    n_long = sum(r.is_long for r in reqs)
    if not args.smoke:
        # pre-compile every prompt shape on every engine (and the gang-SP
        # runners for the long prompts) so measured time is steady-state
        # compute, not first-policy compilation
        backend.warmup({backend.prompt_len(r) for r in reqs},
                       range(args.engines + 1))
        long_lens = {backend.prompt_len(r) for r in reqs if r.is_long}
        if long_lens:
            backend.warmup_gang(
                long_lens,
                {min(em.replicas_needed(r.input_len), args.engines)
                 for r in reqs if r.is_long})
    print(f"mini cluster: {args.engines}+1 engines, model {cfg.name}, "
          f"scenario {args.scenario!r}: {len(reqs)} requests ({n_long} long) "
          f"at {rps:.0f} rps, clock={args.clock}")
    hdr = (f"{'policy':14s} {'done':>7s} {'qd_mean':>9s} {'qd_p99':>9s} "
           f"{'longJCT':>9s} {'preempt':>7s} {'starved':>7s} "
           f"{'compute':>8s} {'wall':>6s}")
    print(hdr)
    for pol_name in policies:
        backend.reset()
        pol = make_policy(pol_name, cc, em)
        t0 = time.perf_counter()
        s = Simulator(pol, backend=backend).run(copy.deepcopy(reqs))
        wall = time.perf_counter() - t0
        def ms(v):
            return (v if v is not None else float("nan")) * 1e3
        gangs = backend.stats["gang_prefills"]
        gang_note = (f"  [gang-SP: {gangs} prefills, "
                     f"{backend.stats['sp_prefill_quanta']} quanta, "
                     f"{backend.stats['gang_scatters']} scatters]"
                     if gangs else "")
        print(f"{pol_name:14s} {s['short_completed']:4d}+{s['long_completed']:d}L "
              f"{ms(s['short_qd_mean']):8.1f}m "
              f"{ms(s['short_qd_pct']['99']):8.1f}m "
              f"{ms(s['long_jct_mean']):8.1f}m "
              f"{s['preemptions']:7d} {s['long_starved_frac']:7.2f} "
              f"{backend.measured_s:7.2f}s {wall:5.1f}s{gang_note}")
        ps = getattr(pol, "prefix_stats", None)
        if ps and ps["lookups"]:
            ks = backend.prefix_cache_stats()
            print(f"  prefix-cache: routed {ps['lookups']} lookups, "
                  f"{ps['hits']} hits ({ps['hits'] / ps['lookups']:.0%}), "
                  f"{ps['hit_tokens']:,} tokens | engine pools: "
                  f"{ks.get('lookups', 0)} lookups, {ks.get('hits', 0)} "
                  f"hits, {ks.get('blocks_shared', 0)} blocks shared, "
                  f"{ks.get('cow_forks', 0)} COW forks")
        if pol.role_log:
            shown = ", ".join(f"t={t*1e3:.2f}ms r{rid} {old}->{new}"
                              for t, rid, old, new in pol.role_log[:6])
            more = f" (+{len(pol.role_log) - 6} more)" \
                if len(pol.role_log) > 6 else ""
            occ = ", ".join(f"{role}={frac:.1%}"
                            for role, frac in s["role_occupancy"].items())
            print(f"  role timeline: {shown}{more}")
            print(f"  role occupancy: {occ}  "
                  f"[{s['role_flips']} flips, engine-vetted: "
                  f"{backend.stats['role_flips']}]")
        if args.compare_sim:
            ps = make_policy(pol_name, cc, em)
            ss = Simulator(ps).run(copy.deepcopy(reqs))
            print(f"  {'(sim)':12s} {ss['short_completed']:4d}+"
                  f"{ss['long_completed']:d}L "
                  f"{ms(ss['short_qd_mean']):8.1f}m "
                  f"{ms(ss['short_qd_pct']['99']):8.1f}m "
                  f"{ms(ss['long_jct_mean']):8.1f}m "
                  f"{ss['preemptions']:7d} {ss['long_starved_frac']:7.2f}")
    timings = backend.sp_per_layer_s()
    if len(timings) > 1:
        curve = ", ".join(f"deg{d}: {v * 1e3:.2f}ms/layer"
                          for d, v in timings.items())
        print(f"measured SP calibration ({curve}) — feed into the analytic "
              f"model via backend.calibrate_costmodel(em)")
    if args.smoke:
        print("SMOKE OK")
    else:
        print("\nexpected: pecsched cuts short queueing delay vs fifo; long "
              "JCT rises only modestly (the paper's headline trade-off)")


if __name__ == "__main__":
    main()
