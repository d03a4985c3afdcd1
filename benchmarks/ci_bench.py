"""CI bench smoke: the repo's per-PR performance trajectory, as one JSON.

Runs a reduced configuration of the standing benchmarks —

  * `simulator_scale`-style trace replays (events/sec of the slotted-heap
    event loop under fifo/pecsched/pecsched-coord/sjf_pred),
  * a reduced `scale_sweep` case (100K requests on a 256-replica fleet,
    generated trace + streaming metrics — the memory-flat path), and
  * `engine_overhead` (real-JAX context-switch / suspension-state /
    KV-migration costs, §5.1/§5.2)

— writes every number to ``BENCH_pr.json`` (uploaded as a CI artifact, so
the trajectory is diffable across PRs), and GATES on the simulator cases:

  * throughput: events/sec must stay within ``MAX_REGRESSION`` of the
    checked-in ``bench_baseline.json`` floor, and
  * memory: per-case peak RSS (``resource.getrusage`` of the case's own
    subprocess) must stay within ``MAX_RSS_REGRESSION`` of its baseline.

Every simulator case runs ``--repeats`` times in a fresh subprocess each
(best-of-N throughput, min-of-N RSS): the event loop is pure Python and
deterministic, so the best repeat is the measurement and the spread is
host noise (CI runners and shared dev boxes both steal CPU in bursts).

Engine timings are recorded but not gated — wall-clock JAX compute on
shared CI runners is too noisy for a hard bound.

The baseline values are deliberately conservative (local measurement with
a haircut, see `--update-baseline`) so that runner-speed variance does not
trip the gate while an algorithmic regression (the event loop going
quadratic, say) still does.

    PYTHONPATH=src python benchmarks/ci_bench.py
    PYTHONPATH=src python benchmarks/ci_bench.py --update-baseline
"""
from __future__ import annotations

import argparse
import json
import os
import resource
import subprocess
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))

BASELINE_PATH = Path(__file__).parent / "bench_baseline.json"
#: fail if simulator replay throughput drops >30% below the baseline floor
MAX_REGRESSION = 0.30
#: fail if a case's peak RSS grows >30% above the baseline
MAX_RSS_REGRESSION = 0.30
#: haircut applied when recording a new throughput baseline, absorbing
#: machine-speed variance between the recording host and CI runners
BASELINE_HAIRCUT = 0.7
#: headroom applied when recording a new RSS baseline (allocator and
#: interpreter-version variance, same idea in the other direction)
RSS_HEADROOM = 1.15

SIM_CASES = (
    # (name, policy, scenario, n_requests)
    ("fifo_azure_20k", "fifo", "azure_default", 20_000),
    ("pecsched_azure_20k", "pecsched", "azure_default", 20_000),
    ("pecsched_coord_bursty_10k", "pecsched/coord", "bursty", 10_000),
    # predicted-SJF under bursty arrivals: per-request decode-lane rounds
    # (+ misprediction evictions) make this the event-loop-heaviest policy;
    # gated so the lane machinery staying O(log n) is a checked invariant
    ("sjf_pred_bursty_10k", "sjf_pred", "bursty", 10_000),
    # prefix-cache routing on multi-turn chat: every dispatch adds residency
    # lookups/records and per-request prefill discounts on top of the base
    # PecSched path — gated so the cache machinery stays O(1) per decision
    ("pecsched_cache_multiturn_10k", "pecsched/cache", "chat_multiturn",
     10_000),
    # plan-ahead SLO scheduling on the tiered bursty mix: every arrival
    # dirties the plan and every dispatch may replan (sort + fluid placement
    # of the whole short queue) — gated so planning stays O(queue log queue)
    # amortized, not O(n) replans of an ever-growing backlog
    ("pecsched_slo_tiered_10k", "pecsched/slo", "slo_tiered", 10_000),
)

#: reduced scale_sweep case: generated trace + streaming metrics on a
#: 256-replica fleet — gates BOTH that fleet-scale dispatch stays O(1) per
#: event and that the memory-flat replay path stays memory-flat
SCALE_CASES = (
    # (name, policy, scenario, n_requests, n_replicas)
    ("pecsched_scale_100k_256r", "pecsched", "azure_default", 100_000, 256),
)


# ---------------------------------------------------------------------------
# child mode: one case, one process → ru_maxrss is that case's peak RSS
# ---------------------------------------------------------------------------
def _child(spec: str) -> None:
    kw = json.loads(spec)
    import copy

    from repro.core import Simulator, get_scenario, make_policy, paper_cluster
    from repro.core.workload import calibrate_short_capacity

    if kw.get("n_replicas"):                    # scale case: streaming path
        from scale_sweep import run_case
        rec = run_case(kw["policy"], kw["scenario"], kw["n_requests"],
                       kw["n_replicas"])
        rec = {"events_per_sec": rec["events_per_sec"],
               "events": rec["events"], "wall_s": rec["wall_s"],
               "completed": rec["completed"],
               "peak_rss_mb": rec["peak_rss_mb"]}
    else:
        cc, em = paper_cluster("mistral_7b")
        rps = calibrate_short_capacity(cc, em) * 0.65
        reqs = get_scenario(kw["scenario"], n_requests=kw["n_requests"],
                            seed=0, arrival_rps=rps)
        p = make_policy(kw["policy"], cc, em)
        sim = Simulator(p)
        s = sim.run(copy.deepcopy(reqs))
        prof = sim.profile()
        rec = {"events_per_sec": round(prof["events_per_sec"], 1),
               "events": prof["events"], "wall_s": round(sim.run_time, 3),
               "completed": s["short_completed"] + s["long_completed"],
               "peak_rss_mb": round(
                   resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
                   / 1024.0, 1)}
    print("RESULT " + json.dumps(rec))


def _spawn(kw: dict) -> dict:
    proc = subprocess.run(
        [sys.executable, str(Path(__file__).resolve()), "--run-one",
         json.dumps(kw)],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})   # never the accelerator
    if proc.returncode != 0:
        raise RuntimeError(
            f"bench case {kw} failed:\n{proc.stdout}\n{proc.stderr}")
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"bench case {kw}: no RESULT line in\n{proc.stdout}")


def run_sim_cases(repeats: int) -> dict:
    out = {}
    specs = [(name, {"policy": pol, "scenario": scen, "n_requests": n})
             for name, pol, scen, n in SIM_CASES]
    specs += [(name, {"policy": pol, "scenario": scen, "n_requests": n,
                      "n_replicas": r})
              for name, pol, scen, n, r in SCALE_CASES]
    for name, kw in specs:
        runs = [_spawn(kw) for _ in range(repeats)]
        best = max(runs, key=lambda r: r["events_per_sec"])
        rec = dict(best)
        rec["peak_rss_mb"] = min(r["peak_rss_mb"] for r in runs)
        rec["repeats"] = repeats
        out[name] = rec
        print(f"[sim]    {name:28s} {rec['events_per_sec']:>12,.0f} ev/s "
              f"(best of {repeats}; {rec['events']} events, "
              f"{rec['wall_s']:.2f}s, rss {rec['peak_rss_mb']:.0f} MB)")
    return out


def run_engine_case() -> dict:
    sys.path.insert(0, str(Path(__file__).parent))
    from engine_overhead import run as engine_run
    t0 = time.perf_counter()
    res = engine_run(seq_long=64, layers=4)
    res = {k: round(float(v), 6) for k, v in res.items()}
    res["wall_s"] = round(time.perf_counter() - t0, 3)
    print(f"[engine] context_switch={res['context_switch_ms']:.2f}ms "
          f"suspend_state={res['suspend_state_vs_kv']*100:.1f}%ofKV "
          f"kv_migration={res['kv_migration_ms']:.2f}ms")
    return res


def gate(sim_results: dict, baseline: dict) -> list:
    failures = []
    ungated = set(sim_results) - set(baseline.get("simulator", {}))
    for name in sorted(ungated):
        failures.append(f"{name}: measured but has no baseline floor — "
                        f"run ci_bench.py --update-baseline and commit "
                        f"{BASELINE_PATH.name}")
    for name, base in baseline.get("simulator", {}).items():
        cur = sim_results.get(name)
        if cur is None:
            failures.append(f"{name}: in baseline but not measured")
            continue
        floor = base["events_per_sec"] * (1.0 - MAX_REGRESSION)
        ok = cur["events_per_sec"] >= floor
        rss_cap = None
        rss_ok = True
        if "peak_rss_mb" in base:
            rss_cap = base["peak_rss_mb"] * (1.0 + MAX_RSS_REGRESSION)
            rss_ok = cur["peak_rss_mb"] <= rss_cap
        status = "OK" if ok and rss_ok else "REGRESSED"
        cap_txt = f", rss {cur['peak_rss_mb']:,.0f} MB vs cap " \
                  f"{rss_cap:,.0f}" if rss_cap is not None else ""
        print(f"[gate]   {name:28s} {cur['events_per_sec']:>12,.0f} ev/s "
              f"vs floor {floor:,.0f}{cap_txt} ({status})")
        if not ok:
            failures.append(
                f"{name}: {cur['events_per_sec']:,.0f} ev/s is "
                f">{MAX_REGRESSION:.0%} below baseline "
                f"{base['events_per_sec']:,.0f}")
        if not rss_ok:
            failures.append(
                f"{name}: peak RSS {cur['peak_rss_mb']:,.0f} MB is "
                f">{MAX_RSS_REGRESSION:.0%} above baseline "
                f"{base['peak_rss_mb']:,.0f} MB")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=str(Path(__file__).parent / "artifacts"
                                         / "BENCH_pr.json"))
    ap.add_argument("--repeats", type=int, default=3,
                    help="subprocess repeats per case (best-of-N gating)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="record current throughput (with the haircut) and "
                         "peak RSS (with headroom) as the new checked-in "
                         "baseline instead of gating")
    ap.add_argument("--run-one", metavar="JSON",
                    help="(internal) run one case in-process and print its "
                         "RESULT line; used for per-case RSS isolation")
    args = ap.parse_args()
    if args.run_one:
        _child(args.run_one)
        return

    sim_results = run_sim_cases(max(1, args.repeats))
    engine_results = run_engine_case()

    report = {
        "schema": 2,
        "simulator": sim_results,
        "engine": engine_results,
        "gate": {"max_regression": MAX_REGRESSION,
                 "max_rss_regression": MAX_RSS_REGRESSION,
                 "baseline": str(BASELINE_PATH.name)},
    }
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(report, indent=1))
    print(f"wrote {out}")

    if args.update_baseline:
        baseline = {
            "note": f"simulator events/sec floors = measured * "
                    f"{BASELINE_HAIRCUT} (machine-variance haircut); "
                    f"peak_rss_mb = measured * {RSS_HEADROOM} (allocator "
                    f"headroom).  The bench-smoke gate fails below "
                    f"(1 - {MAX_REGRESSION}) * the throughput floor or "
                    f"above (1 + {MAX_RSS_REGRESSION}) * the RSS value",
            "simulator": {
                name: {"events_per_sec":
                       round(r["events_per_sec"] * BASELINE_HAIRCUT, 1),
                       "peak_rss_mb":
                       round(r["peak_rss_mb"] * RSS_HEADROOM, 1)}
                for name, r in sim_results.items()},
        }
        BASELINE_PATH.write_text(json.dumps(baseline, indent=1))
        print(f"updated {BASELINE_PATH}")
        return

    if not BASELINE_PATH.exists():
        print(f"ERROR: no baseline at {BASELINE_PATH}; run with "
              f"--update-baseline to record one", file=sys.stderr)
        sys.exit(2)
    failures = gate(sim_results, json.loads(BASELINE_PATH.read_text()))
    if failures:
        for f in failures:
            print(f"BENCH REGRESSION: {f}", file=sys.stderr)
        sys.exit(1)
    print("BENCH OK")


if __name__ == "__main__":
    main()
