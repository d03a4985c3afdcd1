"""Million-request / 1000-replica scale sweep (ROADMAP item 4: "an order
of magnitude on both axes").

Replays GENERATED traces — a chunked generator re-bases scenario chunks
onto a running rid/arrival offset, so a 1M-request replay never holds the
trace in memory — through the streaming-metrics simulator on fleet-scale
clusters, and records events/sec + peak RSS per (policy, shape) case.

Every case runs in its own subprocess so `resource.getrusage(RUSAGE_SELF)
.ru_maxrss` is that case's peak RSS, not the sweep's high-water mark.
Results land in ``benchmarks/artifacts/BENCH_scale.json`` (the BENCH
artifact family `ci_bench.py` uploads from).

    PYTHONPATH=src python -m benchmarks.scale_sweep                # full 1M sweep
    PYTHONPATH=src python -m benchmarks.scale_sweep \
        --shapes 20000x32 --policies fifo,pecsched                 # smoke
"""
from __future__ import annotations

import argparse
import json
import os
import resource
import subprocess
import sys
from pathlib import Path
from typing import Iterator, Tuple

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))

DEFAULT_POLICIES = "fifo,pecsched,pecsched/coord,sjf_pred"
DEFAULT_SHAPES = "20000x32,1000000x1000"
CHUNK = 20_000


def scaled_cluster(model: str, n_replicas: int):
    """The paper's §6.2 per-model setup, scaled to `n_replicas`: same TP,
    same ~1/8 dedicated-decode fraction, A100 nodes of 8 GPUs."""
    from repro.configs import get_config
    from repro.core import ClusterConfig, ExecutionModel
    from repro.core.workload import PAPER_SETUPS
    from repro.sp.planner import A100_40G

    setup = PAPER_SETUPS[model]
    tp = setup["tp"]
    gpus_per_node = 8
    n_nodes = max(1, (n_replicas * tp + gpus_per_node - 1) // gpus_per_node)
    cc = ClusterConfig(n_nodes=n_nodes, gpus_per_node=gpus_per_node, tp=tp,
                       gpu_mem_bytes=80e9, hw=A100_40G,
                       n_short_decode_replicas=max(
                           setup["n_decode"],
                           round(n_replicas * setup["n_decode"] / 32)))
    em = ExecutionModel(get_config(model), cc.replica_spec())
    return cc, em


def chunked_trace(scenario: str, n_requests: int, arrival_rps: float,
                  seed: int, chunk: int = CHUNK) -> Iterator:
    """Arrival-sorted request stream of `n_requests`, generated `chunk` at
    a time: each chunk's dense rids are shifted by a running offset and its
    arrivals re-based past the previous chunk's span, so the concatenation
    is one coherent trace that never exists in memory at once."""
    from repro.core import get_scenario

    t_off, rid_off, produced, k = 0.0, 0, 0, 0
    gap = 1.0 / max(arrival_rps, 1e-9)
    while produced < n_requests:
        n = min(chunk, n_requests - produced)
        reqs = get_scenario(scenario, n_requests=n, seed=seed + k,
                            arrival_rps=arrival_rps)
        reqs.sort(key=lambda r: r.arrival)
        span = reqs[-1].arrival if reqs else 0.0
        for r in reqs:
            r.rid += rid_off
            r.arrival += t_off
            yield r
        rid_off += n
        t_off += span + gap
        produced += n
        k += 1


def run_case(policy: str, scenario: str, n_requests: int, n_replicas: int,
             *, model: str = "mistral_7b", utilization: float = 0.65,
             seed: int = 0) -> dict:
    """One (policy, shape) replay: streaming metrics, generated trace.
    Returns the result record (including this process's peak RSS — callers
    wanting per-case isolation run this in a subprocess)."""
    from repro.core import Simulator, make_policy
    from repro.core.workload import calibrate_short_capacity

    cc, em = scaled_cluster(model, n_replicas)
    rps = calibrate_short_capacity(cc, em,
                                   n=max(1500, 2 * cc.n_replicas)) \
        * utilization
    p = make_policy(policy, cc, em).enable_streaming_metrics()
    sim = Simulator(p)
    s = sim.run(chunked_trace(scenario, n_requests, rps, seed))
    prof = sim.profile()
    return {
        "policy": policy,
        "scenario": scenario,
        "n_requests": n_requests,
        "n_replicas": cc.n_replicas,
        "events": prof["events"],
        "events_per_sec": round(prof["events_per_sec"], 1),
        "wall_s": round(sim.run_time, 3),
        "completed": s["short_completed"] + s["long_completed"],
        "peak_rss_mb": round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0, 1),
        "dispatch_elided": prof["dispatch_elided_quantum"]
        + prof["dispatch_elided_idle"],
    }


def _child(spec: str) -> None:
    kw = json.loads(spec)
    rec = run_case(kw["policy"], kw["scenario"], kw["n_requests"],
                   kw["n_replicas"], model=kw["model"],
                   utilization=kw["utilization"], seed=kw["seed"])
    print("RESULT " + json.dumps(rec))


def _spawn(kw: dict) -> dict:
    proc = subprocess.run(
        [sys.executable, str(Path(__file__).resolve()), "--run-one",
         json.dumps(kw)],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})   # never the accelerator
    if proc.returncode != 0:
        raise RuntimeError(
            f"scale case {kw} failed:\n{proc.stdout}\n{proc.stderr}")
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"scale case {kw}: no RESULT line in\n{proc.stdout}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--policies", default=DEFAULT_POLICIES)
    ap.add_argument("--shapes", default=DEFAULT_SHAPES,
                    help="comma-separated n_requests x n_replicas shapes, "
                         "e.g. 20000x32,1000000x1000")
    ap.add_argument("--scenario", default="azure_default")
    ap.add_argument("--model", default="mistral_7b")
    ap.add_argument("--utilization", type=float, default=0.65)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(Path(__file__).parent / "artifacts"
                                         / "BENCH_scale.json"))
    ap.add_argument("--run-one", metavar="JSON",
                    help="(internal) run a single case in-process and print "
                         "its RESULT line; used for per-case RSS isolation")
    args = ap.parse_args()
    if args.run_one:
        _child(args.run_one)
        return

    shapes = []
    for s in args.shapes.split(","):
        n, r = s.lower().split("x")
        shapes.append((int(n), int(r)))
    policies = args.policies.split(",")

    print(f"{'case':42s} {'events':>10s} {'wall_s':>8s} "
          f"{'events/sec':>11s} {'rss_mb':>8s} {'done':>9s}")
    cases = {}
    for n_requests, n_replicas in shapes:
        for pol in policies:
            kw = {"policy": pol, "scenario": args.scenario,
                  "n_requests": n_requests, "n_replicas": n_replicas,
                  "model": args.model, "utilization": args.utilization,
                  "seed": args.seed}
            rec = _spawn(kw)
            name = (f"{pol.replace('/', '_')}_{args.scenario}"
                    f"_{n_requests}x{n_replicas}")
            cases[name] = rec
            print(f"{name:42s} {rec['events']:>10d} {rec['wall_s']:>8.2f} "
                  f"{rec['events_per_sec']:>11,.0f} "
                  f"{rec['peak_rss_mb']:>8.1f} {rec['completed']:>9d}")

    report = {"schema": 1, "model": args.model, "scenario": args.scenario,
              "utilization": args.utilization, "cases": cases}
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(report, indent=1))
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
