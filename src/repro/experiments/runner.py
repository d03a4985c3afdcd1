"""Sweep runner: execute ExperimentSpecs on either backend, with an
on-disk JSON result cache and optional process-parallel execution.

One spec -> one `metrics.summarize` dict (plus runner bookkeeping:
`wall_s`, `sched_time_s`, `n_dispatches`, `_spec`).  Results are cached
per spec under ``<cache_dir>/<spec.key()>.<spec_hash>.json``; the hash
covers every spec field plus `spec.SCHEMA_VERSION`, so CI smoke reruns are
incremental — only new or changed cells execute, stale files simply stop
matching and are ignored.

Backends:

* ``backend="sim"``: the model's paper cluster (`workload.paper_cluster`)
  replayed analytically.  Arrival rate = `utilization` x the calibrated
  short-only capacity (cached per model), except for pinned scenarios
  (`spec.PINNED_SCENARIOS`) which define their own timeline.  Sim specs
  are pure functions of the spec -> safe to fan out across processes
  (``workers > 1``; spawn context, PYTHONPATH propagated).

* ``backend="engine"``: a 2-layer reduced build of the spec's model on a
  small real-JAX cluster (2 general + 1 dedicated-decode replica, the
  cross-backend test topology).  Engines and their jit caches are reused
  across specs in-process (reset between runs), so a 9-policy sweep pays
  compilation once.  Engine specs always run serially in-process.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from repro.core import (ClusterConfig, Simulator, get_scenario, make_policy)
from repro.core.costmodel import ExecutionModel
from repro.core.fleet import FleetConfig, FleetController, reclamation_wave
from repro.core.workload import calibrate_short_capacity, paper_cluster
from repro.experiments.spec import (PINNED_SCENARIOS, SCHEMA_VERSION,
                                    ExperimentSpec)

# in-process caches: capacity calibration per model, engine stack per
# (model, clock) — both deterministic, both expensive to rebuild
_CAPACITY: Dict[str, float] = {}
_ENGINE_STACKS: Dict[Tuple[str, str], Tuple] = {}

ENGINE_LAYERS = 2
ENGINE_MAX_LEN = 128
#: engine-scale prefill latency target: tight enough that a 300K-token long
#: needs an SP group (replicas_needed >= 2) on the reduced model, so the
#: engine cells exercise the gang-scheduling path (multi-replica claim +
#: fast-SP pricing; real shard_map gangs whenever the host has the devices)
ENGINE_TARGET_PREFILL_S = 0.5


def short_capacity(model: str) -> float:
    cap = _CAPACITY.get(model)
    if cap is None:
        cc, em = paper_cluster(model)
        cap = _CAPACITY[model] = calibrate_short_capacity(cc, em)
    return cap


def engine_cluster(cfg) -> Tuple[ClusterConfig, ExecutionModel]:
    """The small real-engine topology every engine spec runs on: 2 general
    replicas + 1 dedicated short-decode replica (tests/test_backends.py)."""
    cc = ClusterConfig(n_nodes=1, gpus_per_node=3, tp=1,
                       n_short_decode_replicas=1, max_decode_concurrency=8)
    return cc, ExecutionModel(cfg, cc.replica_spec(),
                              target_prefill_s=ENGINE_TARGET_PREFILL_S)


def engine_stack(model: str, clock: str):
    """(cfg, cluster, em, backend) for engine specs; cached in-process."""
    key = (model, clock)
    stack = _ENGINE_STACKS.get(key)
    if stack is None:
        import jax
        from repro.configs import get_config, reduced_config
        from repro.models import init_params
        from repro.serving.backend import EngineBackend
        cfg = dataclasses.replace(
            reduced_config(get_config(model), layers=ENGINE_LAYERS),
            dtype="float32", sliding_window=0)
        params = init_params(jax.random.PRNGKey(0), cfg)
        cc, em = engine_cluster(cfg)
        backend = EngineBackend(cfg, params, max_len=ENGINE_MAX_LEN,
                                layers_per_quantum=1, clock=clock)
        stack = _ENGINE_STACKS[key] = (cfg, cc, em, backend)
    return stack


# ---------------------------------------------------------------------------
# workload + execution for one spec
# ---------------------------------------------------------------------------
def build_requests(spec: ExperimentSpec, cc, em) -> List:
    # fleet_* keys configure the churn layer (fleet_controller below), not
    # the trace builder
    overrides = {k: v for k, v in spec.overrides
                 if not k.startswith("fleet_")}
    if spec.scenario not in PINNED_SCENARIOS and "arrival_rps" not in overrides:
        if spec.backend == "sim":
            cap = short_capacity(spec.model)
        else:
            cap = calibrate_short_capacity(cc, em)
        overrides["arrival_rps"] = cap * spec.utilization
    return get_scenario(spec.scenario, n_requests=spec.n_requests,
                        seed=spec.seed, **overrides)


def fleet_controller(spec: ExperimentSpec, cc,
                     reqs: List) -> Optional[FleetController]:
    """Churn layer for one spec: the `churn` scenario gets a default 20%
    reclamation wave at the trace's first arrival quartile; `fleet_*`
    overrides (prefix stripped) pin or extend any `FleetConfig` field and
    activate the layer on any scenario.  Everything is a deterministic
    function of the spec + built trace, so cached results stay valid."""
    fo = {k[len("fleet_"):]: v for k, v in spec.overrides
          if k.startswith("fleet_")}
    if spec.scenario != "churn" and not fo:
        return None
    arrivals = sorted(r.arrival for r in reqs)
    span = arrivals[-1] - arrivals[0] if arrivals else 0.0
    wave_at = fo.pop("wave_at", None)
    if wave_at is None:
        wave_at = (arrivals[0] + 0.25 * span) if arrivals else 0.0
    wave_frac = fo.pop("wave_frac", 0.20)
    reclamations = fo.pop("reclamations", None)
    if reclamations is None:
        reclamations = reclamation_wave(float(wave_at), float(wave_frac),
                                        cc.n_replicas)
    else:
        reclamations = tuple((float(t), int(rid)) for t, rid in reclamations)
    # default notice window: 1% of the trace span — a real grace period on
    # both the seconds-scale sim timeline and the ms-scale engine timeline
    notice_s = float(fo.pop("notice_s", 0.01 * span))
    return FleetController(FleetConfig(reclamations=reclamations,
                                       notice_s=notice_s, **fo))


def run_spec(spec: ExperimentSpec) -> Dict:
    """Execute one spec to completion and return its summary dict."""
    if spec.backend == "sim":
        cc, em = paper_cluster(spec.model)
        backend = None
    else:
        _, cc, em, backend = engine_stack(spec.model, spec.engine_clock)
        backend.reset()
    reqs = build_requests(spec, cc, em)
    policy = make_policy(spec.policy, cc, em)
    fleet = fleet_controller(spec, cc, reqs)
    sim = Simulator(policy, fleet=fleet) if backend is None \
        else Simulator(policy, backend=backend, fleet=fleet)
    t0 = time.perf_counter()
    summary = sim.run(reqs)
    summary["wall_s"] = time.perf_counter() - t0
    summary["sched_time_s"] = sim.sched_time
    summary["n_dispatches"] = sim.n_dispatches
    # JSON-normalized (tuples -> lists) so a live summary compares equal to
    # its cache-file round trip
    summary["_spec"] = json.loads(json.dumps(spec.to_dict()))
    return summary


def _run_spec_for_pool(spec_dict: Dict) -> Dict:
    return run_spec(ExperimentSpec.from_dict(spec_dict))


def _cpu_only_worker() -> None:
    """Pool initializer: a simulator worker never claims the accelerator,
    which belongs to one process (the parent may hold it)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")


# ---------------------------------------------------------------------------
# sweep with on-disk cache
# ---------------------------------------------------------------------------
def _cache_path(cache_dir: Path, spec: ExperimentSpec) -> Path:
    return cache_dir / f"{spec.key()}.{spec.spec_hash()}.json"


def _cache_load(cache_dir: Optional[Path], spec: ExperimentSpec) -> Optional[Dict]:
    if cache_dir is None:
        return None
    path = _cache_path(cache_dir, spec)
    if not path.exists():
        return None
    try:
        blob = json.loads(path.read_text())
    except (json.JSONDecodeError, OSError):
        return None
    if blob.get("schema") != SCHEMA_VERSION or \
            blob.get("hash") != spec.spec_hash():
        return None
    return blob["summary"]


def _cache_store(cache_dir: Optional[Path], spec: ExperimentSpec,
                 summary: Dict) -> None:
    if cache_dir is None:
        return
    cache_dir.mkdir(parents=True, exist_ok=True)
    _cache_path(cache_dir, spec).write_text(json.dumps(
        {"schema": SCHEMA_VERSION, "hash": spec.spec_hash(),
         "spec": spec.to_dict(), "summary": summary},
        indent=1, default=float))


def run_sweep(specs: Sequence[ExperimentSpec], *,
              cache_dir: Optional[os.PathLike] = None,
              workers: int = 1, force: bool = False
              ) -> Dict[ExperimentSpec, Dict]:
    """Run every spec (cache-aware) and return {spec: summary}.

    ``workers > 1`` fans *sim* specs out over a spawn-context process pool;
    engine specs always run serially in this process (live JAX engines are
    neither picklable nor worth re-compiling per worker).
    """
    cache = Path(cache_dir) if cache_dir is not None else None
    results: Dict[ExperimentSpec, Dict] = {}
    pending: List[ExperimentSpec] = []
    for spec in specs:
        hit = None if force else _cache_load(cache, spec)
        if hit is not None:
            results[spec] = hit
        else:
            pending.append(spec)

    par = [s for s in pending if s.backend == "sim"] if workers > 1 else []
    serial = [s for s in pending if s not in par]

    if par:
        import multiprocessing
        from concurrent.futures import ProcessPoolExecutor
        # spawn (not fork): JAX is loaded in this process and forked XLA
        # thread state can deadlock.  Spawned children need repro on their
        # path even when the parent got it from conftest, so propagate it.
        src = str(Path(__file__).resolve().parents[2])
        env_path = os.environ.get("PYTHONPATH", "")
        if src not in env_path.split(os.pathsep):
            os.environ["PYTHONPATH"] = (src + os.pathsep + env_path
                                        if env_path else src)
        ctx = multiprocessing.get_context("spawn")
        with ProcessPoolExecutor(max_workers=workers, mp_context=ctx,
                                 initializer=_cpu_only_worker) as ex:
            for spec, summary in zip(
                    par, ex.map(_run_spec_for_pool,
                                [s.to_dict() for s in par])):
                results[spec] = summary
                _cache_store(cache, spec, summary)
    for spec in serial:
        summary = run_spec(spec)
        results[spec] = summary
        _cache_store(cache, spec, summary)
    return results


def by_policy(results: Dict[ExperimentSpec, Dict]
              ) -> Dict[Tuple[str, str, str, int], Dict[str, Dict]]:
    """Regroup sweep results as {(backend, model, scenario, seed):
    {policy: summary}} — the per-cell shape the claims registry evaluates
    against.  Two specs that differ only in a dimension this key does NOT
    carry (n_requests, utilization, overrides, engine_clock) would silently
    overwrite each other's policy entry, so that collision is an error:
    evaluate such grids cell by cell instead."""
    out: Dict[Tuple[str, str, str, int], Dict[str, Dict]] = {}
    for spec, summary in results.items():
        cell = out.setdefault(
            (spec.backend, spec.model, spec.scenario, spec.seed), {})
        if spec.policy in cell:
            raise ValueError(
                f"ambiguous cell {(spec.backend, spec.model, spec.scenario, spec.seed)}: "
                f"policy {spec.policy!r} appears with multiple "
                f"n_requests/utilization/override variants")
        cell[spec.policy] = summary
    return out
