"""Drive one measured window through the served path.

`make_policy(policy)` -> `Simulator` -> `EngineBackend` -> `ReplicaEngine`,
bound together by `MiniCluster`.  The window lasts a fixed number of wall
seconds: taps on the backend's `submit` and `on_event` end it at the first
call after the deadline, so the virtual clock stops at the last event the
window applied.  In a traced run the same taps (and taps on the engines and
the policy) write host spans into the profiler's trace and record the
shapes each program ran at, which the per-layer metrics read.
"""
from __future__ import annotations

import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax

from repro.serving import MiniCluster, ServeRequest
import repro.serving.cluster as cluster_mod


#: name prefixes of the host spans the taps write in a traced window
SPAN_NAMES = ("submit:", "on_event:", "dispatch", "prefill_quantum",
              "prefill_logits", "decode_iteration", "admit")


class WindowClosed(Exception):
    """Raised from a tap once the window's wall deadline has passed."""


class CompileCounter:
    """XLA compilations and persistent-cache loads, so a window can show it
    ran only programs that set-up had already made."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.n += 1


@dataclass
class Taps:
    """What a traced window recorded, in host ints and host seconds."""
    #: (prompt length, layers run, reused prefix length) per prefill quantum
    prefill_quanta: List[tuple] = field(default_factory=list)
    #: prompt length per first-token logits call
    finalize: List[int] = field(default_factory=list)
    #: (wall seconds, active slot lengths before the step) per decode step
    decode_steps: List[tuple] = field(default_factory=list)


def build_cluster(cfg, params, cell: Dict) -> MiniCluster:
    return MiniCluster(cfg, params, n_engines=cell["n_engines"],
                       policy=cell["policy"], max_len=cell["max_len"],
                       max_slots=cell["max_slots"],
                       long_threshold=cell["long_threshold"],
                       layers_per_quantum=cell["layers_per_quantum"],
                       clock="measured",
                       target_prefill_s=cell["target_prefill_s"])


def warm(mc: MiniCluster, lengths: List[int], n_replicas: int) -> None:
    """Compile (or load) every program the window will run: prefill, first
    logits, KV admission and one decode step at each prompt length, on
    replica 0 (programs are shared by every engine of one config); the
    other replicas' KV pools are allocated here too."""
    mc.backend.warmup(lengths, [0])
    for rid in range(1, n_replicas):
        mc.backend._engine(rid)
    jax.block_until_ready([e.kvpool.k for e in mc.backend._engines.values()])


def _span(traced: bool, name: str):
    return jax.profiler.TraceAnnotation(name) if traced else nullcontext()


def install_taps(mc: MiniCluster, deadline: List[float], traced: bool,
                 taps: Taps) -> None:
    """Observers on the served path that change nothing it computes.
    `deadline[0]` is the wall time at which the window closes."""
    be = mc.backend
    submit, on_event = be.submit, be.on_event

    def t_submit(work):
        if time.perf_counter() >= deadline[0]:
            raise WindowClosed
        with _span(traced, f"submit:{work.kind}"):
            submit(work)

    def t_on_event(t, kind, work):
        if time.perf_counter() >= deadline[0]:
            raise WindowClosed
        with _span(traced, f"on_event:{work.kind}"):
            on_event(t, kind, work)
    be.submit, be.on_event = t_submit, t_on_event
    if not traced:
        return

    make_policy = cluster_mod.make_policy        # restored by run_window

    def t_make_policy(*a, **kw):
        pol = make_policy(*a, **kw)
        dispatch = pol.dispatch

        def t_dispatch(t):
            with _span(True, "dispatch"):
                dispatch(t)
        pol.dispatch = t_dispatch
        return pol
    cluster_mod.make_policy = t_make_policy

    for eng in be._engines.values():
        _tap_engine(eng, taps)


def _tap_engine(eng, taps: Taps) -> None:
    pq, pl, di, ad = (eng.prefill_quantum, eng.prefill_logits,
                      eng.decode_iteration, eng.admit)

    def prefill_quantum(st):
        lo = st.layer
        with _span(True, "prefill_quantum"):
            out = pq(st)
        taps.prefill_quanta.append((int(st.tokens.shape[1]), st.layer - lo,
                                    st.prefix_len))
        return out

    def prefill_logits(st):
        with _span(True, "prefill_logits"):
            out = pl(st)
        taps.finalize.append(int(st.tokens.shape[1]))
        return out

    def decode_iteration(tokens):
        lens = eng.slot_lengths()
        t0 = time.perf_counter()
        with _span(True, "decode_iteration"):
            out = di(tokens)          # ends in host ints: synchronous
        taps.decode_steps.append((time.perf_counter() - t0,
                                  tuple(lens[s] for s in tokens)))
        return out

    def admit(rid, st):
        with _span(True, "admit"):
            return ad(rid, st)
    eng.prefill_quantum, eng.prefill_logits = prefill_quantum, prefill_logits
    eng.decode_iteration, eng.admit = decode_iteration, admit


@dataclass
class Window:
    wall_s: float                 # wall seconds from first submit to close
    v_end: float                  # virtual time at which the window closed
    closed_by_deadline: bool
    requests: list                # policy-side Request objects that arrived
    served: Dict[int, List[int]]  # rid -> tokens the engines generated
    #: rid -> layers done, of the prefills still running at the close
    prefill_layers: Dict[int, int]
    n_layers: int
    compiles: int
    n_events: int
    measured_s: float


def run_window(mc: MiniCluster, reqs, seconds: float, traced: bool,
               counter: CompileCounter, taps: Optional[Taps] = None,
               ) -> Window:
    taps = taps if taps is not None else Taps()
    deadline = [float("inf")]
    install_taps(mc, deadline, traced, taps)
    for r in reqs:
        mc.submit(ServeRequest(rid=r.rid, arrival=r.arrival, tokens=r.tokens,
                               max_new=r.max_new))
    n0 = counter.n
    t0 = time.perf_counter()
    deadline[0] = t0 + seconds
    closed = False
    make_policy = cluster_mod.make_policy
    try:
        mc.run()
    except WindowClosed:
        closed = True
    finally:
        cluster_mod.make_policy = make_policy
    wall = time.perf_counter() - t0
    sim = mc.backend.sim
    arrived = [r for r in sim.policy.all_requests if r.arrival <= sim.now]
    be = mc.backend
    running = {**be._psessions, **be._gangs}
    return Window(wall_s=wall, v_end=sim.now, closed_by_deadline=closed,
                  requests=arrived,
                  served={k: list(v) for k, v in be.generated.items()},
                  prefill_layers={k: int(st.layer) for k, st in running.items()
                                  if k not in be.generated},
                  n_layers=be.cfg.num_layers,
                  compiles=counter.n - n0, n_events=sim.n_events,
                  measured_s=be.measured_s)
