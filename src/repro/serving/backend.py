"""EngineBackend: the real-execution half of the ExecutionBackend split.

Every abstract command a policy issues (core/schedulers.py) is carried out
on genuine `ReplicaEngine`s:

* ``short_prefill`` / ``short_full`` / ``long_full`` / ``*_decode`` run to
  completion the moment they are submitted (they are never preempted by any
  policy), and the measured compute time becomes the Work's duration.
* ``long_prefill`` and ``long_decode`` are *preemptible*: they advance one
  quantum at a time through backend-internal ``ENGINE_STEP`` events
  (layers_per_quantum layers per step for prefill — the paper's §5.1
  suspension state — one decode iteration per step for decode), so a policy
  can pause them mid-flight and resume bit-exactly from the saved
  `PrefillState` / decode slot.
* Short-request KV migrates to the decode replica through `admit` (§5.2);
  decode is slot-chunked, so a burst larger than `max_slots` waits for
  evictions instead of crashing (`SlotsFull`).

Gang-scheduled fast SP (§5.3, the paper's third technique — live): when a
policy starts a multi-replica ``long_prefill`` with ``sp_mode="fastsp"``,
the backend *gangs* the group — it maps the claimed replicas onto a
(ring, sp) device mesh (`sp/gang.py`), runs the actual shard_map hybrid-SP
kernels (outer ring attention, inner a2a/allgather per the planner's
`SPPlan.inner_impl`) quantum by quantum with preemption points in between,
and on completion scatters the sequence-sharded KV back into the home
replica's paged pool (`ReplicaEngine.scatter_kv`), where decode picks it
up block-granularly.  A gang quantum covers ``layers_per_quantum x degree``
layers at equal per-device compute, so the prefill completes in ~degree x
fewer engine quanta — the mechanism by which fast SP shrinks the
preemption window.  Per-degree measured per-layer timings accumulate in
``sp_timings`` and can be fed back into the analytic cost model via
`calibrate_costmodel`, so SimBackend and EngineBackend predict the same
winner.  On hosts with fewer devices than the gang (tier-1 CI sees ONE),
`gang_degree` collapses to 1 and the long runs the single-replica path —
``sp_mode="ring"`` (the /FSP ablation and all baselines) always does, and
so does every family but dense: the gang's layer slice runs the dense
layer body.

Two virtual-clock modes:

* ``clock="measured"`` (default): completion times are the *measured* JAX
  compute seconds — scheduling dynamics reflect the hardware.
* ``clock="analytic"``: completion times come from the policy's cost-model
  estimate, exactly like SimBackend, while every command still executes on
  real engines.  Both backends then see an identical event timeline, which
  is what makes decision-sequence parity assertable (tests/test_backends.py)
  rather than merely plausible.

Requests carry cluster-scale token counts (100 K+ for longs); real engines
are CPU-sized.  Unless a `token_provider` supplies actual prompts (the
MiniCluster path), prompts are synthesized deterministically per rid with a
log-scaled, bucketed length so relative ordering (longs >> shorts) survives
while jit recompiles stay bounded.
"""
from __future__ import annotations

import functools
import math
import sys
import time
from collections import Counter, deque
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ModelConfig
from repro.core.backend import ExecutionBackend
from repro.core.request import Request
from repro.core.simulator import Work
from repro.kernels import ops
from repro.serving.engine import (FAMILIES, PrefillState, ReplicaEngine,
                                  SlotsFull)
from repro.sp.gang import (GangPrefillState, GangSPRunner, gang_degree,
                           make_gang_mesh, plan_for_gang)

# kinds that no policy ever cancels: execute eagerly at submit time.
# `pred_decode` (prediction-aware decode-lane rounds) is eager too: the
# round's END is its preemption point — the policy decides evict-vs-finish
# from the budget, never mid-round — so each round runs to completion the
# moment it is submitted.
_EAGER_KINDS = ("short_prefill", "short_prefill_coloc", "short_decode",
                "short_decode_inplace", "short_full", "long_full",
                "pred_decode")
_PREEMPTIBLE_KINDS = ("long_prefill", "long_decode")

# synthesized-prompt length buckets (limits distinct jit shapes per engine)
_BUCKETS = (4, 8, 12, 16, 24, 32, 48, 64, 96, 128, 192, 256)


def _greedy(logits: jax.Array) -> int:
    """First generated token from (1, V) prefill logits: one host transfer,
    no device program (nothing to compile inside a measured window)."""
    return int(np.argmax(np.asarray(logits)[0]))


def _call(start):
    """Marks a method the event loop or a policy calls into the backend.
    Its host seconds add to `host_s`, which `Simulator.sched_time` leaves
    out, and tokens it generates are stamped from `start(self, *args)`,
    the virtual time at which the call's work begins."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(self, *args):
            t0 = time.perf_counter()
            self._vstart, self._vdt = start(self, *args), 0.0
            try:
                return fn(self, *args)
            finally:
                self.host_s += time.perf_counter() - t0
        return call
    return wrap


class EngineBackend(ExecutionBackend):
    """Drive any `make_policy` policy over real JAX ReplicaEngines."""

    def __init__(self, cfg: ModelConfig, params, *, max_len: int = 128,
                 layers_per_quantum: int = 2, max_slots: int = 8,
                 clock: str = "measured", max_new_cap: int = 4,
                 token_provider: Optional[Callable[[Request],
                                                   Optional[np.ndarray]]] = None,
                 seed: int = 0, enable_sp: bool = True,
                 sp_degree_cap: int = 0, long_threshold: int = 0,
                 max_gang: int = 0):
        assert clock in ("measured", "analytic"), clock
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.lpq = layers_per_quantum
        self.max_slots = max_slots
        self.clock = clock
        self.max_new_cap = max_new_cap
        self.token_provider = token_provider
        self.seed = seed
        self.enable_sp = enable_sp and FAMILIES[cfg.family].gang
        self.sp_degree_cap = sp_degree_cap
        self.needs_finish = clock == "analytic"
        self.max_prompt = max(4, max_len - min(max_new_cap, 32) - 1)
        self._buckets = [b for b in _BUCKETS if b <= self.max_prompt]
        self._engines: Dict[int, ReplicaEngine] = {}      # replica rid -> engine
        self._tokens: Dict[int, np.ndarray] = {}          # request rid -> prompt
        # prefix-group token streams: requests in one group synthesize their
        # shared leading tokens from one deterministic stream, so an engine
        # that already prefilled an earlier group member holds byte-identical
        # prefix blocks (persists across reset(): pure function of group)
        self._group_streams: Dict[int, np.ndarray] = {}
        self._psessions: Dict[int, PrefillState] = {}     # in-flight prefills
        self._gangs: Dict[int, GangPrefillState] = {}     # in-flight gang SP
        self._dsessions: Dict[int, Dict] = {}             # in-flight long decodes
        self._kv: Dict[int, PrefillState] = {}            # prefilled, not decoded
        self._resident: Dict[int, int] = {}               # gang rid -> home replica
        self._parked_scatter: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        # decode-lane preemption (sjf_pred/tail_aware): host-side parked KV
        # of evicted decode lanes, and cluster-token decode progress per rid
        self._parked_decode: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        self._pdone: Dict[int, int] = {}
        self._gang_runners: Dict[int, GangSPRunner] = {}   # by degree
        #: device -> the weights there, shared by every gang runner
        self._weight_copies: Dict = {}
        #: `warmup` compiles every gang of 2 to `max_gang` replicas at each
        #: of its lengths of `long_threshold` tokens or more (0: no gang)
        self.long_threshold = long_threshold
        self.max_gang = max_gang
        self.generated: Dict[int, List[int]] = {}         # request rid -> tokens
        #: request rid -> virtual time of each generated token: the first
        #: at prefill completion, each later one at its decode step's end
        #: (the Work's start plus the measured seconds of the Work so far)
        self.token_times: Dict[int, List[float]] = {}
        self.stats = Counter()
        self.measured_s = 0.0
        #: host seconds spent inside calls from the loop and the policy
        self.host_s = 0.0
        self._vstart = self._vdt = 0.0     # the current call's clock
        #: the process's span and counter aggregates (`repro.obs`)
        self.obs = obs.TRACER
        #: degree -> measured seconds per layer (1 = single-replica path);
        #: accumulates across reset() like the engines' jit caches, so a
        #: sweep's calibration sees every run
        self.sp_timings: Dict[int, List[float]] = {}

    # ------------------------------------------------------------------
    def reset(self) -> None:
        """Clear per-run state; engines (and their jit caches), gang runners
        and sp_timings survive so a policy sweep pays compilation once."""
        for eng in self._engines.values():
            eng.clear()
        self._tokens.clear()
        self._psessions.clear()
        self._gangs.clear()
        self._dsessions.clear()
        self._kv.clear()
        self._resident.clear()
        self._parked_scatter.clear()
        self._parked_decode.clear()
        self._pdone.clear()
        self.generated.clear()
        self.token_times.clear()
        self.stats = Counter()
        self.measured_s = 0.0
        self.host_s = 0.0
        self.obs.reset()

    def prompt_len(self, req: Request) -> int:
        """Engine-side prompt length this request will execute with."""
        if self.token_provider is not None:
            toks = self.token_provider(req)
            if toks is not None:
                return int(np.asarray(toks).shape[0])
        return self._scale_len(req.input_len)

    def warmup(self, lengths, replica_ids) -> None:
        """Pre-compile the prefill/decode jits for the given prompt lengths
        on the given replicas, so measured virtual time reflects steady-state
        compute instead of charging first-shape compilation to whichever
        policy happens to run first."""
        impl = ops.paged_decode_impl(self.cfg.head_dim)
        print(f"decode attention: {self.cfg.name} runs {impl} "
              f"(ops.paged_decode_impl)", file=sys.stderr)
        for rid in replica_ids:
            eng = self._engine(rid)
            for n in sorted(set(lengths)):
                st = eng.start_prefill(-1, jnp.zeros((1, int(n)), jnp.int32))
                done = False
                while not done:
                    st, done = eng.prefill_quantum(st)
                eng.prefill_logits(st)
                slot = eng.admit(-1, st)
                eng.decode_iteration({slot: 0})
                eng.evict(slot)
        if self.enable_sp and self.max_gang > 1 and jax.device_count() > 1:
            self.warmup_gang([n for n in lengths if n >= self.long_threshold],
                             range(2, self.max_gang + 1),
                             cluster_input_len=None)

    def warmup_gang(self, lengths, degrees, *,
                    cluster_input_len: Optional[int] = 300_000) -> None:
        """Pre-compile the gang-SP runners (embed, every quantum slice,
        logits) for the given engine-side prompt lengths and gang degrees,
        with the inner strategy the planner picks at `cluster_input_len`
        (None: at each length, as a request of that length plans) — the
        gang counterpart of `warmup`, keeping shard_map compilation out
        of the measured clock and out of the `sp_timings` calibration
        samples."""
        for requested in sorted(set(degrees)):
            degree = gang_degree(requested, cap=self.sp_degree_cap)
            if degree < 2:
                continue
            runner = self._runner_for(degree)
            for n in sorted(set(lengths)):
                plan = plan_for_gang(self.cfg, cluster_input_len or int(n),
                                     runner.mesh)
                st = runner.start(-1, np.zeros(int(n), np.int32), plan)
                done = False
                while not done:
                    st, done = runner.quantum(st, self.lpq * degree)
                runner.logits(st)
                runner.gather_kv(st)

    def _engine(self, rid: int) -> ReplicaEngine:
        eng = self._engines.get(rid)
        if eng is None:
            eng = ReplicaEngine(self.cfg, self.params, max_slots=self.max_slots,
                                max_len=self.max_len,
                                layers_per_quantum=self.lpq)
            self._engines[rid] = eng
        return eng

    # ---- prompt synthesis / scaling ----------------------------------
    def _scale_len(self, n: int) -> int:
        raw = 8.0 * math.log2(1.0 + n / 256.0)
        for b in self._buckets:
            if raw <= b:
                return b
        return self.max_prompt

    def _group_stream(self, group: int) -> np.ndarray:
        s = self._group_streams.get(group)
        if s is None:
            rng = np.random.default_rng((self.seed, 0x9E3779B9,
                                         group & 0x7FFFFFFF))
            s = rng.integers(0, self.cfg.vocab_size,
                             self.max_prompt).astype(np.int32)
            self._group_streams[group] = s
        return s

    def _prompt(self, req: Request) -> np.ndarray:
        toks = self._tokens.get(req.rid)
        if toks is None:
            if self.token_provider is not None:
                toks = self.token_provider(req)
            if toks is None:
                n = self._scale_len(req.input_len)
                rng = np.random.default_rng((self.seed,
                                             req.rid & 0x7FFFFFFF))
                toks = rng.integers(0, self.cfg.vocab_size, n)
                if req.prefix_group is not None and req.prefix_len > 0:
                    # leading tokens come from the group's shared stream —
                    # scaled like the lengths, so the cluster-scale prefix
                    # relationship survives onto engine-sized prompts
                    p = min(self._scale_len(req.prefix_len), n)
                    toks = np.asarray(toks)
                    toks[:p] = self._group_stream(req.prefix_group)[:p]
            toks = np.asarray(toks, np.int32)
            if toks.shape[0] > self.max_len - 1:
                raise ValueError(
                    f"prompt of {toks.shape[0]} tokens exceeds engine "
                    f"max_len {self.max_len}")
            self._tokens[req.rid] = toks
        return toks

    def _target_new(self, req: Request) -> int:
        return max(1, min(self.max_new_cap, req.output_len))

    # ---- timed execution primitives ----------------------------------
    def _timed(self, fn, *args):
        """Run `fn` and charge its wall time, waiting for every array it
        produced — the arrays inside a returned `PrefillState` /
        `GangPrefillState` included (both are pytrees)."""
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        dt = time.perf_counter() - t0
        self.measured_s += dt
        self._vdt += dt
        return out, dt

    def _first_token(self, rid: int, logits: jax.Array) -> None:
        self.generated[rid] = [_greedy(logits)]
        self.token_times[rid] = [self._vstart + self._vdt]

    def _next_token(self, rid: int, tok: int) -> None:
        self.generated[rid].append(tok)
        self.token_times[rid].append(self._vstart + self._vdt)

    def _start_prefill(self, eng: ReplicaEngine, req: Request) -> PrefillState:
        prompt = self._prompt(req)
        host = tuple(int(t) for t in prompt)
        pk = pv = None
        if req.prefix_group is not None:
            # probe this engine's block-hash index: a hit turns the prefill
            # into a suffix-only one (the reused blocks' layers are skipped)
            hit, pk, pv = eng.lookup_cached_prefix(host)
            self.stats["prefix_lookups"] += 1
            if hit.n_tokens:
                self.stats["prefix_hits"] += 1
                self.stats["prefix_hit_tokens"] += hit.n_tokens
        st, _ = self._timed(
            lambda: eng.start_prefill(req.rid, jnp.asarray(prompt[None]),
                                      prefix_k=pk, prefix_v=pv,
                                      host_tokens=host))
        return st

    def _prefill_quanta(self, eng: ReplicaEngine, st: PrefillState,
                        target_layer: int, record: bool = False) -> float:
        dt = 0.0
        while st.layer < target_layer:
            lo = st.layer
            (_, _done), d = self._timed(eng.prefill_quantum, st)
            dt += d
            self.stats["prefill_quanta"] += 1
            # degree-1 timings feed the SP calibration only for LONG
            # prefills: their prompt bucket matches what gangs execute, so
            # the speedup curve compares like with like
            if record and st.layer > lo:
                self.sp_timings.setdefault(1, []).append(d / (st.layer - lo))
        return dt

    def _complete_prefill(self, eng: ReplicaEngine, req: Request) -> float:
        """Run remaining layers + first-token logits; park KV for decode."""
        st = self._psessions.pop(req.rid, None)
        if st is None:
            st = self._start_prefill(eng, req)
        dt = self._prefill_quanta(eng, st, self.cfg.num_layers,
                                  record=req.is_long)
        logits, d = self._timed(eng.prefill_logits, st)
        dt += d
        self._first_token(req.rid, logits)
        self._kv[req.rid] = st
        if (req.prefix_group is not None and st.host_tokens is not None
                and not eng.kvpool.ring):
            # park the full prompt KV in THIS engine's prefix cache (admit
            # + release -> cached-free list) so the group's next request
            # routed here skips the shared blocks (a pool with rings
            # reuses no prefix).  Bookkeeping copy, off the virtual clock —
            # the analytic model prices the skip via
            # prefill_time(cached_tokens=...), not this transfer.
            k, v, _, _ = eng.assemble_kv(st)
            eng.cache_prompt(0x40000000 ^ req.rid, k, v, st.host_tokens)
        return dt

    # ---- gang-scheduled SP prefill (§5.3) ----------------------------
    def _gang_degree_for(self, work: Work) -> int:
        """1 (the single-replica path) unless the policy asked for fast SP
        and a gang can prefill the model's family."""
        if not self.enable_sp or work.sp_mode != "fastsp":
            return 1
        return gang_degree(len(work.replica_ids), cap=self.sp_degree_cap)

    def _runner_for(self, degree: int) -> GangSPRunner:
        r = self._gang_runners.get(degree)
        if r is None:
            mesh = make_gang_mesh(degree, self.cfg.num_heads)
            r = GangSPRunner(self.cfg, self.params, mesh,
                             self._weight_copies)
            self._gang_runners[degree] = r
        return r

    def _start_gang(self, req: Request, degree: int) -> GangPrefillState:
        mesh = make_gang_mesh(degree, self.cfg.num_heads)
        # strategy choice reflects the CLUSTER-scale request length — the
        # planner's four-combination search (§5.3), not the scale prompt
        plan = plan_for_gang(self.cfg, req.input_len, mesh)
        runner = self._runner_for(degree)
        st, _ = self._timed(runner.start, req.rid, self._prompt(req), plan)
        self.stats["gang_prefills"] += 1
        return st

    def _gang_quantum(self, st: GangPrefillState) -> Tuple[bool, float]:
        """One SP quantum: lpq x degree layers at equal per-device compute."""
        runner = self._runner_for(st.degree)
        lo = st.layer
        (_, done), d = self._timed(runner.quantum, st, self.lpq * st.degree)
        self.stats["sp_prefill_quanta"] += 1
        if st.layer > lo:
            self.sp_timings.setdefault(st.degree, []).append(
                d / (st.layer - lo))
        return done, d

    def _finish_gang(self, work: Work) -> float:
        """Remaining gang quanta + first-token logits + KV scatter back to
        the home replica's paged pool."""
        req = work.requests[0]
        st = self._gangs[req.rid]
        runner = self._runner_for(st.degree)
        dt = 0.0
        while st.layer < self.cfg.num_layers:
            _, d = self._gang_quantum(st)
            dt += d
        logits, d = self._timed(runner.logits, st)
        dt += d
        self._first_token(req.rid, logits)
        k, v = runner.gather_kv(st)
        del self._gangs[req.rid]
        home = work.replica_ids[0]
        try:
            self._engine(home).scatter_kv(req.rid, jnp.asarray(k),
                                          jnp.asarray(v))
            self._resident[req.rid] = home
            self.stats["gang_scatters"] += 1
        except SlotsFull:
            # home pool momentarily out of blocks: park host-side, the
            # scatter retries when the decode phase binds a slot
            self._parked_scatter[req.rid] = (k, v)
            self.stats["gang_scatter_deferred"] += 1
        return dt

    def prefix_cache_stats(self) -> Dict[str, int]:
        """Pool-level prefix-cache counters summed across engines, plus the
        backend's own lookup tallies — the tooling/profile surface."""
        out = Counter()
        for eng in self._engines.values():
            out.update(eng.kvpool.stats)
        out["backend_lookups"] = int(self.stats.get("prefix_lookups", 0))
        out["backend_hits"] = int(self.stats.get("prefix_hits", 0))
        out["backend_hit_tokens"] = int(
            self.stats.get("prefix_hit_tokens", 0))
        return dict(out)

    def sp_per_layer_s(self) -> Dict[int, float]:
        """Median measured seconds/layer per SP degree (1 = no gang)."""
        return {d: float(np.median(v))
                for d, v in sorted(self.sp_timings.items()) if v}

    def calibrate_costmodel(self, em) -> Dict[int, float]:
        """Feed measured per-degree SP timings into the analytic model
        (`ExecutionModel.calibrate_sp`) so both backends price fast-SP
        prefill from the same curve."""
        m = self.sp_per_layer_s()
        if m:
            em.calibrate_sp(m)
        return m

    # ---- decode -------------------------------------------------------
    def _decode_batch(self, eng: ReplicaEngine, reqs: List[Request]) -> float:
        """Admit each request's parked KV and decode to its target length,
        chunked by free slots: a burst larger than the slot count waits for
        evictions inside the batch instead of raising through the loop."""
        dt = 0.0
        pending = deque(reqs)
        while pending:
            admitted: Dict[int, Request] = {}
            toks: Dict[int, int] = {}
            remaining: Dict[int, int] = {}
            while pending and eng.free_slots():
                r = pending.popleft()
                if r.rid not in self._kv:
                    # already decoded: the first dispatch of this request
                    # executed eagerly before churn canceled its Work and
                    # the policy restarted it — generations are complete
                    self.stats["churn_redecode_skips"] += 1
                    continue
                try:
                    slot = eng.admit(r.rid, self._kv[r.rid])
                except SlotsFull:           # lost a race with a long's slot
                    pending.appendleft(r)
                    break
                self.stats["kv_migrations"] += 1
                del self._kv[r.rid]
                admitted[slot] = r
                toks[slot] = self.generated[r.rid][-1]
                remaining[slot] = self._target_new(r) - 1
            if not admitted:
                if not pending:             # everything was a churn skip
                    break
                raise SlotsFull(
                    "decode pool wedged: no slot frees up for "
                    f"{len(pending)} pending requests")
            while True:
                active = {s: toks[s] for s, n in remaining.items() if n > 0}
                if not active:
                    break
                out, d = self._timed(eng.decode_iteration, active)
                dt += d
                self.stats["decode_iters"] += 1
                for s, tok in out.items():
                    self._next_token(admitted[s].rid, tok)
                    toks[s] = tok
                    remaining[s] -= 1
            for s in admitted:
                eng.evict(s)
        return dt

    def _pred_decode_round(self, eng: ReplicaEngine, work: Work) -> float:
        """One budgeted decode-lane round for the prediction-aware policies.

        The policy schedules `work.token_budget` cluster tokens; truth may
        end the round early (EOS).  Cluster-token progress maps onto the
        engine's capped token target proportionally, with the FINAL round
        (budget covers the true remainder) always decoding to the full
        target so generations match an uninterrupted run token for token.

        Admission mirrors the two park paths: the first round admits the
        prefill's parked `PrefillState` (`self._kv`); a round after a
        decode-lane eviction re-scatters the host-parked paged KV
        (`scatter_kv` + `bind_slot` — the gang scatter park path).  On a
        non-final round the slot's KV is gathered host-side, the blocks are
        released via `evict` (PagedKVCache.release), and the request waits
        for re-admission: deterministic greedy decode over the exactly
        preserved KV makes the continuation bit-identical.
        """
        req = work.requests[0]
        rid = req.rid
        budget = int(work.token_budget or 0)
        done = self._pdone.get(rid, 1)          # prefill emitted token 1
        done_after = done + budget
        final = done_after >= req.output_len
        T = self._target_new(req)
        goal = T if final else min(
            T - 1, 1 + int((T - 1) * done_after / max(req.output_len, 1)))
        if rid in self._kv:
            slot = eng.admit(rid, self._kv[rid])
            del self._kv[rid]
            self.stats["kv_migrations"] += 1
        elif rid in self._parked_decode:
            k, v = self._parked_decode.pop(rid)
            eng.scatter_kv(rid, jnp.asarray(k), jnp.asarray(v))
            slot = eng.bind_slot(rid)
            self.stats["decode_readmits"] += 1
        else:
            # the final round already ran before churn canceled its Work
            # and re-queued the request — nothing left to decode
            self.stats["churn_redecode_skips"] += 1
            return 0.0
        dt = 0.0
        last = self.generated[rid][-1]
        for _ in range(max(goal - len(self.generated[rid]), 0)):
            out, d = self._timed(eng.decode_iteration, {slot: last})
            dt += d
            self.stats["decode_iters"] += 1
            last = out[slot]
            self._next_token(rid, last)
        if final:
            eng.evict(slot)
            self._pdone.pop(rid, None)
        else:
            # decode-lane preemption at a step boundary: park host-side,
            # release the blocks for the lane's next tenant
            k, v = eng.kvpool.gather(rid)
            self._parked_decode[rid] = (np.asarray(k), np.asarray(v))
            eng.evict(slot)
            self._pdone[rid] = done_after
            self.stats["decode_preemptions"] += 1
        return dt

    def _bind_long_decode(self, req: Request, work_rid: int) -> None:
        """Install the long's decode session from whichever KV path its
        prefill took: parked PrefillState (single-replica), pool-resident
        blocks (gang scatter) or a deferred host-side scatter.  State is
        only consumed AFTER the step that needs it succeeds, so a SlotsFull
        here leaves everything in place for a retried submit.  The session
        remembers which engine holds the KV (`home`): for a gang long that
        is the scatter target, which need not be the decode work's first
        replica under every policy."""
        if req.rid in self._kv:
            eng = self._engine(work_rid)
            slot = eng.admit(req.rid, self._kv[req.rid])
            del self._kv[req.rid]
            self.stats["kv_migrations"] += 1
            home = work_rid
        else:
            if req.rid in self._parked_scatter:
                k, v = self._parked_scatter[req.rid]
                eng = self._engine(work_rid)
                eng.scatter_kv(req.rid, jnp.asarray(k), jnp.asarray(v))
                del self._parked_scatter[req.rid]
                self._resident[req.rid] = work_rid
            if req.rid not in self._resident:
                return                       # prefill never ran (defensive)
            home = self._resident[req.rid]
            slot = self._engine(home).bind_slot(req.rid)
            del self._resident[req.rid]
        # remaining counts from what is already generated (1 token after a
        # normal prefill; more after a churn evacuation re-bind mid-decode)
        self._dsessions[req.rid] = {
            "slot": slot, "home": home,
            "last": self.generated[req.rid][-1],
            "remaining": max(
                self._target_new(req) - len(self.generated[req.rid]), 0)}

    # ---- eager kinds --------------------------------------------------
    def _execute(self, work: Work) -> float:
        eng = self._engine(work.replica_ids[0])
        kind = work.kind
        dt = 0.0
        if kind in ("short_prefill", "short_prefill_coloc"):
            for r in work.requests:
                dt += self._complete_prefill(eng, r)
        elif kind in ("short_decode", "short_decode_inplace"):
            dt += self._decode_batch(eng, work.requests)
        elif kind in ("short_full", "long_full"):
            for r in work.requests:
                dt += self._complete_prefill(eng, r)
            dt += self._decode_batch(eng, work.requests)
        elif kind == "pred_decode":
            dt += self._pred_decode_round(eng, work)
        else:                               # pragma: no cover - guarded by submit
            raise ValueError(kind)
        self.stats[kind] += 1
        return dt

    # ------------------------------------------------------------------
    # ExecutionBackend interface
    # ------------------------------------------------------------------
    @_call(lambda self, work: work.start)
    def submit(self, work: Work) -> None:
        t = work.start
        if work.kind in _EAGER_KINDS:
            measured = self._execute(work)
            if self.clock == "measured":
                work.duration = measured
            self.sim.push(t + work.duration, "DONE", work)
            return
        if work.kind not in _PREEMPTIBLE_KINDS:
            raise ValueError(f"unknown work kind {work.kind!r}")
        req = work.requests[0]
        eng = self._engine(work.replica_ids[0])
        if work.kind == "long_prefill":
            degree = self._gang_degree_for(work)
            started = (req.rid in self._psessions or req.rid in self._gangs
                       or req.rid in self._kv or req.rid in self._resident
                       or req.rid in self._parked_scatter)
            if not started:
                if degree >= 2:
                    self._gangs[req.rid] = self._start_gang(req, degree)
                else:
                    self._psessions[req.rid] = self._start_prefill(eng, req)
        else:                               # long_decode
            if req.rid not in self._dsessions:
                self._bind_long_decode(req, work.replica_ids[0])
        if self.clock == "analytic":
            self.sim.push(t + work.duration, "DONE", work)
        else:
            self.sim.push(t, "ENGINE_STEP", work)

    @_call(lambda self, work: self.sim.now)
    def decode_inline(self, work: Work) -> None:
        """/Dis colocated shorts finish with decode modeled inline by the
        policy; run that decode for real (on the colocation group's first
        engine) so generations complete and the parked KV is released.  Its
        measured time stays off the virtual clock, matching the analytic
        inline model."""
        self._decode_batch(self._engine(work.replica_ids[0]), work.requests)

    def role_change(self, t: float, rid: int, old_role: str,
                    new_role: str) -> None:
        """Verify a coordinator role flip against the real engine: the
        policy promises the replica is drained, and here that promise meets
        the hardware.  A live decode slot or resident gang KV on the
        flipping engine means the policy flipped mid-work — fail loudly
        instead of serving a role with another role's state resident.
        Parked per-request KV (`self._kv`) is engine-agnostic host state
        and migrates at admit time (§5.2), so it needs no action here."""
        eng = self._engines.get(rid)
        if eng is not None:
            live = [r for r in eng.slot_rid if r is not None]
            resident = [req_rid for req_rid, home in self._resident.items()
                        if home == rid]
            if live or resident:
                raise RuntimeError(
                    f"unsafe role flip {old_role}->{new_role} on replica "
                    f"{rid}: live decode slots {live}, resident gang KV "
                    f"{resident}")
        self.stats["role_flips"] += 1

    @_call(lambda self, t, rid: t)
    def reclaim_replica(self, t: float, rid: int) -> Dict[str, int]:
        """Spot eviction of replica `rid`: park every piece of KV physically
        resident on its engine so migrated requests resume elsewhere, then
        clear the engine (blocks, slots, prefix cache — the physical twin
        of `PrefixResidency.drop_replica`).

        Evacuation is the gang-scatter park recipe: gather the request's
        paged KV, copy it host-side into `_parked_scatter`, and let the
        next `_bind_long_decode` scatter it into whichever surviving
        replica the policy re-dispatches on (`scatter_kv` + `bind_slot`).
        In-flight prefill sessions (`_psessions`/`_gangs`) hold
        engine-agnostic device arrays, not pool blocks, and parked
        prefills (`_kv`) are already host-portable — both migrate for free
        at their next use, so only pool-resident state needs parking."""
        eng = self._engines.get(rid)
        if eng is None:
            return {}
        parked = blocks = 0
        # live long-decode sessions homed here: park mid-generation
        for req_rid in [r for r, s in self._dsessions.items()
                        if s["home"] == rid]:
            blocks += len(eng.kvpool.tables.get(req_rid, ()))
            k, v = eng.kvpool.gather(req_rid)
            self._parked_scatter[req_rid] = (np.asarray(k), np.asarray(v))
            del self._dsessions[req_rid]
            parked += 1
        # gang-scattered KV awaiting its decode bind
        for req_rid in [r for r, home in self._resident.items()
                        if home == rid]:
            blocks += len(eng.kvpool.tables.get(req_rid, ()))
            k, v = eng.kvpool.gather(req_rid)
            self._parked_scatter[req_rid] = (np.asarray(k), np.asarray(v))
            del self._resident[req_rid]
            parked += 1
        eng.clear()
        self.stats["reclaims"] += 1
        self.stats["evacuated_sessions"] += parked
        self.stats["evacuated_blocks"] += blocks
        return {"parked_sessions": parked, "evacuated_blocks": blocks}

    @_call(lambda self, work: self.sim.now)
    def cancel(self, work: Work) -> bool:
        ok = self.sim.cancel(work)
        if ok and self.clock == "analytic":
            # analytic clock executes lazily; materialize the progress this
            # Work made up to the preemption point so the resumed session
            # continues from a genuine §5.1 suspension state
            frac = 0.0
            if work.duration > 0:
                frac = min(max((self.sim.now - work.start) / work.duration,
                               0.0), 1.0)
            req = work.requests[0]
            eng = self._engine(work.replica_ids[0])
            if work.kind == "long_prefill":
                st = self._psessions.get(req.rid)
                gst = self._gangs.get(req.rid)
                if st is not None:
                    left = self.cfg.num_layers - st.layer
                    self._prefill_quanta(eng, st,
                                         st.layer + int(frac * left),
                                         record=True)
                elif gst is not None:
                    left = self.cfg.num_layers - gst.layer
                    target = gst.layer + int(frac * left)
                    while gst.layer < target:
                        self._gang_quantum(gst)
            elif work.kind == "long_decode":
                sess = self._dsessions.get(req.rid)
                if sess is not None:
                    self._decode_steps(self._engine(sess["home"]), req, sess,
                                       int(frac * sess["remaining"]))
        return ok

    def _decode_steps(self, eng: ReplicaEngine, req: Request, sess: Dict,
                      n: int) -> float:
        dt = 0.0
        for _ in range(min(n, sess["remaining"])):
            out, d = self._timed(eng.decode_iteration,
                                 {sess["slot"]: sess["last"]})
            dt += d
            self.stats["decode_iters"] += 1
            tok = out[sess["slot"]]
            self._next_token(req.rid, tok)
            sess["last"] = tok
            sess["remaining"] -= 1
        return dt

    # ---- measured clock: quantum events ------------------------------
    @_call(lambda self, t, kind, work: t)
    def on_event(self, t: float, kind: str, work: Work) -> None:
        assert kind == "ENGINE_STEP", kind
        req = work.requests[0]
        eng = self._engine(work.replica_ids[0])
        if work.kind == "long_prefill":
            gst = self._gangs.get(req.rid)
            if gst is not None:
                done, d = ((True, 0.0) if gst.layer >= self.cfg.num_layers
                           else self._gang_quantum(gst))
                if not done:
                    self.sim.push(t + d, "ENGINE_STEP", work)
                    return
                d += self._finish_gang(work)
                work.duration = t + d - work.start
                self.sim.push(t + d, "DONE", work)
                return
            st = self._psessions.get(req.rid)
            if st is None:                  # finished before a late preemption
                work.duration = max(t - work.start, 0.0)
                self.sim.push(t, "DONE", work)
                return
            d = self._prefill_quanta(
                eng, st, min(st.layer + self.lpq, self.cfg.num_layers),
                record=True)
            if st.layer < self.cfg.num_layers:
                self.sim.push(t + d, "ENGINE_STEP", work)
                return
            logits, d2 = self._timed(eng.prefill_logits, st)
            self._first_token(req.rid, logits)
            self._kv[req.rid] = self._psessions.pop(req.rid)
            work.duration = t + d + d2 - work.start
            self.sim.push(t + d + d2, "DONE", work)
        else:                               # long_decode
            sess = self._dsessions.get(req.rid)
            if sess is None or sess["remaining"] <= 0:
                if sess is not None:
                    self._engine(sess["home"]).evict(sess["slot"])
                    del self._dsessions[req.rid]
                work.duration = max(t - work.start, 0.0)
                self.sim.push(t, "DONE", work)
                return
            eng = self._engine(sess["home"])
            d = self._decode_steps(eng, req, sess, 1)
            if sess["remaining"] <= 0:
                eng.evict(sess["slot"])
                del self._dsessions[req.rid]
                work.duration = t + d - work.start
                self.sim.push(t + d, "DONE", work)
            else:
                self.sim.push(t + d, "ENGINE_STEP", work)

    # ---- analytic clock: lazy completion ------------------------------
    @_call(lambda self, t, work: t)
    def finish(self, t: float, work: Work) -> None:
        if work.kind == "long_prefill":
            req = work.requests[0]
            if req.rid in self._gangs:
                self._finish_gang(work)
            elif (req.rid not in self._kv and req.rid not in self._resident
                    and req.rid not in self._parked_scatter):
                # run whatever layers remain on the single-replica path
                self._complete_prefill(self._engine(work.replica_ids[0]), req)
        elif work.kind == "long_decode":
            req = work.requests[0]
            sess = self._dsessions.pop(req.rid, None)
            if sess is not None:
                eng = self._engine(sess["home"])
                self._decode_steps(eng, req, sess, sess["remaining"])
                eng.evict(sess["slot"])
