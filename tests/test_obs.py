"""Spans and counters of the served path (`repro.obs`), the policy's time
apart from the backend's, preemption suspension times and per-token
times."""
import copy
import dataclasses
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config, reduced_config
from repro.core import (Simulator, experiment_trace, make_policy,
                        paper_cluster)
from repro.models import init_params
from repro.serving.backend import EngineBackend
from repro.serving.cluster import MiniCluster, ServeRequest
from repro.serving.engine import ReplicaEngine, SlotsFull


@pytest.fixture(scope="module")
def small_model():
    cfg = dataclasses.replace(
        reduced_config(get_config("llama3_8b"), layers=4),
        dtype="float32", sliding_window=0)
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture
def tracer():
    """The process tracer, cleared before and after."""
    obs.reset()
    yield obs.TRACER
    obs.reset()


# ---------------- aggregates -------------------------------------------------
def test_nested_spans_add_total_and_self_time():
    tr = obs.Tracer()
    with tr.span("outer"):
        time.sleep(0.02)
        for _ in range(2):
            with tr.span("outer/inner"):
                time.sleep(0.01)
    outer, inner = tr.spans["outer"], tr.spans["outer/inner"]
    assert (outer.n, inner.n) == (1, 2)
    assert inner.total_s == pytest.approx(inner.self_s)
    assert inner.total_s >= 0.02
    # the outer span's self time leaves out what its children covered
    assert outer.self_s == pytest.approx(outer.total_s - inner.total_s)
    assert 0.02 <= outer.self_s < outer.total_s
    assert tr.mean_ms("outer/inner") == pytest.approx(
        1e3 * inner.total_s / 2)
    assert tr.mean_ms("never") == 0.0


def test_count_disable_and_reset():
    tr = obs.Tracer()
    tr.count("slots", 3)
    tr.count("slots")
    with tr.span("a"):
        pass
    assert tr.counters["slots"] == 4 and tr.spans["a"].n == 1
    tr.disable()
    # one shared null context, and nothing added
    assert tr.span("a") is tr.span("b", rid=3)
    with tr.span("a"):
        tr.count("slots", 10)
    assert tr.counters["slots"] == 4 and tr.spans["a"].n == 1
    tr.reset()
    assert not tr.spans and not tr.counters


def test_a_span_left_by_an_exception_still_counts():
    tr = obs.Tracer()
    with pytest.raises(KeyError):
        with tr.span("outer"):
            with tr.span("outer/inner"):
                raise KeyError
    assert tr.spans["outer"].n == tr.spans["outer/inner"].n == 1
    assert not tr._open


# ---------------- spans of the engine ----------------------------------------
def test_engine_spans_count_quanta_migrations_and_steps(small_model, tracer):
    cfg, params = small_model
    eng = ReplicaEngine(cfg, params, max_len=64, layers_per_quantum=2)
    st = eng.start_prefill(0, jnp.arange(24, dtype=jnp.int32)[None])
    done = False
    while not done:
        st, done = eng.prefill_quantum(st)
    first = int(jnp.argmax(eng.prefill_logits(st)[0]))
    slot = eng.admit(0, st)
    tok = first
    for _ in range(3):
        tok = eng.decode_iteration({slot: tok})[slot]
    n = {k: a.n for k, a in tracer.spans.items()}
    assert n == {
        "prefill_quantum": 2, "prefill_quantum/program": 2,
        "prefill_quantum/kv_split": 2,
        "admit": 1, "admit/assemble": 1, "admit/pool_write": 1,
        "decode_iteration": 3, "decode_iteration/prep": 3,
        "decode_iteration/view": 1,         # rebuilt once, after the admit
        "decode_iteration/program": 3, "decode_iteration/append": 3,
        "decode_iteration/readback": 3}
    assert tracer.counters == {"admit/tokens": 24,
                               "decode_iteration/slots": 3}
    for parent in ("prefill_quantum", "admit", "decode_iteration"):
        kids = sum(a.total_s for k, a in tracer.spans.items()
                   if k.startswith(parent + "/") and k.count("/") == 1
                   and k != "decode_iteration/view")
        agg = tracer.spans[parent]
        assert agg.self_s == pytest.approx(agg.total_s - kids, abs=1e-9)


def test_a_refused_admit_is_not_a_migration(small_model, tracer):
    cfg, params = small_model
    eng = ReplicaEngine(cfg, params, max_len=64, max_slots=1)
    st = eng.start_prefill(0, jnp.arange(8, dtype=jnp.int32)[None])
    for _ in range(cfg.num_layers // eng.lpq):
        st, _ = eng.prefill_quantum(st)
    eng.admit(0, st)
    with pytest.raises(SlotsFull):
        eng.admit(1, st)
    assert tracer.spans["admit"].n == 1


def test_span_reads_back_from_the_profiler_by_its_bare_name(tmp_path,
                                                            tracer):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    with obs.span("admit", rid=7):
        with obs.span("admit/assemble"):
            jnp.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    events = [e for p in ProfileData.from_file(path).planes
              if p.name == "/host:CPU" for ln in p.lines for e in ln.events
              if e.name.startswith("admit")]
    by_name = {e.name: dict(e.stats) for e in events}
    assert set(by_name) == {"admit", "admit/assemble"}
    assert by_name["admit"]["rid"] == 7
    assert tracer.spans["admit"].n == 1


# ---------------- the backend's clock and the policy's time ------------------
def _requests(cfg, targets, rng, long_len=0):
    reqs = [ServeRequest(rid=i, arrival=0.0, max_new=m,
                         tokens=rng.integers(0, cfg.vocab_size, 12)
                         .astype(np.int32))
            for i, m in enumerate(targets)]
    if long_len:
        reqs.append(ServeRequest(
            rid=len(reqs), arrival=0.0, max_new=3, is_long=True,
            tokens=rng.integers(0, cfg.vocab_size, long_len)
            .astype(np.int32)))
    return reqs


def test_sched_time_leaves_out_the_backends_host_time(small_model,
                                                      monkeypatch):
    cfg, params = small_model
    real = EngineBackend._execute

    def slow(self, work):
        time.sleep(0.05)
        return real(self, work)
    monkeypatch.setattr(EngineBackend, "_execute", slow)
    mc = MiniCluster(cfg, params, n_engines=1, policy="pecsched",
                     max_len=64)
    for r in _requests(cfg, [2, 3, 2], np.random.default_rng(0)):
        mc.submit(r)
    mc.run()
    sim, be = mc.backend.sim, mc.backend
    n_eager = sum(be.stats[k] for k in ("short_prefill", "short_decode"))
    assert n_eager >= 2
    assert be.host_s >= 0.05 * n_eager
    assert sim.backend_time == pytest.approx(be.host_s, rel=1e-6)
    assert sim.sched_time < 0.05
    p = sim.profile()
    assert p["policy_s"] + p["backend_s"] + p["loop_s"] == pytest.approx(
        p["wall_s"])


def test_token_times_one_per_token_and_a_short_member_ends_first(
        small_model):
    cfg, params = small_model
    mc = MiniCluster(cfg, params, n_engines=1, policy="pecsched",
                     max_len=64, long_threshold=40)
    for r in _requests(cfg, [2, 6], np.random.default_rng(1), long_len=48):
        mc.submit(r)
    mc.run()
    be = mc.backend
    by_rid = {r.rid: r for r in mc.policy_obj.all_requests}
    assert set(be.token_times) == set(be.generated) == set(by_rid)
    for rid, times in be.token_times.items():
        assert len(times) == len(be.generated[rid]) == by_rid[rid].output_len
        assert all(a <= b for a, b in zip(times, times[1:]))
        # the last token is the request's own, never after its finish
        assert times[-1] <= by_rid[rid].finish + 1e-12
    short, longer = by_rid[0], by_rid[1]
    # one decode batch: both finish at its end, the 2-token member earlier
    assert short.finish == longer.finish
    assert be.token_times[0][-1] < short.finish
    assert be.token_times[1][-1] == pytest.approx(longer.finish)
    assert be.token_times[0][0] <= short.first_token
    # a reset clears them with the generations
    be.reset()
    assert not be.token_times and be.host_s == 0.0


# ---------------- preemption suspensions ------------------------------------
@pytest.fixture(scope="module")
def pecsched_run():
    cc, em = paper_cluster("mistral_7b")
    reqs, _ = experiment_trace(cc, em, n_requests=3000, seed=1)
    pol = make_policy("pecsched", cc, em)
    Simulator(pol).run(copy.deepcopy(reqs))
    return pol


def test_suspended_time_of_preempted_and_untouched_longs(pecsched_run):
    longs = [r for r in pecsched_run.all_requests if r.is_long]
    hit = [r for r in longs if r.n_preemptions]
    untouched = [r for r in longs if not r.n_preemptions]
    assert hit and untouched
    assert all(r.suspended_s > 0 and r.paused_at is None for r in hit)
    assert all(r.suspended_s == 0.0 for r in untouched)
    assert all(r.suspended_s <= r.jct for r in longs if r.finish is not None)
    # no short is ever suspended
    assert all(r.suspended_s == 0.0 for r in pecsched_run.all_requests
               if not r.is_long)
