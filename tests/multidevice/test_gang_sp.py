"""Gang-scheduled SP prefill on real engines (the paper's fast-SP path, live).

Covers, on a forced-8-device host mesh (skipped otherwise — see conftest):

* numerical parity: gang-SP prefill logits and the post-scatter paged KV
  match the single-replica prefill within float32 tolerance, for every
  planner strategy combination (megatron/ulysses x attn/mlp) and 2 model
  configs (different GQA head counts);
* token-identical generations when an SP-prefilled long is preempted and
  resumed mid-gang vs never preempted;
* the acceptance bar: a degree>=2 gang completes long prefill in
  measurably fewer engine quanta than the single-replica path;
* cross-backend ablation: pecsched vs pecsched/FSP preemption-frequency
  and long-JCT deltas have the same sign on SimBackend and on the
  measured-clock EngineBackend;
* calibration: engine-measured per-degree timings fed back through
  `ExecutionModel.calibrate_sp` make the analytic model predict the same
  winner (fast SP beats ring-only) the engines measured.
"""
import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.core import ClusterConfig, ExecutionModel, Simulator, make_policy
from repro.core.request import Request
from repro.models import init_params
from repro.serving.backend import EngineBackend
from repro.serving.engine import ReplicaEngine
from repro.sp.gang import GangSPRunner, SPPlan, make_gang_mesh, plan_for_gang

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "set before jax initializes (see tests/multidevice/conftest.py)")

LAYERS = 4


def small_cfg(name):
    return dataclasses.replace(
        reduced_config(get_config(name), layers=LAYERS),
        dtype="float32", sliding_window=0)


@pytest.fixture(scope="module", params=["mistral_7b", "qwen2_7b"])
def model(request):
    cfg = small_cfg(request.param)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


# ---------------- numerical parity ------------------------------------------
@pytest.mark.parametrize("attn_strategy", ["megatron", "ulysses"])
@pytest.mark.parametrize("mlp_strategy", ["megatron", "ulysses"])
def test_gang_prefill_and_scatter_match_single_replica(model, attn_strategy,
                                                       mlp_strategy):
    """Gang logits == single-replica logits, and the KV that `scatter_kv`
    lands in the home replica's paged pool == the single-replica prefill KV,
    for every planner strategy combination."""
    cfg, params = model
    eng = ReplicaEngine(cfg, params, max_len=256, layers_per_quantum=1)
    rng = np.random.default_rng(1)
    toks = rng.integers(0, cfg.vocab_size, 96).astype(np.int32)

    st = eng.start_prefill(0, jnp.asarray(toks[None]))
    done = False
    while not done:
        st, done = eng.prefill_quantum(st)
    ref_logits = eng.prefill_logits(st)
    ref_k = jnp.stack(st.kv_k, 0)[:, 0]
    ref_v = jnp.stack(st.kv_v, 0)[:, 0]

    mesh = make_gang_mesh(4, cfg.num_heads)
    plan = SPPlan(attn_strategy=attn_strategy, mlp_strategy=mlp_strategy,
                  est_time=1.0)
    runner = GangSPRunner(cfg, params, mesh)
    gst = runner.start(7, toks, plan)
    gdone = False
    while not gdone:
        gst, gdone = runner.quantum(gst, 4)
    g_logits = runner.logits(gst)
    gk, gv = runner.gather_kv(gst)

    assert float(jnp.abs(g_logits - ref_logits).max()) < 5e-4
    np.testing.assert_allclose(gk, np.asarray(ref_k), atol=5e-5)
    np.testing.assert_allclose(gv, np.asarray(ref_v), atol=5e-5)

    # scatter into the home replica's paged pool and read it back
    home = ReplicaEngine(cfg, params, max_len=256)
    home.scatter_kv(7, jnp.asarray(gk), jnp.asarray(gv))
    pk, pv = home.kvpool.gather(7)
    np.testing.assert_array_equal(np.asarray(pk), gk)
    np.testing.assert_array_equal(np.asarray(pv), gv)


def test_planner_strategy_reaches_the_gang():
    """The gang must run the planner's chosen inner strategy
    (SPPlan.inner_impl), not a hardcoded one."""
    cfg = small_cfg("mistral_7b")
    mesh = make_gang_mesh(4, cfg.num_heads)
    plan = plan_for_gang(cfg, 300_000, mesh)
    assert plan.inner_impl in ("a2a", "allgather")
    assert plan.inner_impl == \
        {"megatron": "allgather", "ulysses": "a2a"}[plan.attn_strategy]


# ---------------- scheduler-level harness -----------------------------------
N_GENERAL = 2          # 2-replica gang: degree 2, mid-prefill preemption point
LONG_PROMPT = 224      # engine-side tokens for the long (compute-dominated)
SHORT_PROMPT = 16


def gang_cluster(cfg):
    """N_GENERAL general + 1 decode replica, prefill target tight enough
    that a 300K long claims every general replica (an SP gang)."""
    cc = ClusterConfig(n_nodes=1, gpus_per_node=N_GENERAL + 1, tp=1,
                       n_short_decode_replicas=1, max_decode_concurrency=8)
    em = ExecutionModel(cfg, cc.replica_spec(), target_prefill_s=0.05)
    assert em.replicas_needed(300_000) >= N_GENERAL
    return cc, em


def gang_trace(n_shorts=12, long_output=6, gap=2e-3):
    reqs = [Request(rid=0, arrival=0.0, input_len=300_000,
                    output_len=long_output, is_long=True)]
    rng = np.random.default_rng(4)
    for i in range(1, n_shorts + 1):
        reqs.append(Request(rid=i, arrival=round(i * gap, 6),
                            input_len=int(rng.integers(300, 3000)),
                            output_len=int(rng.integers(2, 8))))
    return reqs


def _tokens_for(req):
    n = LONG_PROMPT if req.is_long else SHORT_PROMPT
    rng = np.random.default_rng(req.rid + 11)
    return rng.integers(0, 1000, n).astype(np.int32)


@pytest.fixture(scope="module")
def backend_stack():
    cfg = small_cfg("mistral_7b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    cc, em = gang_cluster(cfg)
    be = EngineBackend(cfg, params, max_len=256, layers_per_quantum=1,
                       clock="measured", token_provider=_tokens_for)
    return cfg, cc, em, be


def run_policy(be, cc, em, policy, trace, *, enable_sp=True):
    be.reset()
    be.enable_sp = enable_sp
    pol = make_policy(policy, cc, em)
    summary = Simulator(pol, backend=be).run(copy.deepcopy(trace))
    return pol, summary


def test_gang_uses_fewer_engine_quanta(backend_stack):
    """Acceptance bar: pecsched's long prefill via a degree>=2 gang
    completes in measurably fewer engine quanta than the single-replica
    path on the same trace."""
    cfg, cc, em, be = backend_stack
    trace = gang_trace()

    _, s_sp = run_policy(be, cc, em, "pecsched", trace, enable_sp=True)
    sp_stats = dict(be.stats)
    assert sp_stats["gang_prefills"] >= 1
    assert sp_stats["gang_scatters"] >= 1
    assert s_sp["long_completed"] == 1
    assert s_sp["short_completed"] == len(trace) - 1

    _, s_single = run_policy(be, cc, em, "pecsched", trace, enable_sp=False)
    single_stats = dict(be.stats)
    assert single_stats.get("gang_prefills", 0) == 0
    assert s_single["long_completed"] == 1

    # lpq=1, degree 2: the gang covers 2 layers per quantum.  Shorts take
    # identical quanta in both runs, so the long's cost is the difference.
    gang_quanta = sp_stats["sp_prefill_quanta"]
    long_single_quanta = (single_stats["prefill_quanta"]
                          - sp_stats["prefill_quanta"])
    assert long_single_quanta == LAYERS
    assert gang_quanta == -(-LAYERS // 2)
    assert gang_quanta < long_single_quanta


def test_preempted_gang_long_generates_identical_tokens(backend_stack):
    """A gang-SP long preempted (and resumed) by short pressure must
    generate exactly the tokens of an unpreempted gang run (the paper's
    suspension-state exactness, on the SP path)."""
    cfg, cc, em, be = backend_stack

    _, s_quiet = run_policy(be, cc, em, "pecsched", gang_trace(n_shorts=0))
    assert be.stats["gang_prefills"] == 1
    quiet_tokens = list(be.generated[0])
    assert s_quiet["preemptions"] == 0

    _, s_busy = run_policy(be, cc, em, "pecsched",
                           gang_trace(n_shorts=16, gap=1e-4))
    assert be.stats["gang_prefills"] == 1
    assert s_busy["preemptions"] > 0, "short pressure must preempt the gang"
    busy_tokens = list(be.generated[0])

    assert quiet_tokens == busy_tokens
    assert len(quiet_tokens) == be._target_new(gang_trace()[0])


def test_fsp_ablation_same_sign_on_sim_and_measured_engine(backend_stack):
    """pecsched vs pecsched/FSP: preemption-frequency and long-JCT deltas
    must have the same sign on the analytic SimBackend and on the
    measured-clock EngineBackend (the paper's Fig. 14 / Table 3 ablation,
    evaluated in both worlds)."""
    cfg, cc, em, be = backend_stack
    trace = gang_trace(n_shorts=24, gap=1.5e-3)

    deltas = {}
    for world in ("sim", "engine"):
        jct, preempt = {}, {}
        for pol_name in ("pecsched", "pecsched/fsp"):
            if world == "sim":
                pol = make_policy(pol_name, cc, em)
                s = Simulator(pol).run(copy.deepcopy(trace))
            else:
                # warm pass compiles every shape; measure the second pass
                run_policy(be, cc, em, pol_name, trace)
                pol, s = run_policy(be, cc, em, pol_name, trace)
            longs = [r for r in pol.done_requests if r.is_long]
            assert len(longs) == 1
            jct[pol_name] = longs[0].finish - longs[0].arrival
            preempt[pol_name] = s["preemptions"]
        deltas[world] = (jct["pecsched/fsp"] - jct["pecsched"],
                         preempt["pecsched/fsp"] - preempt["pecsched"])

    for world, (d_jct, d_pre) in deltas.items():
        assert d_jct > 0, (world, deltas)    # /FSP's long finishes later
        assert d_pre >= 0, (world, deltas)   # suspended at least as often


def test_measured_timings_calibrate_the_analytic_winner(backend_stack):
    """The engine's measured per-degree timings, fed back through
    `calibrate_sp`, must leave the analytic model predicting the winner the
    engines actually measured between their two executable prefill options:
    the fast-SP gang beats the single-replica path (what /FSP falls back
    to), and the calibrated curve is exactly the measured speedup."""
    cfg, cc, em, be = backend_stack
    trace = gang_trace(n_shorts=2)
    # degree-1 long timings come from a no-gang run, gang timings from an
    # SP run; warm each shape first so medians are steady-state
    for sp in (False, True):
        run_policy(be, cc, em, "pecsched", trace, enable_sp=sp)
    be.sp_timings.clear()
    for sp in (False, True):
        run_policy(be, cc, em, "pecsched", trace, enable_sp=sp)
    t_ring_before = em.prefill_time(300_000, 2, sp_mode="ring")
    measured = be.calibrate_costmodel(em)
    degree = max(measured)
    assert degree >= 2 and 1 in measured
    assert measured[degree] < measured[1], measured

    t_fast = em.prefill_time(300_000, degree, sp_mode="fastsp")
    t_local = em.prefill_time(300_000, 1, sp_mode="local")
    # same winner as measured: the gang beat the single-replica prefill
    assert t_fast < t_local
    # the calibrated estimate IS the measured speedup curve
    assert t_fast == pytest.approx(t_local / (measured[1] / measured[degree]))
    # ring-only and local pricing never consult the calibration
    assert em.prefill_time(300_000, 2, sp_mode="ring") == t_ring_before
    em._sp_speedup = {}                                  # leave em clean
