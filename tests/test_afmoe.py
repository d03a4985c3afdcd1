"""The afmoe family (Trinity) through the engine, at a small size on the CPU,
against the plain float32 reference of the benchmark
(`bench/references/afmoe.py`) on the same seeded weights.

The model: one period of the layer pattern (three windowed layers, then a
full one), the first layer a dense SwiGLU, the other three routed over 8
experts (2 a token) of which this engine holds 4, plus a shared expert;
window 16 over blocks of 8, so a windowed layer's ring is 3 blocks and a
40-token prompt followed by 30 decoded tokens wraps it many times.

Tolerances.  The served path and the reference both compute in float32
(on the CPU a float32 matmul is float32); they differ in summation order
only (the engine's chunked attention, its paged decode, the expert
gather), which moves logits by about 1e-6 of their largest magnitude.
`REL` = 1e-4 of the largest logit leaves 100 times that, and the same
path served in bfloat16 reads about 1e-2 (asserted below), so a path that
computed in bfloat16 where float32 is stated fails.
"""
import dataclasses
import functools
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1] / "bench"
sys.path.insert(0, str(BENCH))
from benchlib.spec import load_module  # noqa: E402

from repro.models import afmoe  # noqa: E402
from repro.serving import engine as engine_mod  # noqa: E402
from repro.serving.engine import ReplicaEngine  # noqa: E402

REF = load_module(BENCH / "references" / "afmoe.py")
ADAPT = load_module(BENCH / "adapters" / "afmoe.py")

#: relative to the largest reference logit; see the module docstring
REL = 1e-4
BLOCK, PROMPT, DECODE = 8, 40, 30


def small(dtype="float32", **kw):
    c = json.loads((BENCH / "configs" / "trinity-mini-16of128E.json")
                   .read_text())
    c.update(num_hidden_layers=4, hidden_size=256, num_attention_heads=4,
             num_key_value_heads=2, head_dim=64, intermediate_size=512,
             moe_intermediate_size=128, router_experts=8, num_experts=4,
             num_experts_per_tok=2, num_dense_layers=1, sliding_window=16,
             vocab_size=512, torch_dtype=dtype)
    c.update(kw)
    return c


@functools.lru_cache(maxsize=None)
def model(dtype="float32"):
    c = small(dtype)
    return c, ADAPT.program_config(c), ADAPT.make_params(c, 2**32 + 11)


def prefill(eng, rid, toks, between=None):
    """Prefill quantum by quantum; `between()` runs at every boundary."""
    st = eng.start_prefill(rid, jnp.asarray(toks[None]))
    done = False
    while not done:
        st, done = eng.prefill_quantum(st)
        if between is not None and not done:
            between()
    return st


def serve(dtype="float32", impl=None):
    """Prefill a prompt, then decode greedily through the ring; returns the
    token sequence and the logits at each served position."""
    _, cfg, params = model(dtype)
    toks = np.random.default_rng(5).integers(0, 512, PROMPT).astype(np.int32)
    eng = ReplicaEngine(cfg, params, max_slots=2, max_len=96,
                        block_size=BLOCK)
    st = prefill(eng, 0, toks)
    out = [np.asarray(eng.prefill_logits(st), np.float32)[0]]
    slot = eng.admit(0, st)
    seq, tok = list(toks), int(out[0].argmax())
    decode = (engine_mod._decode_afmoe if impl is None else
              functools.partial(engine_mod._decode_afmoe, impl=impl))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine_mod, "_decode_afmoe", decode)
        for _ in range(DECODE):
            seq.append(tok)
            lg = np.asarray(eng.decode_logits({slot: tok}), np.float32)[slot]
            out.append(lg)
            tok = int(lg.argmax())
    return np.asarray(seq), np.stack(out)


def reference(seq, dtype="float32"):
    c, _, params = model(dtype)
    return REF.logits(c, params, seq, np.arange(PROMPT - 1, len(seq)))


def rel_err(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.mark.parametrize("impl", [None, "pallas_interpret"])
def test_prefill_then_ring_decode_matches_the_reference(impl):
    """Prefill in 2-layer quanta, then 30 decode steps whose windowed
    layers wrap their 3-block ring, against the reference's full forward
    over the same tokens; `impl` None is the XLA gather the engine picks
    here, "pallas_interpret" the windowed Pallas kernel."""
    seq, got = serve(impl=impl)
    assert len(seq) - BLOCK * 3 > 16          # the ring wrapped
    assert rel_err(got, reference(seq)) < REL


def test_bfloat16_fails_the_float32_tolerance():
    """The tolerance tells float32 from bfloat16: the same path served in
    bfloat16 departs from the reference by far more than `REL`."""
    seq, got = serve("bfloat16")
    assert rel_err(got, reference(seq, "bfloat16")) > 20 * REL


def test_prefill_preempted_at_every_quantum_resumes_to_the_same_logits():
    """§5.1: a prefill suspended at every quantum boundary while another
    request's prefill runs on the engine resumes to bit-identical logits
    and KV."""
    _, cfg, params = model()
    toks = np.arange(PROMPT, dtype=np.int32) * 7 % 512
    other = np.arange(24, dtype=np.int32) * 3 % 512
    eng = ReplicaEngine(cfg, params, max_slots=2, max_len=96,
                        block_size=BLOCK)
    straight = prefill(eng, 0, toks)
    interrupted = prefill(eng, 1, toks,
                          between=lambda: prefill(eng, 2, other))
    np.testing.assert_array_equal(np.asarray(eng.prefill_logits(straight)),
                                  np.asarray(eng.prefill_logits(interrupted)))
    for a, b in zip(straight.kv_k + straight.kv_v,
                    interrupted.kv_k + interrupted.kv_v):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_admit_moves_only_ring_blocks_for_windowed_layers():
    """§5.2 migration of a 40-token prompt with a 16-token window: the full
    layer gets its 5 blocks (and the decode reservation), each windowed
    layer the 16 last positions in a ring of 3 blocks, and what the pool
    then holds is the prefill's KV there."""
    _, cfg, params = model()
    toks = np.arange(PROMPT, dtype=np.int32)
    eng = ReplicaEngine(cfg, params, max_slots=2, max_len=96,
                        block_size=BLOCK)
    st = prefill(eng, 0, toks)
    win = cfg.window_layers
    assert [st.kv_k[i].shape[2] for i in range(4)] == [
        16 if i in win else PROMPT for i in range(4)]
    k, v, wk, wv = eng.assemble_kv(st)
    assert k.shape[0] == 1 and wk.shape[:3] == (3, 2, 16)
    pool = eng.kvpool
    free, wfree = len(pool.free), len(pool.wfree)
    eng.admit(0, st)
    ring = pool.rings[0]
    assert len(ring) == pool.ring == 3 == wfree - len(pool.wfree)
    assert len(pool.tables[0]) == 96 // BLOCK == free - len(pool.free)
    gk, _ = pool.gather(0)
    for i in range(4):
        want = np.asarray(st.kv_k[i][0])
        n = want.shape[1]
        np.testing.assert_array_equal(np.asarray(gk[i, :, PROMPT - n:]),
                                      want)
    eng.evict(0)
    assert (len(pool.free), len(pool.wfree)) == (free, wfree)


def test_decode_parked_and_rescattered_continues_bit_identical():
    """A decode lane preempted after its ring wrapped: the request's KV is
    gathered to the host, its blocks released, then scattered back and
    bound to a slot (the backend's park path); its next tokens and logits
    equal those of the uninterrupted decode."""
    _, cfg, params = model()
    toks = np.arange(PROMPT, dtype=np.int32) * 5 % 512

    def run(park_at):
        eng = ReplicaEngine(cfg, params, max_slots=2, max_len=96,
                            block_size=BLOCK)
        st = prefill(eng, 0, toks)
        tok = int(np.asarray(eng.prefill_logits(st))[0].argmax())
        slot, out = eng.admit(0, st), []
        for i in range(DECODE):
            if i == park_at:
                k, v = eng.kvpool.gather(0)
                k, v = np.asarray(k), np.asarray(v)
                eng.evict(slot)
                assert not eng.resident(0)
                eng.scatter_kv(0, jnp.asarray(k), jnp.asarray(v))
                slot = eng.bind_slot(0)
            lg = np.asarray(eng.decode_logits({slot: tok}))[slot]
            out.append(lg)
            tok = int(lg.argmax())
        return np.stack(out)
    straight = run(None)
    assert PROMPT + 20 - BLOCK * 3 > 16            # the ring wrapped
    np.testing.assert_array_equal(run(20), straight)



# ---------------------------------------------------------------------------
# the expert share
# ---------------------------------------------------------------------------
def _plain_moe(cfg, pm, j, h):
    """The uncut MoE layer j in float64 numpy: every routed expert, the
    shared one once."""
    h = np.asarray(h, np.float64)
    f = {k: np.asarray(a, np.float64) for k, a in pm.items() if k != "shared"}
    s = 1.0 / (1.0 + np.exp(-h @ f["router"][j]))
    idx = np.argsort(-(s + f["bias"][j]), -1)[:, :cfg.experts_per_token]
    w = np.take_along_axis(s, idx, -1)
    w = w / w.sum(-1, keepdims=True) * cfg.route_scale

    def swiglu(x, g, u, d):
        a = x @ g
        return (a / (1.0 + np.exp(-a)) * (x @ u)) @ d
    y = np.zeros_like(h)
    for t in range(h.shape[0]):
        for k in range(cfg.experts_per_token):
            e = idx[t, k]
            y[t] += w[t, k] * swiglu(h[t], f["w_gate"][j, e], f["w_up"][j, e],
                                     f["w_down"][j, e])
    sh = {k: np.asarray(a, np.float64) for k, a in pm["shared"].items()}
    return y + swiglu(h, sh["w_gate"][j], sh["w_up"][j], sh["w_down"][j])


@pytest.mark.parametrize("rows,bias", [(6, 0.1), (160, 0.1), (160, 3.0)])
def test_expert_shares_sum_to_the_uncut_layer(rows, bias):
    """Four chips' shares of 2 of 8 experts each: their routed parts, with
    the shared expert (which every chip computes alike) counted once, add
    up to the uncut layer.  160 rows take the gathered path (capacity 56
    of 160); a large expert bias skews routing past the capacity, into the
    dense path."""
    c = small(num_experts=8)
    cfg = ADAPT.program_config(c)
    params = ADAPT.make_params(c, 3)
    pm = dict(params["moe"])
    pm["bias"] = pm["bias"] * (bias / 0.1)
    h = jax.random.normal(jax.random.key(1), (rows, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        sh = pm["shared"]
        shared = afmoe._swiglu(h, sh["w_gate"][1], sh["w_up"][1],
                               sh["w_down"][1])
        total = -3 * shared                 # 4 shares, the shared part once
        held = 0
        for off in range(0, 8, 2):
            share = dataclasses.replace(cfg, experts_held=2,
                                        expert_offset=off)
            mine = dict(pm, **{k: pm[k][:, off:off + 2]
                               for k in ("w_gate", "w_up", "w_down")})
            y, n = afmoe.expert_share(share, mine, 1, h)
            total = total + y
            held += int(n)
    assert held == rows * cfg.experts_per_token
    want = _plain_moe(cfg, pm, 1, h)
    np.testing.assert_allclose(np.asarray(total), want, rtol=1e-4,
                               atol=1e-4 * np.abs(want).max())
    assert afmoe.capacity(cfg, 160) < 160


def test_served_through_pecsched_and_never_ganged():
    """The normal path, make_policy("pecsched") -> Simulator ->
    EngineBackend -> ReplicaEngine: shorts and longs of the afmoe model
    all finish with their tokens, greedy as the engine alone serves them,
    and gang SP stays with the dense family."""
    from repro.serving import MiniCluster, ServeRequest
    _, cfg, params = model()
    mc = MiniCluster(cfg, params, n_engines=2, max_len=96, max_slots=2,
                     long_threshold=32, target_prefill_s=1e-12)
    assert not mc.backend.enable_sp
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 512, n).astype(np.int32)
               for n in (12, 48, 20, 64, 8)]
    for i, p in enumerate(prompts):
        mc.submit(ServeRequest(rid=i, arrival=0.01 * i, tokens=p, max_new=4))
    mc.run()
    assert len(mc.done) == len(prompts)
    eng = ReplicaEngine(cfg, params, max_slots=1, max_len=96,
                        block_size=16)
    for r in mc.done:
        st = prefill(eng, 99, prompts[r.rid])
        tok = int(np.asarray(eng.prefill_logits(st))[0].argmax())
        slot, want = eng.admit(99, st), [tok]
        for _ in range(3):
            tok = eng.decode_iteration({slot: tok})[slot]
            want.append(tok)
        eng.evict(slot)
        assert r.generated == want
