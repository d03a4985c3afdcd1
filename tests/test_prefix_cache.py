"""Prefix-cache correctness: cache-hit equivalence, refcount conservation, COW.

Three layers, mirroring the subsystem's own stack:

* **Engine** — a cache-hit suffix prefill (`lookup_cached_prefix` ->
  `start_prefill(prefix_k/v)` -> `admit` -> greedy decode) must give the
  same greedy decode tokens as a from-scratch prefill of the same prompt,
  with logits equal to float32 tolerance (`LOGIT_TOL`).  The suffix
  program is not the full-prefill program — it attends over the prefix KV
  concatenated in front at a query offset — so XLA tiles its reductions
  differently and bitwise equality is out of contract even when the
  donor ran the same sequence shape.  Swept across block-boundary and
  partial-tail prefix lengths and across donor shapes (deterministically;
  a hypothesis-randomized twin runs when the optional dep is installed).

* **Pool** — block refcounts conserve the pool under shared admits,
  copy-on-write appends, reserve headroom and LRU cache eviction: every
  block is in exactly one of {blank-free, cached-parked, referenced}.

* **Policy** — `pecsched/cache` actually consults its residency map
  (counters move, durations shrink) and `PrefixResidency` honours its LRU
  group bound.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.models import init_params
from repro.serving.engine import ReplicaEngine
from repro.serving.kvcache import PagedKVCache

BLOCK = 8
#: float32 logits of order 1 after 2 layers at d_model 256: reduction
#: reordering between the suffix and full-prefill programs moves them by
#: about 1e-6 (2e-6 the largest seen); a logic fault (wrong position, stale
#: or missing prefix KV) moves them by O(0.1)
LOGIT_TOL = 1e-4


@pytest.fixture(scope="module")
def engine():
    cfg = dataclasses.replace(
        reduced_config(get_config("llama3_8b"), layers=2),
        dtype="float32", sliding_window=0)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, ReplicaEngine(cfg, params, max_len=96, block_size=BLOCK)


def _full_prefill(eng, rid, toks):
    st = eng.start_prefill(rid, jnp.asarray(toks)[None],
                           host_tokens=tuple(int(x) for x in toks))
    done = False
    while not done:
        st, done = eng.prefill_quantum(st)
    return st


def _greedy(eng, slot, first, n):
    out, tok = [first], first
    for _ in range(n):
        tok = eng.decode_iteration({slot: tok})[slot]
        out.append(tok)
    return out


def _kv_of(st):
    return jnp.stack(st.kv_k, 0)[:, 0], jnp.stack(st.kv_v, 0)[:, 0]


def _run_cache_vs_scratch(eng, a, b, want_hit):
    """Decode `b` from scratch, then again through a cache hit against
    `a`'s parked KV: greedy tokens must match and logits agree to
    `LOGIT_TOL`."""
    # from-scratch reference FIRST, then forget it (its own blocks would
    # otherwise satisfy the lookup and mask the a-vs-b reuse under test)
    st = _full_prefill(eng, 100, b)
    ref_logits = eng.prefill_logits(st)
    slot = eng.admit(100, st)
    ref_toks = _greedy(eng, slot, int(jnp.argmax(ref_logits[0])), 4)
    eng.evict(slot)
    eng.release_kv(100)
    eng.kvpool.drop_cache()

    st_a = _full_prefill(eng, 1, a)
    eng.cache_prompt(1, *_kv_of(st_a), host_tokens=tuple(int(x) for x in a))
    hit, pk, pv = eng.lookup_cached_prefix(tuple(int(x) for x in b))
    assert hit.n_tokens == want_hit
    if want_hit:
        assert pk.shape[2] == want_hit
        st_c = eng.start_prefill(2, jnp.asarray(b)[None], prefix_k=pk,
                                 prefix_v=pv,
                                 host_tokens=tuple(int(x) for x in b))
    else:
        st_c = _full_prefill(eng, 2, b)
    done = False
    while not done:
        st_c, done = eng.prefill_quantum(st_c)
    logits = eng.prefill_logits(st_c)
    np.testing.assert_allclose(np.asarray(ref_logits), np.asarray(logits),
                               atol=LOGIT_TOL, rtol=LOGIT_TOL)
    slot = eng.admit(2, st_c)
    toks = _greedy(eng, slot, int(jnp.argmax(logits[0])), 4)
    assert toks == ref_toks, "cache-hit decode diverged"
    eng.clear()


@pytest.mark.parametrize("shared,total", [
    (BLOCK, 44),             # exactly one block shared
    (2 * BLOCK + 5, 44),     # partial tail: hit quantizes down to 2 blocks
    (3 * BLOCK, 44),         # block-aligned multi-block share
])
def test_cache_hit_decode_bit_exact(engine, shared, total):
    """Same-shape donor: reuse gives the from-scratch greedy tokens and
    logits within LOGIT_TOL."""
    cfg, eng = engine
    rng = np.random.default_rng(7)
    a = rng.integers(0, cfg.vocab_size, total)
    b = np.concatenate([a[:shared],
                        rng.integers(0, cfg.vocab_size, total - shared)])
    _run_cache_vs_scratch(eng, a, b, (shared // BLOCK) * BLOCK)


def test_cache_hit_reprompt_whole_prompt_guard_bit_exact(engine):
    """Re-sending a cached prompt verbatim: the lookup must trim the hit
    to leave at least one live suffix token (prefill_logits needs a real
    last-position hidden state) and the result still matches."""
    cfg, eng = engine
    rng = np.random.default_rng(11)
    a = rng.integers(0, cfg.vocab_size, 44)
    _run_cache_vs_scratch(eng, a, a.copy(), 40)


def test_cache_hit_cross_shape_decode_identical(engine):
    """Cross-shape reuse (the chat_multiturn pattern: the donor turn was
    shorter than the consumer): identical greedy decode, logits within
    LOGIT_TOL."""
    cfg, eng = engine
    rng = np.random.default_rng(13)
    a = rng.integers(0, cfg.vocab_size, 40)
    b = np.concatenate([a[:24], rng.integers(0, cfg.vocab_size, 20)])
    _run_cache_vs_scratch(eng, a, b, 24)


def test_cache_hit_bit_exact_random_lengths(engine):
    """Hypothesis twin of the deterministic sweep: random same-shape
    shared/suffix splits around block boundaries."""
    pytest.importorskip(
        "hypothesis", reason="optional dep: pip install hypothesis")
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    cfg, eng = engine

    @settings(deadline=None, max_examples=10,
              suppress_health_check=[HealthCheck.too_slow])
    @given(shared=st.integers(1, 43))
    def prop(shared):
        rng = np.random.default_rng(shared)
        a = rng.integers(0, cfg.vocab_size, 44)
        b = np.concatenate([a[:shared],
                            rng.integers(0, cfg.vocab_size, 44 - shared)])
        _run_cache_vs_scratch(eng, a, b, (shared // BLOCK) * BLOCK)

    prop()


# ---------------------------------------------------------------------------
# pool-level refcount conservation + COW
# ---------------------------------------------------------------------------
L, KV, HD = 2, 1, 2
BS = 4
N_BLOCKS = 16


def _pool():
    return PagedKVCache.create(L, N_BLOCKS, KV, BS, HD, dtype=jnp.float32)


def _kv_seq(seed, n):
    vals = seed * 1000 + np.arange(n, dtype=np.float32)
    k = np.broadcast_to(vals[None, None, :, None], (L, KV, n, HD))
    return jnp.asarray(k), jnp.asarray(k + 0.5)


def _assert_conserved(pc):
    """Every physical block is in exactly one of {free, cached, referenced}
    and every live table's blocks carry a positive refcount."""
    free, cached, refd = set(pc.free), set(pc.cached), set(pc.ref)
    assert not (free & cached) and not (free & refd) and not (cached & refd)
    assert free | cached | refd == set(range(pc.n_blocks))
    assert all(n > 0 for n in pc.ref.values())
    for table in pc.tables.values():
        assert set(table) <= refd


def test_shared_admit_refcounts_and_release_parking():
    pc = _pool()
    toks_a = list(range(10))                      # 2 full blocks + tail 2
    pc.admit(0, *_kv_seq(0, 10), tokens=toks_a)
    _assert_conserved(pc)
    toks_b = toks_a[:8] + [91, 92, 93]            # shares the 2 full blocks
    hit = pc.admit(1, *_kv_seq(1, 11), tokens=toks_b)
    assert hit.n_tokens == 8 and len(hit.blocks) == 2
    for b in hit.blocks:
        assert pc.ref[b] == 2                     # shared by both tables
    assert pc.stats["blocks_shared"] == 2
    # sibling tails diverged under the same chain hash: admit-side COW fork
    assert pc.stats["cow_forks"] == 1
    _assert_conserved(pc)
    pc.release(0)                                 # parents drop to ref 1 ...
    for b in hit.blocks:
        assert pc.ref[b] == 1
    _assert_conserved(pc)
    pc.release(1)                                 # ... then park (hash live)
    assert not pc.tables
    assert len(pc.cached) > 0, "registered blocks must park, not vanish"
    _assert_conserved(pc)
    # parked prefix still serves lookups
    assert pc.lookup_prefix(toks_a).n_tokens == 8
    pc.drop_cache()
    assert sorted(pc.free) == list(range(N_BLOCKS))
    assert not pc.cached and not pc.chain and not pc.ref


def test_append_cow_fork_leaves_sharer_untouched():
    """Appending into a block another holder still references must fork a
    private copy (the vLLM copy-on-write rule): the sharer's bytes stay
    bit-identical, the appender sees its own token, the pool conserves."""
    pc = _pool()
    pc.admit(0, *_kv_seq(0, 6), tokens=list(range(6)))   # partial tail block
    last = pc.tables[0][-1]
    pc._acquire(last)            # a concurrent reader pins the tail block
    assert pc.ref[last] == 2
    before_k = np.asarray(pc.k[:, last])
    kt, vt = _kv_seq(0, 7)
    pc.append_token(0, kt[:, :, 6], vt[:, :, 6])
    assert pc.stats["cow_forks"] == 1
    assert pc.tables[0][-1] != last, "append must fork, not write in place"
    assert pc.ref[last] == 1                     # our reference moved off
    np.testing.assert_array_equal(np.asarray(pc.k[:, last]), before_k)
    k, _ = pc.gather(0)
    want_k, _ = _kv_seq(0, 7)
    np.testing.assert_array_equal(np.asarray(k), np.asarray(want_k))
    pc._release_block(last)      # reader unpins
    pc.release(0)
    _assert_conserved(pc)


def test_lru_eviction_prefers_oldest_parked_prefix():
    pc = PagedKVCache.create(L, 4, KV, BS, HD, dtype=jnp.float32)
    pc.admit(0, *_kv_seq(0, 4), tokens=[1, 2, 3, 4])
    pc.admit(1, *_kv_seq(1, 4), tokens=[5, 6, 7, 8])
    pc.release(0)
    pc.release(1)                # both parked; 0's block is older
    assert len(pc.cached) == 2 and len(pc.free) == 2
    pc.admit(2, *_kv_seq(2, 12), tokens=[9] * 12)   # needs 3: evicts oldest
    assert pc.lookup_prefix([1, 2, 3, 4]).n_tokens == 0, "oldest evicted"
    assert pc.lookup_prefix([5, 6, 7, 8]).n_tokens == 4, "newest retained"
    pc.release(2)
    _assert_conserved(pc)


def test_refcount_conservation_random_ops():
    """Deterministic random walk over admit/release/reserve/append with a
    small token universe (so chains genuinely collide and share)."""
    rng = np.random.default_rng(0)
    pc = _pool()
    live = {}
    next_rid = 0
    for step in range(200):
        op = rng.choice(["admit", "release", "append", "reserve"])
        if op == "admit":
            n = int(rng.integers(1, 13))
            toks = [int(x) for x in rng.integers(0, 3, n)]
            can = pc.can_admit(n)        # conservative: assumes no sharing
            try:
                pc.admit(next_rid, *_kv_seq(next_rid, n), tokens=toks)
                live[next_rid] = n
                next_rid += 1
            except MemoryError:
                # only a genuinely tight pool may refuse; a shared prefix
                # is allowed to rescue an admit can_admit() rejected
                assert not can
        elif op == "release" and live:
            rid = int(rng.choice(sorted(live)))
            pc.release(rid)
            del live[rid]
        elif op == "append" and live:
            rid = int(rng.choice(sorted(live)))
            pos = pc.lengths[rid]
            kt, vt = _kv_seq(rid, pos + 1)
            try:
                pc.append_token(rid, kt[:, :, pos], vt[:, :, pos])
                live[rid] = pos + 1
            except MemoryError:
                pass
        elif op == "reserve" and live:
            rid = int(rng.choice(sorted(live)))
            try:
                pc.reserve(rid, pc.lengths[rid] + 2 * BS)
            except MemoryError:
                pass
        _assert_conserved(pc)
        assert pc.written_tokens() == sum(live.values())
    for rid in sorted(live):
        pc.release(rid)
    _assert_conserved(pc)
    pc.drop_cache()
    assert sorted(pc.free) == list(range(N_BLOCKS))


def test_split_accounting_reserved_is_not_fragmentation():
    """The satellite split: utilization (physical blocks), written_tokens
    (live payload), reserved_tokens (on-purpose headroom) and
    fragmentation (partial-tail slack only) answer different questions."""
    pc = _pool()
    pc.admit(0, *_kv_seq(0, 10), tokens=list(range(10)))  # 3 blocks, 2 slack
    assert pc.written_tokens() == 10
    assert pc.reserved_tokens() == 0
    assert pc.utilization() == pytest.approx(3 / N_BLOCKS)
    assert pc.fragmentation() == pytest.approx(1 - 10 / 12)
    pc.reserve(0, 6 * BS)                       # +3 headroom blocks
    assert pc.reserved_tokens() == 3 * BS
    assert pc.utilization() == pytest.approx(6 / N_BLOCKS)
    # headroom must NOT read as fragmentation
    assert pc.fragmentation() == pytest.approx(1 - 10 / 12)
    pc.release(0)                               # registered blocks park ...
    assert pc.written_tokens() == 0
    # ... and parked cache is neither utilization nor fragmentation
    assert pc.utilization() == 0.0
    assert pc.fragmentation() == 0.0
    _assert_conserved(pc)


# ---------------------------------------------------------------------------
# policy-level: residency map + cache policy
# ---------------------------------------------------------------------------
def test_prefix_residency_block_quantized_lru():
    from repro.core.cluster import PrefixResidency
    res = PrefixResidency(2, block_size=16, max_groups=2)
    res.record(0, "g1", 40)                     # 2 full blocks resident
    assert res.cached_tokens(0, "g1", 40) == 32
    assert res.cached_tokens(0, "g1", 20) == 16  # capped by the prefix
    assert res.cached_tokens(1, "g1", 40) == 0   # per-replica
    res.record(0, "g2", 64)
    res.record(0, "g3", 64)                     # bound 2: g1 evicted
    assert res.cached_tokens(0, "g1", 40) == 0
    assert res.cached_tokens(0, "g3", 64) == 64


def test_cache_policy_discounts_and_counts(paper_sim_stack=None):
    import copy

    from repro.core import (Simulator, get_scenario, make_policy,
                            paper_cluster)
    cc, em = paper_cluster("mistral_7b")
    reqs = get_scenario("chat_multiturn", n_requests=600, seed=0)
    base = Simulator(make_policy("pecsched", cc, em)).run(copy.deepcopy(reqs))
    pol = make_policy("pecsched/cache", cc, em)
    cached = Simulator(pol).run(copy.deepcopy(reqs))
    assert cached["prefix_lookups"] > 0
    assert 0 < cached["prefix_hit_rate"] <= 1
    assert cached["prefill_flops_saved"] > 0
    assert pol.prefix_stats["hit_tokens"] > 0
    # reuse must show up as work: long JCT strictly improves on this trace
    assert cached["long_jct_mean"] < base["long_jct_mean"]
    # and the base policy reports inert counters, not missing keys
    assert base["prefix_lookups"] == 0 and base["prefix_hit_rate"] == 0.0
