"""Paged KV cache: allocation correctness + round-trip exactness + an
end-to-end check that paged storage reproduces dense-cache decode."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.kernels import ops
from repro.models import init_params
from repro.serving.engine import ReplicaEngine
from repro.serving.kvcache import PagedKVCache


def test_roundtrip_exact():
    rng = np.random.default_rng(0)
    pc = PagedKVCache.create(n_layers=3, n_blocks=16, kv_heads=2,
                             block_size=8, head_dim=4, dtype=jnp.float32)
    k = jnp.asarray(rng.normal(size=(3, 2, 21, 4)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(3, 2, 21, 4)), jnp.float32)
    pc.admit(7, k, v)
    k2, v2 = pc.gather(7)
    assert jnp.array_equal(k, k2) and jnp.array_equal(v, v2)


def test_append_and_growth():
    rng = np.random.default_rng(1)
    pc = PagedKVCache.create(2, 8, 1, 4, 4, dtype=jnp.float32)
    k0 = jnp.asarray(rng.normal(size=(2, 1, 3, 4)), jnp.float32)
    pc.admit(0, k0, k0)
    appended = []
    for i in range(6):   # crosses a block boundary at 4 and 8
        kt = jnp.asarray(rng.normal(size=(2, 1, 4)), jnp.float32)
        pc.append_token(0, kt, kt)
        appended.append(kt)
    k, v = pc.gather(0)
    assert k.shape[2] == 9
    np.testing.assert_array_equal(k[:, :, :3], k0)
    for i, kt in enumerate(appended):
        np.testing.assert_array_equal(k[:, :, 3 + i], kt)


def test_alloc_release_no_leak():
    pc = PagedKVCache.create(1, 10, 1, 4, 4, dtype=jnp.float32)
    z = jnp.zeros((1, 1, 12, 4), jnp.float32)   # 3 blocks
    for rid in range(3):
        pc.admit(rid, z, z)
    assert len(pc.free) == 1
    assert not pc.can_admit(12)
    with pytest.raises(MemoryError):
        pc.admit(99, z, z)
    for rid in range(3):
        pc.release(rid)
    assert sorted(pc.free) == list(range(10))
    assert pc.utilization() == 0.0


def test_fragmentation_metric():
    pc = PagedKVCache.create(1, 10, 1, 8, 4, dtype=jnp.float32)
    z = jnp.zeros((1, 1, 9, 4), jnp.float32)    # 2 blocks for 9 tokens
    pc.admit(0, z, z)
    assert pc.fragmentation() == pytest.approx(1 - 9 / 16)


def _prefill(eng, rid, toks):
    st = eng.start_prefill(rid, toks)
    done = False
    while not done:
        st, done = eng.prefill_quantum(st)
    return st


def test_release_kv_invalidates_cached_decode_view():
    """Regression: `release_kv` (the slotless cleanup path — gang parks,
    lane retirement outside `evict`) must take the released blocks out of
    what the next decode step reads.  Once a cached dense view kept them;
    now the step's page table must not point at them, and B's continuation
    must be bit-identical to an engine that retired A through `evict`."""
    cfg = dataclasses.replace(
        reduced_config(get_config("mistral_7b"), layers=2),
        dtype="float32", sliding_window=0)
    params = init_params(jax.random.PRNGKey(0), cfg)

    eng = ReplicaEngine(cfg, params, max_slots=2, max_len=64)
    sA = eng.admit(0, _prefill(eng, 0, jnp.arange(1, 9)[None]))
    sB = eng.admit(1, _prefill(eng, 1, jnp.arange(11, 23)[None]))
    out = eng.decode_iteration({sA: 1, sB: 2})
    blocks_a = set(eng.kvpool.tables[0])
    assert blocks_a <= set(np.asarray(eng._page_table()[sA]).tolist())

    eng.slot_rid[sA] = None       # lane retired without going through evict
    eng.release_kv(0)             # ...cleanup releases the blocks directly
    table = np.asarray(eng._page_table())
    assert not blocks_a & set(table[sA].tolist()), \
        "release_kv left the released blocks in the decode page table"
    assert not blocks_a & set(table[sB].tolist())
    assert (table[sA] == eng.scratch).all()

    # B's continuation is bit-identical to an engine that retired A through
    # the normal evict path
    ref = ReplicaEngine(cfg, params, max_slots=2, max_len=64)
    rA = ref.admit(0, _prefill(ref, 0, jnp.arange(1, 9)[None]))
    rB = ref.admit(1, _prefill(ref, 1, jnp.arange(11, 23)[None]))
    ref_out = ref.decode_iteration({rA: 1, rB: 2})
    assert ref_out == out
    ref.evict(rA)
    for _ in range(3):
        lb = eng.decode_logits({sB: out[sB]})[sB]
        ref_lb = ref.decode_logits({rB: ref_out[rB]})[rB]
        assert jnp.array_equal(lb, ref_lb)
        out = {sB: int(jnp.argmax(lb))}
        ref_out = {rB: int(jnp.argmax(ref_lb))}
    kb, _ = eng.kvpool.gather(1)
    ref_kb, _ = ref.kvpool.gather(1)
    assert jnp.array_equal(kb, ref_kb)


def test_paged_equals_dense_decode_attention():
    """Attention over paged-gathered KV == attention over dense KV."""
    rng = np.random.default_rng(2)
    L, KV, S, hd, H = 2, 2, 19, 8, 4
    k = jnp.asarray(rng.normal(size=(L, KV, S, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(L, KV, S, hd)), jnp.float32)
    pc = PagedKVCache.create(L, 12, KV, 8, hd, dtype=jnp.float32)
    pc.admit(0, k, v)
    kg, vg = pc.gather(0)
    q = jnp.asarray(rng.normal(size=(1, H, hd)), jnp.float32)
    cl = jnp.asarray([S], jnp.int32)
    for layer in range(L):
        dense = ops.decode_attention(q, k[layer][None], v[layer][None], cl,
                                     impl="xla")
        paged = ops.decode_attention(q, kg[layer][None], vg[layer][None], cl,
                                     impl="xla")
        np.testing.assert_allclose(dense, paged, atol=1e-6)


# ---------------------------------------------------------------------------
# two kinds of layer: full tables beside rings of the window
# ---------------------------------------------------------------------------
WIN_LAYERS, WINDOW = (0, 1, 2), 16        # layers 0-2 windowed, 3 full


def _two_kind_pool(n_blocks=40, n_ring=12):
    return PagedKVCache.create(4, n_blocks, 1, 8, 2, dtype=jnp.float32,
                               window_layers=WIN_LAYERS, window=WINDOW,
                               n_window_blocks=n_ring)


def _kv(n, start=0, rid=0):
    """Token t of every layer l holds rid * 1000 + l * 100 + t."""
    t = rid * 1000 + np.arange(start, start + n, dtype=np.float32)
    vals = t[None, :] + 100.0 * np.arange(4, dtype=np.float32)[:, None]
    k = np.broadcast_to(vals[:, None, :, None], (4, 1, n, 2))
    return jnp.asarray(k), jnp.asarray(k + 0.5)


def test_two_kinds_count_full_blocks_and_capped_rings():
    """A request holds full_layers x ceil(len / block) plus window_layers x
    min(ceil(len / block), ring) layer-blocks; `can_admit` and `reserve`
    check both kinds, and a release returns both."""
    pc = _two_kind_pool()
    assert pc.ring == 3                         # ceil(16 / 8) + 1
    assert pc.layer_blocks(10) == (1 * 2, 3 * 2)
    assert pc.layer_blocks(100) == (1 * 13, 3 * 3)
    pc.admit(0, *_kv(100))
    assert len(pc.tables[0]) == 13 and len(pc.rings[0]) == 3
    pc.reserve(0, 200)                          # full grows, ring capped
    assert len(pc.tables[0]) == 25 and len(pc.rings[0]) == 3
    assert pc.can_admit(20) and not pc.can_admit(8 * 16)   # full blocks
    pc.admit(1, *_kv(20))
    pc.admit(2, *_kv(20))
    assert len(pc.wfree) == 3 and pc.can_admit(20)
    pc.admit(3, *_kv(20))
    assert not pc.can_admit(1)                  # the rings ran out
    with pytest.raises(MemoryError):
        pc.admit(4, *_kv(1))
    with pytest.raises(MemoryError):
        pc.reserve(3, 10 * 8)
    for rid in range(4):
        pc.release(rid)
    assert sorted(pc.free) == list(range(40))
    assert sorted(pc.wfree) == list(range(12))


def test_a_ring_takes_the_next_positions_in_place():
    """Appends past the ring reuse its blocks: the window's positions read
    back exactly, the older ones as zeros, and no block is allocated."""
    pc = _two_kind_pool()
    pc.admit(0, *_kv(20))
    pc.reserve(0, 64)
    ring = list(pc.rings[0])
    for t in range(20, 50):
        k, v = _kv(1, t)
        pc.append_token(0, k[:, :, 0], v[:, :, 0])
    assert pc.rings[0] == ring and len(pc.wfree) == 12 - 3
    k, _ = pc.gather(0)
    want, _ = _kv(50)
    np.testing.assert_array_equal(np.asarray(k[3]), np.asarray(want[3]))
    np.testing.assert_array_equal(np.asarray(k[:3, :, 50 - WINDOW:]),
                                  np.asarray(want[:3, :, 50 - WINDOW:]))
    assert not np.asarray(k[:3, :, :50 - WINDOW]).any()


def test_a_pool_with_rings_reuses_no_prefix():
    """Windowed layers keep no prefix's KV, so a pool with rings finds no
    prefix for a prefill to skip, though a resident prompt holds it."""
    pc = _two_kind_pool()
    toks = list(range(48))
    pc.admit(0, *_kv(40), tokens=toks[:40])
    hit = pc.lookup_prefix(toks)
    assert hit.total_tokens == 0 and not hit.blocks
    assert pc.stats["lookups"] == 1 and pc.stats["hits"] == 0
