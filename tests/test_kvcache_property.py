"""Hypothesis property test for the PagedKVCache, mirroring the EventHeap
suite (tests/test_heap_property.py): random admit/append_token/release
sequences run against a plain dict-of-arrays reference model.

Checked on every step:

* gather round-trips exactly — the paged layout is storage, never math;
* `can_admit` never lies: True -> admit succeeds, False -> admit raises;
* block accounting conserves the pool (free + allocated == n_blocks);
* utilization and fragmentation match the reference formulas;
* duplicate admits / appends to absent rids raise, and a release returns
  every block.

The same holds for a pool of two kinds (`test_two_kind_pool_...`): full
layers beside windowed ones whose rings wrap, where gather reads the
window's positions exactly and the older ones as zeros, and both kinds'
blocks are conserved.
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis",
                    reason="optional dep: pip install -r requirements-dev.txt")
from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

import jax.numpy as jnp

from repro.serving.kvcache import PagedKVCache

SET = dict(deadline=None, max_examples=60,
           suppress_health_check=[HealthCheck.too_slow])

L, KV, HD = 2, 1, 2          # tiny shapes: the properties are layout-level
BLOCK = 4
N_BLOCKS = 10
OPS = ("admit", "admit", "append", "append", "release", "gather")


def _kv_for(rid: int, start: int, n: int):
    """Deterministic distinguishable values: token t of rid r gets value
    r*1000 + t in every (layer, head, dim) position."""
    vals = rid * 1000 + np.arange(start, start + n, dtype=np.float32)
    k = np.broadcast_to(vals[None, None, :, None], (L, KV, n, HD))
    return jnp.asarray(k), jnp.asarray(k + 0.5)


@settings(**SET)
@given(data=st.data())
def test_paged_kvcache_matches_reference_model(data):
    pc = PagedKVCache.create(L, N_BLOCKS, KV, BLOCK, HD, dtype=jnp.float32)
    ref = {}                     # rid -> token count
    next_rid = 0
    for _ in range(data.draw(st.integers(5, 40), label="n_ops")):
        op = data.draw(st.sampled_from(OPS), label="op")
        if op == "admit":
            n = data.draw(st.integers(1, 2 * BLOCK + 1), label="admit_len")
            need = -(-n // BLOCK)
            can = pc.can_admit(n)
            assert can == (len(pc.free) >= need), "can_admit lied"
            k, v = _kv_for(next_rid, 0, n)
            if not can:
                with pytest.raises(MemoryError):
                    pc.admit(next_rid, k, v)
                continue
            pc.admit(next_rid, k, v)
            ref[next_rid] = n
            next_rid += 1
        elif op == "append":
            if not ref:
                continue
            rid = data.draw(st.sampled_from(sorted(ref)), label="append_rid")
            pos = ref[rid]
            if pos % BLOCK == 0 and not pc.free:     # needs a fresh block
                with pytest.raises(MemoryError):
                    pc.append_token(rid, *[a[:, :, 0] for a in _kv_for(rid, pos, 1)])
                continue
            k, v = _kv_for(rid, pos, 1)
            pc.append_token(rid, k[:, :, 0], v[:, :, 0])
            ref[rid] = pos + 1
        elif op == "release":
            if not ref:
                continue
            rid = data.draw(st.sampled_from(sorted(ref)), label="release_rid")
            pc.release(rid)
            del ref[rid]
        else:                                        # gather round-trip
            if not ref:
                continue
            rid = data.draw(st.sampled_from(sorted(ref)), label="gather_rid")
            k, v = pc.gather(rid)
            want_k, want_v = _kv_for(rid, 0, ref[rid])
            np.testing.assert_array_equal(np.asarray(k), np.asarray(want_k))
            np.testing.assert_array_equal(np.asarray(v), np.asarray(want_v))

        # ---- invariants, every step ----------------------------------
        allocated = sum(len(b) for b in pc.tables.values())
        assert allocated + len(pc.free) == N_BLOCKS, "pool leaked blocks"
        assert len(set(pc.free)) == len(pc.free), "duplicate free block"
        for rid, blocks in pc.tables.items():
            assert not (set(blocks) & set(pc.free)), "block both free+used"
            assert len(blocks) * BLOCK >= ref[rid], "table too small"
        assert pc.lengths == ref
        # block-based occupancy (opaque admits: nothing parks in `cached`)
        assert pc.utilization() == pytest.approx(allocated / N_BLOCKS)
        assert pc.written_tokens() == sum(ref.values())
        assert pc.reserved_tokens() == 0
        if allocated:
            assert pc.fragmentation() == pytest.approx(
                1.0 - sum(ref.values()) / (allocated * BLOCK))
        else:
            assert pc.fragmentation() == 0.0

    # drain: every gather still exact, then release everything
    for rid in sorted(ref):
        k, v = pc.gather(rid)
        want_k, _ = _kv_for(rid, 0, ref[rid])
        np.testing.assert_array_equal(np.asarray(k), np.asarray(want_k))
        pc.release(rid)
    assert sorted(pc.free) == list(range(N_BLOCKS))
    assert pc.utilization() == 0.0


def test_reserve_grows_table_without_writing():
    """`reserve` pre-allocates growth room (the engine's decode-lane
    budget): appends inside the reservation never allocate, gather still
    returns only the written tokens, release returns everything."""
    pc = PagedKVCache.create(L, 6, KV, BLOCK, HD, dtype=jnp.float32)
    k, v = _kv_for(0, 0, 3)
    pc.admit(0, k, v)                       # 1 data block
    pc.reserve(0, 3 * BLOCK)                # grow to 3 blocks
    assert len(pc.tables[0]) == 3 and len(pc.free) == 3
    pc.reserve(0, 2 * BLOCK)                # shrinking request: no-op
    assert len(pc.tables[0]) == 3
    with pytest.raises(MemoryError):        # beyond the pool: refused whole
        pc.reserve(0, 100 * BLOCK)
    assert len(pc.tables[0]) == 3
    free_before = len(pc.free)
    for i in range(3 * BLOCK - 3):          # fill the reservation
        kt, vt = _kv_for(0, 3 + i, 1)
        pc.append_token(0, kt[:, :, 0], vt[:, :, 0])
    assert len(pc.free) == free_before      # no allocation inside it
    kk, _ = pc.gather(0)
    want, _ = _kv_for(0, 0, 3 * BLOCK)
    np.testing.assert_array_equal(np.asarray(kk), np.asarray(want))
    pc.release(0)
    assert sorted(pc.free) == list(range(6))


def test_duplicate_admit_and_absent_rid_raise():
    pc = PagedKVCache.create(L, 4, KV, BLOCK, HD, dtype=jnp.float32)
    k, v = _kv_for(0, 0, 3)
    pc.admit(0, k, v)
    with pytest.raises(KeyError):
        pc.admit(0, k, v)
    with pytest.raises(KeyError):
        pc.append_token(99, k[:, :, 0], v[:, :, 0])
    with pytest.raises(KeyError):
        pc.gather(99)


# ---------------------------------------------------------------------------
# two kinds: layer 1 full, layers 0 and 2 keep rings of a 6-token window
# ---------------------------------------------------------------------------
L2, WIN, WINDOW = 3, (0, 2), 6
N_RING = 8                                # rings of ceil(6 / 4) + 1 = 3


def _kv3(rid: int, start: int, n: int):
    vals = rid * 1000 + np.arange(start, start + n, dtype=np.float32)
    vals = vals[None] + 100.0 * np.arange(L2, dtype=np.float32)[:, None]
    k = np.broadcast_to(vals[:, None, :, None], (L2, KV, n, HD))
    return jnp.asarray(k), jnp.asarray(k + 0.5)


@settings(**SET)
@given(data=st.data())
def test_two_kind_pool_matches_reference_model(data):
    pc = PagedKVCache.create(L2, N_BLOCKS, KV, BLOCK, HD, dtype=jnp.float32,
                             window_layers=WIN, window=WINDOW,
                             n_window_blocks=N_RING)
    ring = pc.ring
    ref = {}
    next_rid = 0
    for _ in range(data.draw(st.integers(5, 40), label="n_ops")):
        op = data.draw(st.sampled_from(OPS + ("reserve",)), label="op")
        if op == "admit":
            n = data.draw(st.integers(1, 3 * BLOCK + 1), label="admit_len")
            can = pc.can_admit(n)
            assert can == (len(pc.free) >= -(-n // BLOCK) and len(
                pc.wfree) >= min(-(-n // BLOCK), ring)), "can_admit lied"
            k, v = _kv3(next_rid, 0, n)
            if not can:
                with pytest.raises(MemoryError):
                    pc.admit(next_rid, k, v)
                continue
            pc.admit(next_rid, k, v)
            ref[next_rid] = n
            next_rid += 1
        elif op == "append":
            if not ref:
                continue
            rid = data.draw(st.sampled_from(sorted(ref)), label="append_rid")
            pos = ref[rid]
            grow_full = pos // BLOCK >= len(pc.tables[rid])
            grow_ring = (pos // BLOCK) % ring >= len(pc.rings[rid])
            if (grow_full and not pc.free) or (grow_ring and not pc.wfree):
                continue                 # the engine reserves: never here
            k, v = _kv3(rid, pos, 1)
            pc.append_token(rid, k[:, :, 0], v[:, :, 0])
            ref[rid] = pos + 1
        elif op == "reserve":
            if not ref:
                continue
            rid = data.draw(st.sampled_from(sorted(ref)), label="reserve_rid")
            cap = ref[rid] + data.draw(st.integers(0, 2 * BLOCK))
            try:
                pc.reserve(rid, cap)
            except MemoryError:
                pass
        elif op == "release":
            if not ref:
                continue
            rid = data.draw(st.sampled_from(sorted(ref)), label="release_rid")
            pc.release(rid)
            del ref[rid]
        else:
            if not ref:
                continue
            rid = data.draw(st.sampled_from(sorted(ref)), label="gather_rid")
            n = ref[rid]
            k, _ = pc.gather(rid)
            want, _ = _kv3(rid, 0, n)
            k, want = np.asarray(k), np.asarray(want)
            lo = max(0, n - WINDOW)
            np.testing.assert_array_equal(k[1], want[1])
            np.testing.assert_array_equal(k[::2, :, lo:], want[::2, :, lo:])
            assert not k[::2, :, :lo].any()

        # ---- invariants, every step ----------------------------------
        used = sum(len(b) for b in pc.tables.values())
        rused = sum(len(r) for r in pc.rings.values())
        assert used + len(pc.free) == N_BLOCKS, "pool leaked blocks"
        assert rused + len(pc.wfree) == N_RING, "rings leaked blocks"
        assert len(set(pc.wfree)) == len(pc.wfree)
        for rid, r in pc.rings.items():
            assert not (set(r) & set(pc.wfree)), "ring block both free+used"
            assert min(-(-ref[rid] // BLOCK), ring) <= len(r) <= ring
            assert len(pc.tables[rid]) * BLOCK >= ref[rid]
        assert pc.lengths == ref

    for rid in sorted(ref):
        pc.release(rid)
    assert sorted(pc.free) == list(range(N_BLOCKS))
    assert sorted(pc.wfree) == list(range(N_RING))
