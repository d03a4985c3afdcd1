"""Per-kernel allclose sweeps: Pallas (interpret=True) and the XLA chunked
paths vs the pure-jnp oracles in kernels/ref.py. Property-style: seeded
randomized shape/dtype sweeps (hypothesis is unavailable offline)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode, paged_flash_decode
from repro.kernels.ssd_kernel import ssd_scan_pallas

RNG = np.random.default_rng(42)


def t(*s, dtype=jnp.float32):
    return jnp.asarray(RNG.normal(size=s), dtype)


ATTN_CASES = [
    # b, h, kv, sq, sk, d, causal, win, qoff
    (1, 2, 1, 32, 32, 16, True, 0, 0),
    (2, 4, 2, 40, 40, 8, True, 0, 0),
    (1, 4, 4, 17, 64, 8, False, 0, 0),
    (2, 2, 2, 32, 32, 8, True, 12, 0),
    (1, 2, 2, 8, 64, 8, True, 0, 56),
    (1, 8, 1, 24, 24, 32, True, 0, 0),     # MQA
    (3, 6, 3, 9, 33, 16, True, 7, 0),      # uneven
]


@pytest.mark.parametrize("case", ATTN_CASES)
def test_flash_attention_matches_reference(case):
    b, h, kv, sq, sk, d, causal, win, qo = case
    q, k, v = t(b, h, sq, d), t(b, kv, sk, d), t(b, kv, sk, d)
    kvl = jnp.asarray(RNG.integers(max(sq, 1), sk + 1, size=b), jnp.int32)
    want = ref.mha_reference(q, k, v, causal=causal, sliding_window=win,
                             q_offset=qo, kv_len=kvl)
    got = flash_attention(q, k, v, causal=causal, sliding_window=win,
                          q_offset=qo, kv_len=kvl, block_q=16, block_k=16,
                          interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("case", ATTN_CASES)
def test_xla_attention_matches_reference(case):
    b, h, kv, sq, sk, d, causal, win, qo = case
    q, k, v = t(b, h, sq, d), t(b, kv, sk, d), t(b, kv, sk, d)
    want = ref.mha_reference(q, k, v, causal=causal, sliding_window=win,
                             q_offset=qo)
    got = ops.xla_attention(q, k, v, causal=causal, sliding_window=win,
                            q_offset=qo, q_block=8, kv_block=8)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_flash_attention_bf16():
    q, k, v = (t(2, 4, 32, 16, dtype=jnp.bfloat16) for _ in range(3))
    want = ref.mha_reference(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


def test_xla_attention_lse_merge_property():
    """Splitting KV into two halves and LSE-merging == full attention."""
    from repro.sp.common import finalize, merge_partials
    b, h, s, d = 2, 4, 32, 16
    q, k, v = t(b, h, s, d), t(b, h, s, d), t(b, h, s, d)
    o1, l1 = ops.xla_attention(q, k[:, :, :16], v[:, :, :16], causal=True,
                               q_offset=0, return_lse=True)
    o2, l2 = ops.xla_attention(q, k[:, :, 16:], v[:, :, 16:], causal=True,
                               q_offset=-16, return_lse=True)
    o, lse = merge_partials(o1.astype(jnp.float32), l1,
                            o2.astype(jnp.float32), l2)
    want = ref.mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(finalize(o, lse, q.dtype), want, atol=2e-5)


@pytest.mark.parametrize("win,bk", [(0, 16), (0, 8), (24, 16)])
def test_flash_decode_matches_reference(win, bk):
    b, h, kv, s, d = 3, 8, 2, 64, 16
    q, k, v = t(b, h, d), t(b, kv, s, d), t(b, kv, s, d)
    cl = jnp.asarray([5, 33, 64], jnp.int32)
    want = ref.decode_attention_reference(q, k, v, cl, sliding_window=win)
    got = flash_decode(q, k, v, cl, sliding_window=win, block_k=bk,
                       interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


PAGED_CASES = [
    # h, kv, page, pages per sequence, pages per step, lengths, window,
    # ring (0: the table lists a sequence's pages in order)
    (8, 2, 4, 8, 4, (5, 0, 31, 16), 0, 0),     # 4 q heads per KV head
    (14, 2, 8, 4, 2, (1, 32, 17), 0, 0),       # 7
    (4, 4, 4, 6, 6, (24, 3), 0, 0),            # MHA, one step per sequence
    (8, 2, 4, 8, 4, (5, 30), 6, 0),            # a window
    (8, 2, 4, 4, 2, (5, 30, 9, 12), 6, 3),     # a window over a 3-page ring
]


@pytest.mark.parametrize("case,impl", [
    (c, impl) for c in PAGED_CASES for impl in ("xla", "pallas_interpret")])
def test_paged_decode_matches_reference_on_gathered_pages(case, impl):
    """Attention through a page table into a shared pool == the reference
    over each sequence's pages gathered in order (with a ring, logical
    page j from entry j % ring); a length of 0 gives zeros from the
    kernel."""
    h, kv, page, n, per_step, lens, win, ring = case
    d, n_pool = 16, 40
    b = len(lens)
    kp, vp = t(n_pool, kv, page, d), t(n_pool, kv, page, d)
    pages = jnp.asarray(np.stack([RNG.permutation(n_pool)[:n]
                                  for _ in range(b)]), jnp.int32)
    q = t(b, h, d)
    cl = jnp.asarray(lens, jnp.int32)
    n_log = -(-max(lens) // page) if ring else n
    logical = pages[:, np.arange(n_log) % ring] if ring else pages
    k = kp[logical].transpose(0, 2, 1, 3, 4).reshape(b, kv, n_log * page, d)
    v = vp[logical].transpose(0, 2, 1, 3, 4).reshape(b, kv, n_log * page, d)
    want = np.asarray(ref.decode_attention_reference(q, k, v, cl,
                                                     sliding_window=win))
    if impl == "xla":
        got = ops.paged_decode_attention(q, kp, vp, pages, cl,
                                         sliding_window=win, ring=ring,
                                         impl="xla")
    else:
        starts = jnp.maximum(cl - win, 0) if win else None
        got = paged_flash_decode(q, kp, vp, pages, cl, starts, ring=ring,
                                 pages_per_step=per_step, interpret=True)
    got = np.asarray(got)
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(got[live], want[live], atol=2e-5, rtol=2e-5)
    if impl != "xla":
        assert not got[~live].any()


@pytest.mark.parametrize("chunk,seq", [(16, 64), (32, 96), (32, 70), (8, 8)])
def test_ssd_pallas_matches_reference(chunk, seq):
    b, nh, hd, ns = 2, 4, 8, 16
    x = t(b, seq, nh, hd)
    dt = jax.nn.softplus(t(b, seq, nh))
    A = -jnp.exp(t(nh))
    B, C, D = t(b, seq, ns), t(b, seq, ns), t(nh)
    h0 = t(b, nh, hd, ns) * 0.1
    want, hw = ref.ssd_reference(x, dt, A, B, C, D, init_state=h0,
                                 return_state=True)
    got, hg = ssd_scan_pallas(x, dt, A, B, C, D, chunk=chunk, init_state=h0,
                              return_state=True, interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(hg, hw, atol=2e-3, rtol=2e-3)


def test_ssd_chunked_xla_matches_reference():
    b, s, nh, hd, ns = 2, 64, 4, 8, 16
    x = t(b, s, nh, hd)
    dt = jax.nn.softplus(t(b, s, nh))
    A = -jnp.exp(t(nh))
    B, C, D = t(b, s, ns), t(b, s, ns), t(nh)
    want = ref.ssd_reference(x, dt, A, B, C, D)
    got = ops.ssd_scan(x, dt, A, B, C, D, chunk=16, impl="xla")
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)


def test_ssd_step_chain_equals_scan():
    """Decode-step recurrence chained == full scan (serving invariant)."""
    b, s, nh, hd, ns = 2, 12, 4, 8, 16
    x = t(b, s, nh, hd)
    dt = jax.nn.softplus(t(b, s, nh))
    A = -jnp.exp(t(nh))
    B, C, D = t(b, s, ns), t(b, s, ns), t(nh)
    want = ref.ssd_reference(x, dt, A, B, C, D)
    state = jnp.zeros((b, nh, hd, ns))
    outs = []
    for i in range(s):
        y, state = ops.ssd_step(x[:, i], dt[:, i], A, B[:, i], C[:, i], D, state)
        outs.append(y)
    np.testing.assert_allclose(jnp.stack(outs, 1), want, atol=2e-3)


def test_attention_gradient_finite():
    """Checkpointed chunked attention must be differentiable and finite."""
    q, k, v = t(1, 2, 16, 8), t(1, 2, 16, 8), t(1, 2, 16, 8)

    def loss(q):
        return ops.xla_attention(q, k, v, causal=True, q_block=8,
                                 kv_block=8).sum()
    g = jax.grad(loss)(q)
    assert np.isfinite(np.asarray(g)).all()
    # matches gradient of the naive reference
    g_ref = jax.grad(lambda q: ref.mha_reference(q, k, v, causal=True).sum())(q)
    np.testing.assert_allclose(g, g_ref, atol=2e-4, rtol=2e-4)
