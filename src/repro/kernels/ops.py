"""Jit-friendly kernel wrappers with implementation dispatch.

``impl``:
  "auto"             — Pallas on TPU, XLA elsewhere (CPU tests, dry-run
                       lowering)
  "xla"              — chunked online-softmax attention in pure lax
                       (memory-bounded HLO; what the dry-run lowers so
                       memory_analysis stays sane)
  "pallas"           — the Pallas TPU kernels, always compiled for the chip
  "pallas_interpret" — the same kernels in Pallas interpret mode (CPU
                       validation); only ever by name
  "ref"              — naive full-materialization oracle (small shapes only)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref

NEG_INF = -1e30


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# --------------------------------------------------------------------------
# Chunked (memory-efficient) attention — pure lax, online softmax.
# --------------------------------------------------------------------------
def _attn_block(q, k, v, m, l, acc, qpos, kpos, *, causal, sliding_window,
                kv_len, scale):
    """One (q-block, kv-block) update of online-softmax state.

    Uses true -inf masking so fully-masked rows keep l == 0 / m == -inf —
    required for correct LSE semantics when ring attention merges segments.
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale  # f32
    mask = jnp.ones(s.shape[-2:], dtype=bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if sliding_window > 0:
        mask &= qpos[:, None] - kpos[None, :] < sliding_window
    s = jnp.where(mask[None, None], s, -jnp.inf)
    if kv_len is not None:
        valid = kpos[None, :] < kv_len[:, None]          # (B, bk)
        s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    m_new = jnp.maximum(m, s.max(-1))
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(s - m_safe[..., None])                   # 0 where masked
    corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
    l_new = l * corr + p.sum(-1)
    acc_new = acc * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return m_new, l_new, acc_new


def xla_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, sliding_window: int = 0,
                  q_offset: int = 0, kv_len: Optional[jax.Array] = None,
                  q_block: int = 1024, kv_block: int = 1024,
                  scale: Optional[float] = None,
                  return_lse: bool = False) -> jax.Array:
    """GQA attention, O(block^2) live memory. Shapes as mha_reference.

    return_lse: also return the row log-sum-exp (B, H, Sq) in f32 — the
    merge statistic ring attention needs (-inf for fully-masked rows)."""
    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    n_rep = h // kvh
    scale = scale if scale is not None else d ** -0.5
    q_block = min(q_block, sq)
    kv_block = min(kv_block, sk)
    # pad to block multiples
    sq_p = -(-sq // q_block) * q_block
    sk_p = -(-sk // kv_block) * kv_block
    qf = jnp.pad(q.astype(jnp.float32), ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    kf = jnp.pad(k.astype(jnp.float32), ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
    vf = jnp.pad(v.astype(jnp.float32), ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
    eff_kv_len = jnp.full((b,), sk) if kv_len is None else kv_len
    nq, nk = sq_p // q_block, sk_p // kv_block
    # group q heads with their kv head: (b, kvh, n_rep, s, d)
    qf = qf.reshape(b, kvh, n_rep, sq_p, d)

    def do_q_block(iq):
        qb = jax.lax.dynamic_slice_in_dim(qf, iq * q_block, q_block, axis=3)
        qb = qb.reshape(b, kvh * n_rep, q_block, d)
        qpos = iq * q_block + jnp.arange(q_block) + q_offset

        def kv_step(carry, ik):
            m, l, acc = carry
            kb = jax.lax.dynamic_slice_in_dim(kf, ik * kv_block, kv_block, 2)
            vb = jax.lax.dynamic_slice_in_dim(vf, ik * kv_block, kv_block, 2)
            kb = jnp.repeat(kb, n_rep, axis=1) if n_rep > 1 else kb
            vb = jnp.repeat(vb, n_rep, axis=1) if n_rep > 1 else vb
            kpos = ik * kv_block + jnp.arange(kv_block)
            m, l, acc = _attn_block(qb, kb, vb, m, l, acc, qpos, kpos,
                                    causal=causal, sliding_window=sliding_window,
                                    kv_len=eff_kv_len, scale=scale)
            return (m, l, acc), None

        init = (jnp.full((b, h, q_block), -jnp.inf),
                jnp.zeros((b, h, q_block)),
                jnp.zeros((b, h, q_block, d)))
        (m, l, acc), _ = jax.lax.scan(kv_step, init, jnp.arange(nk))
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), -jnp.inf)
        return o, lse

    # checkpointed per-q-block column: the backward recomputes each column
    # (flash-attention-style) instead of saving per-kv-block probabilities
    out, lses = jax.lax.map(jax.checkpoint(do_q_block),
                            jnp.arange(nq))  # (nq, b, h, qb, ...)
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, sq_p, d)[:, :, :sq]
    if return_lse:
        lse = jnp.moveaxis(lses, 0, 2).reshape(b, h, sq_p)[:, :, :sq]
        return out.astype(q.dtype), lse
    return out.astype(q.dtype)


# --------------------------------------------------------------------------
# Public dispatchers
# --------------------------------------------------------------------------
def attention(q, k, v, *, causal=True, sliding_window=0, q_offset=0,
              kv_len=None, impl="auto", scale=None):
    """Multi-head GQA attention. q (B,H,Sq,D), k/v (B,KV,Sk,D)."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"
    if impl == "ref":
        return _ref.mha_reference(q, k, v, causal=causal,
                                  sliding_window=sliding_window,
                                  q_offset=q_offset, kv_len=kv_len, scale=scale)
    if impl == "xla":
        return xla_attention(q, k, v, causal=causal,
                             sliding_window=sliding_window,
                             q_offset=q_offset, kv_len=kv_len, scale=scale)
    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels import flash_attention as fa
        return fa.flash_attention(q, k, v, causal=causal,
                                  sliding_window=sliding_window,
                                  q_offset=q_offset, kv_len=kv_len, scale=scale,
                                  interpret=impl == "pallas_interpret")
    raise ValueError(f"unknown impl {impl}")


def decode_attention(q, k, v, cache_len, *, sliding_window=0, impl="auto"):
    """Single new token vs KV cache. q (B,H,D), k/v (B,KV,S,D), cache_len (B,)."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"
    if impl == "ref":
        return _ref.decode_attention_reference(q, k, v, cache_len,
                                               sliding_window=sliding_window)
    if impl == "xla":
        if sliding_window:
            # per-batch window mask anchored at cache_len-1
            return _decode_xla_window(q, k, v, cache_len, sliding_window)
        out = xla_attention(q[:, :, None], k, v, causal=False, kv_len=cache_len)
        return out[:, :, 0]
    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels import flash_decode as fd
        return fd.flash_decode(q, k, v, cache_len, sliding_window=sliding_window,
                               interpret=impl == "pallas_interpret")
    raise ValueError(f"unknown impl {impl}")


def _decode_xla_window(q, k, v, cache_len, window):
    b, h, d = q.shape
    kvh, s = k.shape[1], k.shape[2]
    n_rep = h // kvh
    kk = jnp.repeat(k, n_rep, 1) if n_rep > 1 else k
    vv = jnp.repeat(v, n_rep, 1) if n_rep > 1 else v
    logits = jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32),
                        kk.astype(jnp.float32)) * d ** -0.5
    kpos = jnp.arange(s)[None]
    newest = cache_len[:, None] - 1
    valid = (kpos <= newest) & (newest - kpos < window)
    logits = jnp.where(valid[:, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, -1)
    return jnp.einsum("bhk,bhkd->bhd", p, vv.astype(jnp.float32)).astype(q.dtype)


# --------------------------------------------------------------------------
# Mamba2 SSD — chunked (the parallel form of the recurrence)
# --------------------------------------------------------------------------
def ssd_scan(x, dt, A, B, C, D, *, chunk=256, init_state=None,
             return_state=False, impl="auto"):
    """Chunked SSD. Shapes as ref.ssd_reference. O(s·chunk) attention-like work
    within chunks + O(s/chunk) state recurrence across chunks."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"
    if impl == "ref":
        return _ref.ssd_reference(x, dt, A, B, C, D, init_state=init_state,
                                  return_state=return_state)
    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels import ssd_kernel as sk
        return sk.ssd_scan_pallas(x, dt, A, B, C, D, chunk=chunk,
                                  init_state=init_state, return_state=return_state,
                                  interpret=impl == "pallas_interpret")
    return _ssd_chunked_xla(x, dt, A, B, C, D, chunk=chunk,
                            init_state=init_state, return_state=return_state)


def _ssd_chunked_xla(x, dt, A, B, C, D, *, chunk, init_state, return_state):
    b, s, nh, hd = x.shape
    ns = B.shape[-1]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    sp = s + pad
    nc = sp // chunk
    xf = x.astype(jnp.float32).reshape(b, nc, chunk, nh, hd)
    dtf = dt.astype(jnp.float32).reshape(b, nc, chunk, nh)
    Bf = B.astype(jnp.float32).reshape(b, nc, chunk, ns)
    Cf = C.astype(jnp.float32).reshape(b, nc, chunk, ns)
    Af = A.astype(jnp.float32)

    dA = dtf * Af[None, None, None, :]                    # (b,nc,L,nh) log-decay
    seg = jnp.cumsum(dA, axis=2)                          # within-chunk cumulative
    seg_total = seg[:, :, -1]                             # (b,nc,nh)

    # intra-chunk: Y[t] = sum_{u<=t} C_t·B_u x_u dt_u exp(seg_t - seg_u)
    rel = seg[:, :, :, None, :] - seg[:, :, None, :, :]   # (b,nc,t,u,nh)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))[None, None, :, :, None]
    # double-where: masked entries would overflow exp() and poison the
    # backward with inf*0 = NaN
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, rel, 0.0)), 0.0)
    cb = jnp.einsum("bcts,bcus->bctu", Cf, Bf)            # (b,nc,t,u)
    scores = cb[..., None] * decay * dtf[:, :, None]      # (b,nc,t,u,nh)
    y_intra = jnp.einsum("bctun,bcunh->bctnh", scores, xf)

    # chunk-final states: S_c = sum_u exp(seg_total - seg_u) dt_u x_u ⊗ B_u
    w = jnp.exp(seg_total[:, :, None] - seg) * dtf        # (b,nc,L,nh)
    states = jnp.einsum("bcun,bcunh,bcus->bcnhs", w, xf, Bf)

    # inter-chunk recurrence over nc
    h0 = (jnp.zeros((b, nh, hd, ns), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))

    def chunk_step(h, inp):
        st, tot = inp                                     # (b,nh,hd,ns), (b,nh)
        h_out = h                                         # state entering chunk
        h = h * jnp.exp(tot)[..., None, None] + st
        return h, h_out

    hT, h_in = jax.lax.scan(chunk_step,
                            h0, (states.transpose(1, 0, 2, 3, 4),
                                 seg_total.transpose(1, 0, 2)))
    h_in = h_in.transpose(1, 0, 2, 3, 4)                  # (b,nc,nh,hd,ns)

    # inter-chunk contribution: Y_inter[t] = C_t · exp(seg_t) h_in
    y_inter = jnp.einsum("bcts,bctn,bcnhs->bctnh", Cf, jnp.exp(seg), h_in)
    y = (y_intra + y_inter).reshape(b, sp, nh, hd)[:, :s]
    y = y + D.astype(jnp.float32)[None, None, :, None] * x[:, :s].astype(jnp.float32)
    y = y.astype(x.dtype)
    if return_state:
        return y, hT
    return y


def ssd_step(x, dt, A, B, C, D, state):
    """Decode-time single step (pure jnp; trivially memory bound)."""
    return _ref.ssd_step_reference(x, dt, A, B, C, D, state)
