"""Pallas TPU flash-decode: one new query token against a long KV cache.

This is the memory-bound serve_step hot loop (decode_32k / long_500k shapes).
Grid iterates KV blocks sequentially per (batch, head); the online-softmax
state lives in VMEM scratch, so HBM traffic is exactly one pass over the
valid cache prefix — the roofline-optimal schedule for decode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(kvlen_ref,                  # SMEM (B,)
                   q_ref, k_ref, v_ref,        # VMEM blocks
                   o_ref,
                   m_ref, l_ref, acc_ref,
                   *, bk: int, n_kv_blocks: int, sliding_window: int):
    ib = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = kvlen_ref[ib]
    newest = kv_len - 1
    lo = 0 if sliding_window == 0 else jnp.maximum(newest - sliding_window + 1, 0)
    # Skip blocks entirely outside [lo, kv_len)
    needed = jnp.logical_and(ik * bk < kv_len,
                             (ik + 1) * bk > lo if sliding_window else True)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # (1, D)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        d = q.shape[-1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * d ** -0.5
        kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        mask = kpos < kv_len
        if sliding_window > 0:
            mask = jnp.logical_and(mask, kpos >= lo)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...][:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = (l_ref[...][:, 0] * corr + p.sum(axis=1))[:, None]
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new[:, None]

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        l = l_ref[...][:, 0]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, ...] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("sliding_window", "block_k", "interpret"))
def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 cache_len: jax.Array, *, sliding_window: int = 0,
                 block_k: int = 512, interpret: bool = False) -> jax.Array:
    """q (B,H,D); k, v (B,KV,S,D); cache_len (B,); returns (B,H,D)."""
    b, h, d = q.shape
    kvh, s = k.shape[1], k.shape[2]
    n_rep = h // kvh
    bk = min(block_k, s)
    sp = -(-s // bk) * bk
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, sp - s), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, sp - s), (0, 0)))
    nk = sp // bk
    grid = (b, h, nk)
    kernel = functools.partial(_decode_kernel, bk=bk, n_kv_blocks=nk,
                               sliding_window=sliding_window)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, 1, d), lambda ib, ih, ik, *r: (ib, ih, 0, 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda ib, ih, ik, *r: (ib, ih // n_rep, ik, 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda ib, ih, ik, *r: (ib, ih // n_rep, ik, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, 1, d), lambda ib, ih, ik, *r: (ib, ih, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d), q.dtype),
        interpret=interpret,
    )(cache_len.astype(jnp.int32), q[:, :, None], kp, vp)
    return out[:, :, 0]


# ---------------------------------------------------------------------------
# Paged: the same step over a paged KV pool, read in place through a table
# ---------------------------------------------------------------------------
#: pages one grid step copies in and attends over; a table's width is a
#: multiple of it (the engine pads its page tables to one)
PAGES_PER_STEP = 16


def _paged_decode_kernel(*refs, pages_per_step: int, pages_per_seq: int,
                         windowed: bool, ring: int):
    if windowed:
        (lens_ref, starts_ref, pages_ref,     # SMEM (B,), (B,), (B*P,)
         q_ref, k_hbm, v_hbm, o_ref,          # q VMEM; pool HBM
         k_buf, v_buf, sems, m_ref, l_ref, acc_ref) = refs
    else:
        (lens_ref, pages_ref, q_ref, k_hbm, v_hbm, o_ref,
         k_buf, v_buf, sems, m_ref, l_ref, acc_ref) = refs
    b = pl.program_id(0)
    length = lens_ref[b]
    n_kv, page = k_hbm.shape[1], k_hbm.shape[2]
    d = k_hbm.shape[3]
    bk = pages_per_step * page
    if windowed:
        start = starts_ref[b]
        first = start // page           # the first page a step reads

    def page_at(i, j):
        """Pool page of the step's j-th page: table entries in order, or
        from the window's first page on, round the ring."""
        if not windowed:
            return pages_ref[b * pages_per_seq + i * pages_per_step + j]
        lp = first + i * pages_per_step + j
        lp = lp % ring if ring else jnp.minimum(lp, pages_per_seq - 1)
        return pages_ref[b * pages_per_seq + lp]

    def copies(i, buf):
        """The DMAs of step i's pages, every KV head of a page in one."""
        out = []
        for j in range(pages_per_step):
            p = page_at(i, j)
            out.append(pltpu.make_async_copy(k_hbm.at[p], k_buf.at[buf, j],
                                             sems.at[0, buf]))
            out.append(pltpu.make_async_copy(v_hbm.at[p], v_buf.at[buf, j],
                                             sems.at[1, buf]))
        return out

    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    if windowed:
        n_pages = (length + page - 1) // page - first
        n_steps = (n_pages + pages_per_step - 1) // pages_per_step
    else:
        n_steps = (length + bk - 1) // bk

    @pl.when(n_steps > 0)
    def _first():
        for c in copies(0, 0):
            c.start()

    def step(i, carry):
        buf = i % 2

        @pl.when(i + 1 < n_steps)
        def _next():                    # the next step's pages, meanwhile
            for c in copies(i + 1, 1 - buf):
                c.start()

        for c in copies(i, buf):
            c.wait()
        if windowed:
            kpos = (first + i * pages_per_step) * page \
                + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            valid = jnp.logical_and(kpos < length, kpos >= start)
        else:
            valid = i * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk),
                                                      1) < length
        for h in range(n_kv):
            k = k_buf[buf, :, h].reshape(bk, d)
            v = v_buf[buf, :, h].reshape(bk, d)
            q = q_ref[0, h].astype(k.dtype)                      # (G, D)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(valid, s * d ** -0.5, NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + p.sum(axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new
        return carry

    jax.lax.fori_loop(0, n_steps, step, 0)
    l = l_ref[...]
    o_ref[0] = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)


@functools.partial(jax.jit, static_argnames=("ring", "pages_per_step",
                                             "interpret"))
def paged_flash_decode(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                       pages: jax.Array, lengths: jax.Array,
                       starts: jax.Array | None = None, *, ring: int = 0,
                       pages_per_step: int = PAGES_PER_STEP,
                       interpret: bool = False) -> jax.Array:
    """q (B, H, D); k_pages, v_pages (N, KV, page, D), the whole pool;
    pages (B, P) int32, row b the pool pages of sequence b in order;
    lengths (B,): sequence b attends over its first lengths[b] tokens, and
    0 gives zeros.  Returns (B, H, D).

    A window: `starts` (B,) has sequence b attend over [starts[b],
    lengths[b]) only, reading from the page that holds starts[b] on; with
    `ring` > 0, row b holds a ring of that many pages, logical page j of
    the sequence in entry j % ring.

    One grid step per sequence walks its pages `pages_per_step` at a time,
    double-buffered: only pages below the length (and from the start's)
    are read, each page by one DMA of every KV head.  Scores and softmax
    are float32; both matmuls take the pool's dtype with float32
    accumulation."""
    b, h, d = q.shape
    n_kv, page = k_pages.shape[1], k_pages.shape[2]
    n_pages = pages.shape[1]
    pps = min(pages_per_step, n_pages)
    if n_pages % pps:
        raise ValueError(f"page table width {n_pages} is not a multiple of "
                         f"{pps} pages per step")
    windowed = starts is not None
    g = h // n_kv
    qg = q.astype(jnp.float32).reshape(b, n_kv, g, d)
    kernel = functools.partial(_paged_decode_kernel, pages_per_step=pps,
                               pages_per_seq=n_pages, windowed=windowed,
                               ring=ring)
    spec = pl.BlockSpec((1, n_kv, g, d), lambda ib, *_: (ib, 0, 0, 0))
    scalars = [lengths.astype(jnp.int32)]
    if windowed:
        scalars.append(starts.astype(jnp.int32))
    scalars.append(pages.reshape(-1).astype(jnp.int32))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(b,),
            in_specs=[spec, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=spec,
            scratch_shapes=[
                pltpu.VMEM((2, pps, n_kv, page, d), k_pages.dtype),
                pltpu.VMEM((2, pps, n_kv, page, d), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((n_kv, g, 1), jnp.float32),
                pltpu.VMEM((n_kv, g, 1), jnp.float32),
                pltpu.VMEM((n_kv, g, d), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        out_shape=jax.ShapeDtypeStruct((b, n_kv, g, d), jnp.float32),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(*scalars, qg, k_pages, v_pages)
    return out.reshape(b, h, d).astype(q.dtype)
