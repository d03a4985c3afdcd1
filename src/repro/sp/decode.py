"""Distributed decode attention: one query token vs a sequence-sharded KV
cache (the long-context serve_step). Each shard computes a partial flash-
decode over its KV slice, then partials merge with an LSE-weighted all-reduce
— O(B·H·D) bytes on the wire instead of migrating the (huge) KV.

This is the TPU-native colocation enabler from the paper's Fig. 7: the long
request's decode Q is broadcast to the shards that hold its KV, each computes
locally, and a tiny all-reduce merges — "Req1's Q is copied ... outputs are
merged via all-reduce".
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def distributed_decode_local(q, k, v, cache_len, *, seq_axes,
                             sliding_window: int = 0):
    """Runs INSIDE shard_map. q (B,H,D) replicated; k/v (B,KV,S_loc,D) =
    this rank's KV slice; cache_len (B,) GLOBAL valid length."""
    idx = jax.lax.axis_index(seq_axes)
    b, h, d = q.shape
    s_loc = k.shape[2]
    start = idx * s_loc
    newest = cache_len - 1

    qf = q.astype(jnp.float32)
    if sliding_window:
        lo = jnp.maximum(newest - sliding_window + 1, 0)   # (B,) global
    else:
        lo = jnp.zeros_like(cache_len)

    kvh = k.shape[1]
    n_rep = h // kvh
    kf = (jnp.repeat(k, n_rep, 1) if n_rep > 1 else k).astype(jnp.float32)
    vf = (jnp.repeat(v, n_rep, 1) if n_rep > 1 else v).astype(jnp.float32)
    logits = jnp.einsum("bhd,bhkd->bhk", qf, kf) * d ** -0.5
    kpos = start + jnp.arange(s_loc)[None]                 # (1, S_loc) global
    valid = (kpos < cache_len[:, None]) & (kpos >= lo[:, None])
    logits = jnp.where(valid[:, None], logits, -jnp.inf)
    m = logits.max(-1)                                     # (B,H)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    pweights = jnp.exp(logits - m_safe[..., None])
    l = pweights.sum(-1)
    o = jnp.einsum("bhk,bhkd->bhd", pweights, vf)

    # LSE-weighted merge across shards
    g_m = jax.lax.pmax(m, seq_axes)
    g_m_safe = jnp.where(jnp.isneginf(g_m), 0.0, g_m)
    w = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - g_m_safe))
    num = jax.lax.psum(o * w[..., None], seq_axes)
    den = jax.lax.psum(l * w, seq_axes)
    out = num / jnp.maximum(den, 1e-38)[..., None]
    return out.astype(q.dtype)


def distributed_decode_attention(q, k, v, cache_len, *, mesh: Mesh,
                                 seq_axes: Tuple[str, ...] = ("data",),
                                 sliding_window: int = 0,
                                 batch_axes: Tuple[str, ...] = ()) -> jax.Array:
    """GLOBAL q (B,H,D); k/v (B,KV,S,D) sharded on seq over `seq_axes` and on
    batch over `batch_axes` (keeping B sharded avoids gathering the cache)."""
    axes = tuple(a for a in seq_axes if a in mesh.axis_names)
    ba = tuple(a for a in batch_axes
               if a in mesh.axis_names and a not in axes)
    if ba and q.shape[0] % _axsize(mesh, ba) != 0:
        ba = ()
    bspec = (ba if len(ba) > 1 else ba[0]) if ba else None
    seq = axes if len(axes) > 1 else axes[0]
    fn = functools.partial(distributed_decode_local, seq_axes=axes,
                           sliding_window=sliding_window)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(bspec, None, None), P(bspec, None, seq, None),
                  P(bspec, None, seq, None), P(bspec)),
        out_specs=P(bspec, None, None), check_vma=False)(q, k, v, cache_len)


def _axsize(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n
