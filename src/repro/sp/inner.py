"""Intra-node SP variants (the paper's §4.2 pair), run INSIDE shard_map over
the high-bandwidth mesh axis (TPU "model" axis ≈ the paper's NVLink domain).

Both take q (B, H, S_loc, D) / k,v (B, KV, S_loc, D) — a *sequence* sub-shard
per rank — and return the attention output in the same layout.

a2a_attention   — the all-to-all layout swap the paper describes in Fig. 5(a)
                  (DeepSpeed-Ulysses style): seq-sharded -> head-sharded full
                  sequence -> attention -> swap back. Comm volume
                  ≈ 2·s·(Nh+2·Nkv)·dh per rank (two A2As).
allgather_attention — the all-gather/reduce-scatter layout (Megatron-SP
                  style): gather the full sequence KV (+Q) on every rank,
                  compute the local head slice, A2A the output back to
                  sequence shards. Comm ≈ 2·s·d·(T-1) — higher volume,
                  but the attention matmuls run at full sequence length
                  (better MXU efficiency), which is exactly the trade-off
                  the paper's fast-SP selector weighs.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops


def _split_heads(x: jax.Array, p: int, axis_name: str) -> jax.Array:
    """(B, H, S_loc, D) seq-sharded -> (B, H/p, S, D) head-sharded (A2A)."""
    return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)


def _merge_heads(x: jax.Array, p: int, axis_name: str) -> jax.Array:
    """(B, H/p, S, D) head-sharded -> (B, H, S_loc, D) seq-sharded (A2A)."""
    return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)


def a2a_attention(q, k, v, *, axis_name: str, causal: bool = True,
                  sliding_window: int = 0, q_offset: int = 0,
                  scale: Optional[float] = None,
                  return_lse: bool = False):
    p = jax.lax.axis_size(axis_name)
    qh = _split_heads(q, p, axis_name)
    kh = _split_heads(k, p, axis_name)
    vh = _split_heads(v, p, axis_name)
    out = ops.xla_attention(qh, kh, vh, causal=causal,
                            sliding_window=sliding_window, q_offset=q_offset,
                            scale=scale, return_lse=return_lse)
    if return_lse:
        o, lse = out
        o = _merge_heads(o, p, axis_name)
        # lse (B, H/p, S) -> (B, H, S_loc): A2A without trailing dim
        lse = jax.lax.all_to_all(lse, axis_name, split_axis=2, concat_axis=1,
                                 tiled=True)
        return o, lse
    return _merge_heads(out, p, axis_name)


def allgather_attention(q, k, v, *, axis_name: str, causal: bool = True,
                        sliding_window: int = 0, q_offset: int = 0,
                        scale: Optional[float] = None,
                        return_lse: bool = False):
    p = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, h, s_loc, d = q.shape
    hp = h // p
    # gather full sequence on every rank (the higher-volume collective)
    qg = jax.lax.all_gather(q, axis_name, axis=2, tiled=True)   # (B,H,S,D)
    kg = jax.lax.all_gather(k, axis_name, axis=2, tiled=True)
    vg = jax.lax.all_gather(v, axis_name, axis=2, tiled=True)
    # compute only this rank's head slice (TP-style head partition)
    qs = jax.lax.dynamic_slice_in_dim(qg, idx * hp, hp, axis=1)
    kvh = k.shape[1]
    if kvh % p == 0:
        # contiguous slices keep GQA group alignment: hp/kvp == H/KV
        kvp = kvh // p
        ks = jax.lax.dynamic_slice_in_dim(kg, idx * kvp, kvp, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(vg, idx * kvp, kvp, axis=1)
    else:
        # fewer KV heads than ranks: materialize per-q-head KV and slice the
        # same range as q (replicated KV work — the GQA-small-kv corner)
        n_rep = h // kvh
        kg = jnp.repeat(kg, n_rep, axis=1)
        vg = jnp.repeat(vg, n_rep, axis=1)
        ks = jax.lax.dynamic_slice_in_dim(kg, idx * hp, hp, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(vg, idx * hp, hp, axis=1)
    out = ops.xla_attention(qs, ks, vs, causal=causal,
                            sliding_window=sliding_window, q_offset=q_offset,
                            scale=scale, return_lse=return_lse)
    if return_lse:
        o, lse = out
        o = jax.lax.all_to_all(o, axis_name, split_axis=2, concat_axis=1,
                               tiled=True)
        lse = jax.lax.all_to_all(lse, axis_name, split_axis=2, concat_axis=1,
                                 tiled=True)
        return o, lse
    return jax.lax.all_to_all(out, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)
