"""Ring attention across a mesh axis (the paper's cross-node SP layer).

Each rank holds one sequence segment of Q/K/V. KV segments rotate around the
ring via lax.ppermute (neighbour exchange — maps directly onto TPU ICI torus
links); every hop the local Q attends to the incoming KV segment with global
position offsets, and partial results merge via LSE algebra (common.py).

Communication per hop = local KV bytes; total = (P-1) · KV-segment bytes —
the paper's "scalable, low-communication" cross-node layer.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.sp.common import finalize, merge_partials


def ring_attention_local(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         axis_name: str, causal: bool = True,
                         sliding_window: int = 0,
                         scale: Optional[float] = None) -> jax.Array:
    """Runs INSIDE shard_map. q/k/v (B, H|KV, S_local, D) = this rank's segment;
    global sequence = concat of segments along the axis, in axis order."""
    p = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, h, s_loc, d = q.shape
    q_off = idx * s_loc

    def attend_with_offsets(k_seg, v_seg, kv_rank):
        # q_offset encodes the *global* q position relative to this kv
        # segment's start, so causal/window masks are globally correct.
        kv_off = kv_rank * s_loc
        o, lse = ops.xla_attention(
            q, k_seg, v_seg, causal=causal, sliding_window=sliding_window,
            q_offset=q_off - kv_off, scale=scale, return_lse=True)
        return o.astype(jnp.float32), lse

    perm = [(i, (i + 1) % p) for i in range(p)]

    def body(carry, step):
        o, lse, k_cur, v_cur = carry
        kv_rank = (idx - step) % p
        o_new, lse_new = attend_with_offsets(k_cur, v_cur, kv_rank)
        o, lse = merge_partials(o, lse, o_new, lse_new)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (o, lse, k_nxt, v_nxt), None

    o0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    lse0 = jnp.full((b, h, s_loc), -jnp.inf)
    (o, lse, _, _), _ = jax.lax.scan(body, (o0, lse0, k, v), jnp.arange(p))
    return finalize(o, lse, q.dtype)
