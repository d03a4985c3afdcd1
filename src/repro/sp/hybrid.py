"""Fast SP (the paper's §5.3): hybrid sequence parallelism for long prefill.

Outer: ring attention across the long mesh axis ("data", + "pod" multi-pod) —
scalable neighbour exchange on ICI torus links.
Inner: within the high-bandwidth "model" axis, either the A2A layout swap
(paper Fig. 5(a)) or the all-gather layout (Fig. 5(b)); chosen per-request by
the planner's comm/compute estimate (planner.py) — exactly the paper's
"select the lower-latency option" rule, adapted from NVLink/IB to ICI axes.

Public entry: fast_sp_attention(q, k, v) on GLOBAL arrays under a mesh —
wraps the local function in jax.shard_map, so it composes inside a jitted
model step.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels import ops
from repro.sp.common import finalize, merge_partials
from repro.sp.inner import _merge_heads, _split_heads


def _maybe_rep_kv(k, v, h, pi):
    kvh = k.shape[1]
    if kvh % pi:
        n_rep = h // kvh
        k = jnp.repeat(k, n_rep, axis=1)
        v = jnp.repeat(v, n_rep, axis=1)
    return k, v


def fast_sp_attention_local(q, k, v, *, outer_axes, inner_axis: Optional[str],
                            strategy: str = "a2a", causal: bool = True,
                            sliding_window: int = 0,
                            scale: Optional[float] = None):
    """Runs INSIDE shard_map. q (B,H,s_loc,D), k/v (B,KV,s_loc,D); the global
    sequence is sharded over (outer_axes..., inner_axis), outer-major."""
    b, h, s_loc, d = q.shape
    po = jax.lax.axis_size(outer_axes) if outer_axes else 1
    oidx = jax.lax.axis_index(outer_axes) if outer_axes else 0
    pi = jax.lax.axis_size(inner_axis) if inner_axis else 1
    iidx = jax.lax.axis_index(inner_axis) if inner_axis else 0
    seg = s_loc * pi                       # outer segment length

    # ---- inner transform: local seq sub-shard -> full outer segment --------
    if pi == 1:
        qs, ks, vs = q, k, v
    elif strategy == "a2a":
        kk, vv = _maybe_rep_kv(k, v, h, pi)
        qs = _split_heads(q, pi, inner_axis)          # (B, H/pi, seg, D)
        ks = _split_heads(kk, pi, inner_axis)
        vs = _split_heads(vv, pi, inner_axis)
    elif strategy == "allgather":
        hp = h // pi
        qg = jax.lax.all_gather(q, inner_axis, axis=2, tiled=True)
        kg = jax.lax.all_gather(k, inner_axis, axis=2, tiled=True)
        vg = jax.lax.all_gather(v, inner_axis, axis=2, tiled=True)
        qs = jax.lax.dynamic_slice_in_dim(qg, iidx * hp, hp, axis=1)
        kvh = k.shape[1]
        if kvh % pi == 0:
            kvp = kvh // pi
            ks = jax.lax.dynamic_slice_in_dim(kg, iidx * kvp, kvp, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(vg, iidx * kvp, kvp, axis=1)
        else:
            n_rep = h // kvh
            kg = jnp.repeat(kg, n_rep, axis=1)
            vg = jnp.repeat(vg, n_rep, axis=1)
            ks = jax.lax.dynamic_slice_in_dim(kg, iidx * hp, hp, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(vg, iidx * hp, hp, axis=1)
    else:
        raise ValueError(strategy)

    q_off = oidx * seg

    # ---- outer ring over the long axis -------------------------------------
    def attend(k_seg, v_seg, kv_rank):
        o, lse = ops.xla_attention(
            qs, k_seg, v_seg, causal=causal, sliding_window=sliding_window,
            q_offset=q_off - kv_rank * seg, scale=scale, return_lse=True)
        return o.astype(jnp.float32), lse

    if po == 1:
        o, lse = attend(ks, vs, 0)
    else:
        n = po
        perm = [(i, (i + 1) % n) for i in range(n)]

        def body(carry, step):
            o, lse, kc, vc = carry
            kv_rank = (oidx - step) % n
            o2, lse2 = attend(kc, vc, kv_rank)
            o, lse = merge_partials(o, lse, o2, lse2)
            kc = jax.lax.ppermute(kc, outer_axes, perm)
            vc = jax.lax.ppermute(vc, outer_axes, perm)
            return (o, lse, kc, vc), None

        o0 = jnp.zeros(qs.shape, jnp.float32)
        lse0 = jnp.full(qs.shape[:3], -jnp.inf)
        (o, lse, _, _), _ = jax.lax.scan(body, (o0, lse0, ks, vs), jnp.arange(n))

    out = finalize(o, lse, q.dtype)

    # ---- back to the input layout ------------------------------------------
    if pi == 1:
        return out
    if strategy == "a2a":
        return _merge_heads(out, pi, inner_axis)
    return jax.lax.all_to_all(out, inner_axis, split_axis=2, concat_axis=1,
                              tiled=True)


def fast_sp_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      mesh: Mesh, strategy: str = "a2a",
                      causal: bool = True, sliding_window: int = 0,
                      scale: Optional[float] = None,
                      outer_axes: Tuple[str, ...] = ("data",),
                      inner_axis: Optional[str] = "model") -> jax.Array:
    """GLOBAL q (B,H,S,D), k/v (B,KV,S,D). Sequence gets sharded over
    (outer_axes..., inner_axis); heads replicated at entry (the inner
    transform re-shards them). Composable inside jit under `mesh`."""
    outer = tuple(a for a in outer_axes if a in mesh.axis_names)
    inner = inner_axis if (inner_axis and inner_axis in mesh.axis_names) else None
    seq_axes = outer + ((inner,) if inner else ())
    spec_q = P(None, None, seq_axes if len(seq_axes) > 1 else (seq_axes[0] if seq_axes else None), None)
    fn = functools.partial(
        fast_sp_attention_local, outer_axes=outer if outer else None,
        inner_axis=inner, strategy=strategy, causal=causal,
        sliding_window=sliding_window, scale=scale)
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=(spec_q, spec_q, spec_q),
                         out_specs=spec_q, check_vma=False)(q, k, v)
