"""Shared SP utilities: LSE-merging of partial attention results.

Any attention over a KV *subset* yields (o, lse). Results over disjoint KV
subsets merge exactly via log-sum-exp algebra — the primitive behind ring
attention (sequential merges) and distributed decode (all-reduce merge).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def merge_partials(o1: jax.Array, lse1: jax.Array,
                   o2: jax.Array, lse2: jax.Array):
    """Merge two partial attentions over disjoint KV sets.
    o (B,H,S,D) f32, lse (B,H,S) f32 with -inf == empty."""
    m = jnp.maximum(lse1, lse2)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    w1 = jnp.where(jnp.isneginf(lse1), 0.0, jnp.exp(lse1 - m_safe))
    w2 = jnp.where(jnp.isneginf(lse2), 0.0, jnp.exp(lse2 - m_safe))
    den = w1 + w2
    den_safe = jnp.maximum(den, 1e-38)
    o = (o1 * w1[..., None] + o2 * w2[..., None]) / den_safe[..., None]
    lse = jnp.where(den > 0, m_safe + jnp.log(den_safe), -jnp.inf)
    return o, lse


def finalize(o: jax.Array, lse: jax.Array, dtype) -> jax.Array:
    """Zero out rows that attended to nothing (fully masked)."""
    return jnp.where(jnp.isneginf(lse)[..., None], 0.0, o).astype(dtype)
