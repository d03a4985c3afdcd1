"""Puts a Llama-style dense configuration (RMSNorm, rotary GQA attention,
SwiGLU, untied head) into the program: its `ModelConfig`, and weights made
from the seed in the parameter layout `repro.models.model` serves.

The weights are the benchmark's own: one jitted call draws every leaf on
the device in the served dtype.  The plain reference reads the same arrays.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


def program_config(c: dict) -> ModelConfig:
    return ModelConfig(
        name=c["name"], family="dense", source=c["source"],
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], head_dim=c["head_dim"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        dtype=c["torch_dtype"])


def seed_key(seed: int) -> jax.Array:
    """A key for any whole number up to 2**63: its low and high 32 bits."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@functools.partial(jax.jit, static_argnames=("shape",))
def _make(key, *, shape):
    L, d, H, KV, hd, ff, V, dt = shape
    dt = jnp.dtype(dt)
    ks = iter(jax.random.split(key, 12))

    def normal(shp, std):
        return jax.random.normal(next(ks), shp, dt) * jnp.asarray(std, dt)

    def norm_w(shp):   # near 1, so a path that drops a norm weight shows
        return (1.0 + 0.1 * jax.random.normal(next(ks), shp)).astype(dt)
    return {
        "embed": normal((V, d), 1.0),
        "final_norm": norm_w((d,)),
        "lm_head": normal((d, V), d ** -0.5),
        "layers": {
            "attn": {"wq": normal((L, d, H * hd), d ** -0.5),
                     "wk": normal((L, d, KV * hd), d ** -0.5),
                     "wv": normal((L, d, KV * hd), d ** -0.5),
                     "wo": normal((L, H * hd, d), (H * hd) ** -0.5)},
            "mlp": {"w_gate": normal((L, d, ff), d ** -0.5),
                    "w_up": normal((L, d, ff), d ** -0.5),
                    "w_down": normal((L, ff, d), ff ** -0.5)},
            "ln1": norm_w((L, d)), "ln2": norm_w((L, d)),
        },
    }


def make_params(c: dict, seed: int):
    shape = (c["num_hidden_layers"], c["hidden_size"],
             c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"],
             c["intermediate_size"], c["vocab_size"], c["torch_dtype"])
    return jax.block_until_ready(_make(seed_key(seed), shape=shape))
