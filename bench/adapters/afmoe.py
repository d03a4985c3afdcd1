"""Puts an afmoe configuration (Trinity: windowed beside full attention,
a dense lead, sigmoid-routed experts of which this chip holds a share, a
shared expert) into the program: its `ModelConfig`, and weights made from
the seed in the parameter layout `repro.models.afmoe` serves.

The weights are the benchmark's own: one jitted call draws every leaf on
the device, the matrices in the served dtype and the router and expert
bias in float32.  Norm weights sit near 1 and the expert bias is nonzero,
so a path that drops either shows; embeddings are drawn at std
hidden**-0.5, so that muP's sqrt(hidden) brings them to unit size.  The
plain reference reads the same arrays.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


def program_config(c: dict) -> ModelConfig:
    return ModelConfig(
        name=c["name"], family="afmoe", source=c["source"],
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        sliding_window=c["sliding_window"],
        layer_types=tuple(c["layer_types"][:c["num_hidden_layers"]]),
        num_dense_layers=c["num_dense_layers"],
        num_experts=c["router_experts"],
        experts_per_token=c["num_experts_per_tok"],
        moe_d_ff=c["moe_intermediate_size"],
        shared_d_ff=c["moe_intermediate_size"] * c["num_shared_experts"],
        route_scale=float(c["route_scale"]),
        experts_held=c["num_experts"], expert_offset=c["expert_offset"],
        embed_scale=float(c["hidden_size"]) ** 0.5 if c["mup_enabled"]
        else 1.0,
        dtype=c["torch_dtype"])


def seed_key(seed: int) -> jax.Array:
    """A key for any whole number up to 2**63: its low and high 32 bits."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@functools.partial(jax.jit, static_argnames=("shape",))
def _make(key, *, shape):
    L, nd, d, H, KV, hd, ff, E, held, f, fs, V, dt = shape
    dt = jnp.dtype(dt)
    nm = L - nd
    ks = iter(jax.random.split(key, 32))

    def normal(shp, std, dtype=dt):
        return jax.random.normal(next(ks), shp, dtype) \
            * jnp.asarray(std, dtype)

    def norm_w(shp):
        return (1.0 + 0.1 * jax.random.normal(next(ks), shp)).astype(dt)

    def swiglu(lead, width):
        return {"w_gate": normal(lead + (d, width), d ** -0.5),
                "w_up": normal(lead + (d, width), d ** -0.5),
                "w_down": normal(lead + (width, d), width ** -0.5)}
    moe = {"router": normal((nm, d, E), d ** -0.5, jnp.float32),
           "bias": normal((nm, E), 0.1, jnp.float32),
           **swiglu((nm, held), f)}
    if fs:
        moe["shared"] = swiglu((nm,), fs)
    return {
        "embed": normal((V, d), d ** -0.5),
        "final_norm": norm_w((d,)),
        "lm_head": normal((d, V), d ** -0.5),
        "layers": {
            "attn": {"wq": normal((L, d, H * hd), d ** -0.5),
                     "wk": normal((L, d, KV * hd), d ** -0.5),
                     "wv": normal((L, d, KV * hd), d ** -0.5),
                     "wg": normal((L, d, H * hd), d ** -0.5),
                     "wo": normal((L, H * hd, d), (H * hd) ** -0.5),
                     "q_norm": norm_w((L, hd)), "k_norm": norm_w((L, hd))},
            "ln_in": norm_w((L, d)), "ln_post_attn": norm_w((L, d)),
            "ln_pre_mlp": norm_w((L, d)), "ln_post_mlp": norm_w((L, d)),
        },
        "dense": swiglu((nd,), ff),
        "moe": moe,
    }


def make_params(c: dict, seed: int):
    shape = (c["num_hidden_layers"], c["num_dense_layers"], c["hidden_size"],
             c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"],
             c["intermediate_size"], c["router_experts"], c["num_experts"],
             c["moe_intermediate_size"],
             c["moe_intermediate_size"] * c["num_shared_experts"],
             c["vocab_size"], c["torch_dtype"])
    return jax.block_until_ready(_make(seed_key(seed), shape=shape))
