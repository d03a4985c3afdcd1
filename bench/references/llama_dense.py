"""Plain reference of a Llama-style dense decoder, in float32 at `highest`
matrix precision: RMSNorm, rotary embeddings on the two halves of each head
(Mistral's and Yi's `rotate_half`), grouped-query causal attention, SwiGLU,
a final RMSNorm and an untied head.  Written from the published
architecture; it shares no code with the program.

It runs one layer at a time and attention in blocks of query rows, so that
a 9K-token sequence of a full-width layer fits beside the served weights:
each layer's weights are upcast inside its own call.

`weights="fp8"` rounds every matrix to float8-e4m3 with one scale per
matrix before the float32 computation: the control, a precision below the
served bfloat16, which the correctness limit must fail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
#: query rows per attention block; sequences are padded to a multiple of
#: it, bounding the programs built (the padding sits after every position
#: read, so causal attention never sees it)
Q_BLOCK = 1024
#: logit rows are computed in multiples of this, for the same reason
ROWS = 64


def _w(a, weights: str):
    a = a.astype(jnp.float32)
    if weights == "fp8":
        s = jnp.max(jnp.abs(a)) / 448.0
        a = (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return a


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (S, n, hd); positions 0..S-1."""
    S, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("dims", "weights"))
def _layer(x, lay, i, *, dims, weights):
    H, KV, hd, theta, eps = dims
    S = x.shape[0]
    p = {"ln1": lay["ln1"], "ln2": lay["ln2"], **lay["attn"], **lay["mlp"]}
    p = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False), p)
    h = _rms(x, p["ln1"].astype(jnp.float32), eps)
    q = jnp.dot(h, _w(p["wq"], weights), precision=HI).reshape(S, H, hd)
    k = jnp.dot(h, _w(p["wk"], weights), precision=HI).reshape(S, KV, hd)
    v = jnp.dot(h, _w(p["wv"], weights), precision=HI).reshape(S, KV, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    rep = H // KV
    k = jnp.repeat(k, rep, axis=1)              # (S, H, hd)
    v = jnp.repeat(v, rep, axis=1)
    scale = hd ** -0.5

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * scale
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)[:, None]
        s = jnp.where(jnp.arange(S)[None] <= rows, s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v,
                          precision=HI)
    o = jax.lax.map(block, jnp.arange(S // Q_BLOCK)).reshape(S, H * hd)
    x = x + jnp.dot(o, _w(p["wo"], weights), precision=HI)
    h = _rms(x, p["ln2"].astype(jnp.float32), eps)
    g = jnp.dot(h, _w(p["w_gate"], weights), precision=HI)
    u = jnp.dot(h, _w(p["w_up"], weights), precision=HI)
    return x + jnp.dot(jax.nn.silu(g) * u, _w(p["w_down"], weights),
                       precision=HI)


@functools.partial(jax.jit, static_argnames=("eps", "weights"))
def _head(x, norm, head, *, eps, weights):
    return jnp.dot(_rms(x, norm.astype(jnp.float32), eps), _w(head, weights),
                   precision=HI)


@functools.partial(jax.jit, static_argnames=("weights",))
def _embed(table, tokens, *, weights):
    return _w(table[tokens], weights)


def logits(cfg: dict, params, seq: np.ndarray, rows: np.ndarray,
           weights: str = "float32") -> np.ndarray:
    """Logits (len(rows), vocab) at positions `rows` of token sequence
    `seq`, each predicting the token after it."""
    S, n = len(seq), len(rows)
    toks = np.zeros(-(-S // Q_BLOCK) * Q_BLOCK, np.int32)
    toks[:S] = seq
    rows = np.resize(np.asarray(rows, np.int32), -(-n // ROWS) * ROWS)
    dims = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], float(cfg["rope_theta"]),
            float(cfg["rms_norm_eps"]))
    x = _embed(params["embed"], jnp.asarray(toks), weights=weights)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, params["layers"], jnp.int32(i), dims=dims,
                   weights=weights)
    out = _head(x[jnp.asarray(rows)], params["final_norm"], params["lm_head"],
                eps=float(cfg["rms_norm_eps"]), weights=weights)
    return np.asarray(out[:n], np.float32)
