"""Plain reference of the afmoe decoder (Trinity), in float32 at `highest`
matrix precision, written from the published architecture; it shares no
code with the program.

Per layer i, with RMSNorms of the layer's weights:
  h = norm_in(x); q = norm_q(h Wq), k = norm_k(h Wk) over each head's 128,
  v = h Wv; RoPE (rotate-half, positions 0..S-1) on sliding layers only;
  causal grouped-query attention, a key at most `sliding_window - 1`
  behind its query on sliding layers; o times sigmoid(h Wg), then Wo;
  x += norm_post_attn(.);  h = norm_pre_mlp(x);
  the first `num_dense_layers` layers: SwiGLU(h);
  the others: s = sigmoid(h Wr) over all `router_experts`, the
  `num_experts_per_tok` of largest s + expert_bias chosen, weights s of
  the chosen normalised to sum 1 and times `route_scale`; the sum of the
  weighted SwiGLU of each chosen expert this chip holds (`num_experts`
  from `expert_offset`) plus the shared expert's SwiGLU;
  x += norm_post_mlp(.).
Embeddings times sqrt(hidden_size) (`mup_enabled`); a final RMSNorm and an
untied head.

Every expert held runs over every row, its weight zero where it was not
chosen: no capacity, no gather.  It runs one layer at a time (a scan that
upcasts one layer's weights at a time) and attention in blocks of query
rows, with the window as an explicit mask over the whole key range, so a
9K-token sequence fits beside the served weights; one program per padded
length serves every layer.

`weights="fp8"` rounds every matrix (the router's too) to float8-e4m3 with
one scale per matrix before the float32 computation: the control, a
precision below the served bfloat16, which the correctness limit must fail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
#: query rows per attention block; sequences are padded to a whole number
#: of blocks (the padding sits after every position read, so causal
#: attention never sees it)
Q_BLOCK = 1024
#: logit rows are computed in multiples of this
ROWS = 64


def _blocks(S: int) -> int:
    """Blocks a sequence of S tokens is padded to: the least of 1, 2, 3, 4,
    6, 8, 12, ... (2^k and 3 * 2^k) that holds it, so that a sample of many
    lengths compiles a few programs."""
    n = -(-S // Q_BLOCK)
    return min(b for k in range(n.bit_length() + 1)
               for b in (1 << k, 3 << k) if b >= n)


def _w(a, weights: str):
    a = a.astype(jnp.float32)
    if weights == "fp8":
        s = jnp.max(jnp.abs(a)) / 448.0
        a = (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return a


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


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


def _swiglu(h, wg, wu, wd, weights):
    g = jnp.dot(h, _w(wg, weights), precision=HI)
    u = jnp.dot(h, _w(wu, weights), precision=HI)
    return jnp.dot(jax.nn.silu(g) * u, _w(wd, weights), precision=HI)


def _at(tree, i):
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False),
                        tree)


def _attn(x, p, window, *, dims, weights):
    """x + the attention of one layer; `window` 0 for a full layer."""
    H, KV, hd, theta, eps = dims
    S = x.shape[0]
    a = p["attn"]
    h = _rms(x, p["ln_in"], eps)
    q = jnp.dot(h, _w(a["wq"], weights), precision=HI).reshape(S, H, hd)
    k = jnp.dot(h, _w(a["wk"], weights), precision=HI).reshape(S, KV, hd)
    v = jnp.dot(h, _w(a["wv"], weights), precision=HI).reshape(S, KV, hd)
    q, k = _rms(q, a["q_norm"], eps), _rms(k, a["k_norm"], eps)
    q = jnp.where(window > 0, _rope(q, theta), q)
    k = jnp.where(window > 0, _rope(k, theta), k)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    reach = jnp.where(window > 0, window, S)

    def block(b):
        qb = jax.lax.dynamic_slice_in_dim(q, b * Q_BLOCK, Q_BLOCK, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * hd ** -0.5
        rows = b * Q_BLOCK + jnp.arange(Q_BLOCK)[:, None]
        cols = jnp.arange(S)[None]
        s = jnp.where((cols <= rows) & (rows - cols < reach), s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v,
                          precision=HI)
    o = jax.lax.map(block, jnp.arange(S // Q_BLOCK)).reshape(S, H * hd)
    o = o * jax.nn.sigmoid(jnp.dot(h, _w(a["wg"], weights), precision=HI))
    return x + _rms(jnp.dot(o, _w(a["wo"], weights), precision=HI),
                    p["ln_post_attn"], eps)


def _dense_mlp(h, m, *, weights):
    return _swiglu(h, m["w_gate"], m["w_up"], m["w_down"], weights)


def _moe_mlp(h, m, *, route, weights):
    k_top, scale, offset = route
    s = jax.nn.sigmoid(jnp.dot(h, _w(m["router"], weights), precision=HI))
    _, idx = jax.lax.top_k(s + m["bias"].astype(jnp.float32), k_top)
    w = jnp.take_along_axis(s, idx, -1)
    w = w / jnp.sum(w, -1, keepdims=True) * scale
    held = m["w_gate"].shape[0]
    # (S, held): the weight of each held expert at each row, 0 if unchosen
    comb = jnp.einsum("sk,ske->se", w,
                      jax.nn.one_hot(idx - offset, held, dtype=jnp.float32))

    def one(e, y):
        return y + _swiglu(h, m["w_gate"][e], m["w_up"][e], m["w_down"][e],
                           weights) * comb[:, e][:, None]
    y = jax.lax.fori_loop(0, held, one, jnp.zeros_like(h))
    if "shared" in m:
        sh = m["shared"]
        y = y + _swiglu(h, sh["w_gate"], sh["w_up"], sh["w_down"], weights)
    return y


@functools.partial(jax.jit, static_argnames=("dims", "route", "n_dense",
                                             "weights"))
def _layers(x, params, windows, *, dims, route, n_dense, weights):
    """Every layer in turn (a scan: one layer's weights upcast at a time),
    each layer's window from `windows`."""
    eps = dims[-1]

    def body(x, inp):
        i, window = inp
        p = _at(params["layers"], i)
        x = _attn(x, p, window, dims=dims, weights=weights)
        h = _rms(x, p["ln_pre_mlp"], eps)
        y = jax.lax.cond(
            i < n_dense,
            lambda h: _dense_mlp(h, _at(params["dense"],
                                        jnp.minimum(i, n_dense - 1)),
                                 weights=weights),
            lambda h: _moe_mlp(h, _at(params["moe"],
                                      jnp.maximum(i - n_dense, 0)),
                               route=route, weights=weights), h)
        return x + _rms(y, p["ln_post_mlp"], eps), None
    x, _ = jax.lax.scan(body, x, (jnp.arange(windows.shape[0]), windows))
    return x


@functools.partial(jax.jit, static_argnames=("eps", "weights"))
def _head(x, norm, head, *, eps, weights):
    return jnp.dot(_rms(x, norm, eps), _w(head, weights), precision=HI)


@functools.partial(jax.jit, static_argnames=("scale", "weights"))
def _embed(table, tokens, *, scale, weights):
    return _w(table[tokens], weights) * scale


def logits(cfg: dict, params, seq: np.ndarray, rows: np.ndarray,
           weights: str = "float32") -> np.ndarray:
    """Logits (len(rows), vocab) at positions `rows` of token sequence
    `seq`, each predicting the token after it."""
    S, n = len(seq), len(rows)
    toks = np.zeros(_blocks(S) * Q_BLOCK, np.int32)
    toks[:S] = seq
    rows = np.resize(np.asarray(rows, np.int32), -(-n // ROWS) * ROWS)
    eps = float(cfg["rms_norm_eps"])
    dims = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], float(cfg["rope_theta"]), eps)
    route = (cfg["num_experts_per_tok"], float(cfg["route_scale"]),
             int(cfg["expert_offset"]))
    nd = cfg["num_dense_layers"]
    scale = float(cfg["hidden_size"]) ** 0.5 if cfg["mup_enabled"] else 1.0
    x = _embed(params["embed"], jnp.asarray(toks), scale=scale,
               weights=weights)
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    windows = jnp.asarray([cfg["sliding_window"] if k == "sliding_attention"
                           else 0 for k in kinds], jnp.int32)
    x = _layers(x, params, windows, dims=dims, route=route, n_dense=nd,
                weights=weights)
    out = _head(x[jnp.asarray(rows)], params["final_norm"], params["lm_head"],
                eps=eps, weights=weights)
    return np.asarray(out[:n], np.float32)
