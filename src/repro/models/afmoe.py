"""The afmoe block (Trinity): windowed layers beside full ones, a dense
lead, then sigmoid-routed experts of which this chip holds a share.

For layer i of hidden states x, with RMSNorms n_* of the layer:

    h   = n_in(x)
    q   = n_q(h W_q), k = n_k(h W_k), v = h W_v     (n_q, n_k over head_dim)
    q,k = RoPE(q, k)                    on sliding layers only
    o   = attention(q, k, v)            causal; window W on sliding layers
    x  += n_post_attn((o * sigmoid(h W_g)) W_o)
    h   = n_pre_mlp(x)
    x  += n_post_mlp(mlp(h))

mlp is a SwiGLU of width d_ff on the first `num_dense_layers` layers.  On
the others it is the shared expert plus the routed ones:
s = sigmoid(h W_r) over all E experts, the K experts of largest s + b (b
the expert bias) are chosen, each weighted by its s normalised over the K
and times `route_scale`.  This chip computes only the chosen experts it
holds, [expert_offset, expert_offset + experts_held): the others' terms are
left out here as on any one chip of an expert-parallel deployment (the
exchange between chips is not run).  Embeddings are scaled by
`embed_scale` (muP).

Each expert runs over the rows routed to it, gathered up to a capacity
(`capacity`) and through the whole block past it, so no row is dropped;
an expert no row reaches is skipped, which in decode means its weights are
not read.  Rows move by gathers alone (`expert_share`).  `models/moe.py`
(the moe family's full forward) routes by softmax, drops the tokens past
each expert's capacity and dispatches by einsum over every expert, for
GSPMD to shard; this layer routes by sigmoid plus a bias, drops no token
and computes one chip's share, so the two share no dispatch code.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models import layers as L
from repro.models.model import lm_logits

Params = Dict[str, Any]
HI = jax.lax.Precision.HIGHEST


def init_params(rng: jax.Array, cfg: ModelConfig) -> Params:
    dt = jnp.dtype(cfg.dtype)
    d, V, nl, hd = cfg.d_model, cfg.padded_vocab, cfg.num_layers, cfg.head_dim
    H, KV, E = cfg.num_heads, cfg.num_kv_heads, cfg.num_experts
    nd, f, fs = cfg.num_dense_layers, cfg.moe_d_ff, cfg.shared_d_ff
    nm, eh = nl - nd, cfg.held_experts
    ks = iter(jax.random.split(rng, 32))

    def normal(shape, std, dtype=dt):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * std).astype(dtype)

    def norm_w(shape):
        return (1.0 + 0.1 * jax.random.normal(next(ks), shape)).astype(dt)

    def swiglu(n, width, lead=()):
        return {"w_gate": normal((n,) + lead + (d, width), d ** -0.5),
                "w_up": normal((n,) + lead + (d, width), d ** -0.5),
                "w_down": normal((n,) + lead + (width, d), width ** -0.5)}
    moe = {"router": normal((nm, d, E), d ** -0.5, jnp.float32),
           "bias": normal((nm, E), 0.1, jnp.float32),
           **swiglu(nm, f, (eh,))}
    if fs:
        moe["shared"] = swiglu(nm, fs)
    return {
        "embed": normal((V, d), d ** -0.5),
        "final_norm": norm_w((d,)),
        "lm_head": normal((d, V), d ** -0.5),
        "layers": {
            "attn": {"wq": normal((nl, d, H * hd), d ** -0.5),
                     "wk": normal((nl, d, KV * hd), d ** -0.5),
                     "wv": normal((nl, d, KV * hd), d ** -0.5),
                     "wg": normal((nl, d, H * hd), d ** -0.5),
                     "wo": normal((nl, H * hd, d), (H * hd) ** -0.5),
                     "q_norm": norm_w((nl, hd)), "k_norm": norm_w((nl, hd))},
            "ln_in": norm_w((nl, d)), "ln_post_attn": norm_w((nl, d)),
            "ln_pre_mlp": norm_w((nl, d)), "ln_post_mlp": norm_w((nl, d)),
        },
        "dense": swiglu(nd, cfg.d_ff),
        "moe": moe,
    }


def embed(cfg: ModelConfig, params: Params, tokens: jax.Array) -> jax.Array:
    x = params["embed"][tokens].astype(jnp.dtype(cfg.dtype))
    return x * jnp.asarray(cfg.embed_scale, x.dtype)


def is_sliding(cfg: ModelConfig, i) -> jax.Array:
    """Whether (traced) layer i is a windowed one."""
    return jnp.asarray([cfg.layer_window(j) > 0
                        for j in range(cfg.num_layers)])[i]


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def project(cfg: ModelConfig, pa: Params, h: jax.Array):
    """h (..., d) -> q (..., H, hd), k and v (..., KV, hd), q and k
    normalised over head_dim, and the gate sigmoid(h W_g) (..., H*hd)."""
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    lead = h.shape[:-1]
    def normed(w, n, norm):
        y = jnp.dot(h, w.astype(h.dtype), preferred_element_type=jnp.float32)
        return L.rms_norm(y.reshape(lead + (n, hd)), norm,
                          cfg.norm_eps).astype(h.dtype)
    q = normed(pa["wq"], H, pa["q_norm"])
    k = normed(pa["wk"], KV, pa["k_norm"])
    v = L.linear(h, pa["wv"]).reshape(lead + (KV, hd))
    return q, k, v, jax.nn.sigmoid(L.linear(h, pa["wg"]))


def attention(cfg: ModelConfig, pa: Params, h: jax.Array, positions,
              sliding, *, attn_fn=None):
    """Prefill attention of one layer: h (B, S, d) at `positions` (B, S);
    `sliding` (traced bool) picks RoPE and the window.  Returns the
    projected output (B, S, d) and the layer's K, V (B, KV, S, hd).
    `attn_fn(qh, kh, vh, causal=, sliding_window=)` replaces the core
    attention, as in `layers.attention_block`."""
    B, S, _ = h.shape
    W = cfg.sliding_window
    q, k, v, g = project(cfg, pa, h)
    core = attn_fn or (lambda qh, kh, vh, *, causal, sliding_window:
                       ops.attention(qh, kh, vh, causal=causal,
                                     sliding_window=sliding_window,
                                     impl="xla"))

    def run(window, rotate):
        def f(q, k, v):
            if rotate:
                q = L.rope(q, positions, cfg.rope_theta)
                k = L.rope(k, positions, cfg.rope_theta)
            kh, vh = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
            o = core(q.transpose(0, 2, 1, 3), kh, vh, causal=True,
                     sliding_window=window)
            return o, kh, vh
        return f
    o, kh, vh = jax.lax.cond(sliding, run(W, True), run(0, False), q, k, v)
    o = o.transpose(0, 2, 1, 3).reshape(B, S, -1) * g
    return L.linear(o, pa["wo"]), kh, vh


# ---------------------------------------------------------------------------
# MLP: the dense lead and the expert share
# ---------------------------------------------------------------------------
def _swiglu(x, wg, wu, wd):
    dt = x.dtype
    h = jax.nn.silu(x @ wg.astype(dt)) * (x @ wu.astype(dt))
    return h @ wd.astype(dt)


def capacity(cfg: ModelConfig, T: int) -> int:
    """Rows an expert gathers before it runs over the whole block: twice
    the mean load, so that only a skewed expert takes the dense path."""
    mean = T * cfg.experts_per_token / cfg.num_experts
    return int(min(T, -(-(2 * mean + 16) // 8) * 8))


def route(cfg: ModelConfig, pm: Params, j, h: jax.Array):
    """Router of MoE layer j over h (T, d): the chosen experts (T, K) and
    their weights (T, K), float32."""
    logits = jnp.dot(h.astype(jnp.float32), pm["router"][j], precision=HI)
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + pm["bias"][j], cfg.experts_per_token)
    w = jnp.take_along_axis(s, idx, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * cfg.route_scale
    return idx, w


def expert_share(cfg: ModelConfig, pm: Params, j, h: jax.Array,
                 mask=None):
    """MoE layer j over rows h (T, d): the held experts' weighted outputs
    plus the shared expert's, (T, d) float32, and the number of (row,
    chosen expert) pairs that landed on a held expert.  Rows where `mask`
    is False (idle decode slots) route nowhere.

    Gathers only, no scatter: the assignments are sorted by expert, each
    expert gathers its rows from its group's place in that order, writes
    its outputs into a (held, capacity) buffer, and every choice reads
    its output back from there."""
    T, K = h.shape[0], cfg.experts_per_token
    nh, off = cfg.held_experts, cfg.expert_offset
    idx, w = route(cfg, pm, j, h)
    local = idx - off
    held = (local >= 0) & (local < nh)
    if mask is not None:
        held &= mask[:, None]
    # assignments grouped by expert (nh: not held here), and each one's
    # place within its expert's group
    key = jnp.where(held, local, nh).reshape(-1)
    order = jnp.argsort(key, stable=True)
    eq = key[:, None] == jnp.arange(nh + 1)[None]
    counts = jnp.sum(eq, 0)
    starts = jnp.cumsum(counts) - counts
    place = (jnp.argsort(order)
             - jnp.sum(jnp.where(eq, starts[None], 0), 1)).reshape(T, K)
    C = capacity(cfg, T)
    rows_of = jnp.concatenate([order // K, jnp.zeros((C,), order.dtype)])
    comb = jnp.einsum("tk,tke->te", jnp.where(held, w, 0.0),
                      jax.nn.one_hot(local, nh, dtype=jnp.float32))

    def expert(e, carry):
        # the expert's weights are read inside the branch that runs it, so
        # an expert no row reaches reads none
        def weights():
            return (pm[k][j, e] for k in ("w_gate", "w_up", "w_down"))

        def gathered(carry):
            y, outs = carry
            rows = jax.lax.dynamic_slice_in_dim(rows_of, starts[e], C)
            return y, jax.lax.dynamic_update_index_in_dim(
                outs, _swiglu(h[rows], *weights()), e, 0)

        def dense(carry):
            y, outs = carry
            return y + _swiglu(h, *weights()).astype(jnp.float32) \
                * comb[:, e][:, None], outs
        n = counts[e]
        which = jnp.where(n == 0, 0, jnp.where(n <= C, 1, 2))
        return jax.lax.switch(which, [lambda c: c, gathered, dense], carry)
    y, outs = jax.lax.fori_loop(
        0, nh, expert, (jnp.zeros(h.shape, jnp.float32),
                        jnp.zeros((nh, C, h.shape[1]), h.dtype)))
    # each choice reads its gathered output back; an expert that ran over
    # the whole block left its outputs zero
    outs = outs.reshape(nh * C, -1)
    for k in range(K):
        ok = held[:, k] & (place[:, k] < C)
        at = jnp.where(ok, local[:, k] * C + place[:, k], 0)
        y = y + jnp.where(ok, w[:, k], 0.0)[:, None] \
            * outs[at].astype(jnp.float32)
    if "shared" in pm:
        sh = pm["shared"]
        y = y + _swiglu(h, sh["w_gate"][j], sh["w_up"][j],
                        sh["w_down"][j]).astype(jnp.float32)
    return y, held.sum(dtype=jnp.int32)


def mlp(cfg: ModelConfig, params: Params, i, h: jax.Array, mask=None,
        dense_lead=None):
    """Layer i's MLP over rows h (T, d): the dense SwiGLU on the lead
    layers, the expert share after them.  Returns (y (T, d), held
    assignments).  `dense_lead` (static), where the caller knows whether
    layer i is a lead one, picks the branch without a `cond`."""
    nd = cfg.num_dense_layers

    def dense(h):
        p = params["dense"]
        k = jnp.minimum(i, nd - 1)
        return _swiglu(h, p["w_gate"][k], p["w_up"][k], p["w_down"][k]), \
            jnp.int32(0)

    def moe(h):
        y, n = expert_share(cfg, params["moe"], jnp.maximum(i - nd, 0), h,
                            mask)
        return y.astype(h.dtype), n
    if dense_lead is not None or not nd:
        return dense(h) if dense_lead else moe(h)
    return jax.lax.cond(i < nd, dense, moe, h)


# ---------------------------------------------------------------------------
# the layer, prefill form
# ---------------------------------------------------------------------------
def layer(cfg: ModelConfig, params: Params, i, x: jax.Array, positions, *,
          attn_fn=None):
    """Layer i (traced) over x (B, S, d).  Returns (x, (k, v)) with k, v
    (B, KV, S, hd) as the cache keeps them (after the norm, and RoPE on a
    sliding layer), and the held assignments of its MLP."""
    pl = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        params["layers"])
    eps = cfg.norm_eps
    a, k, v = attention(cfg, pl["attn"], L.rms_norm(x, pl["ln_in"], eps),
                        positions, is_sliding(cfg, i), attn_fn=attn_fn)
    x = x + L.rms_norm(a, pl["ln_post_attn"], eps)
    B, S, d = x.shape
    y, n = mlp(cfg, params, i, L.rms_norm(x, pl["ln_pre_mlp"], eps)
               .reshape(B * S, d))
    x = x + L.rms_norm(y.reshape(B, S, d), pl["ln_post_mlp"], eps)
    return x, (k, v), n


def forward(cfg: ModelConfig, params: Params, tokens: jax.Array):
    """Full-sequence logits (B, S, V) — the test path; the engine runs the
    same `layer` a quantum at a time."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    x = embed(cfg, params, tokens)

    def body(x, i):
        x, _, _ = layer(cfg, params, i, x, positions)
        return x, None
    x, _ = jax.lax.scan(body, x, jnp.arange(cfg.num_layers))
    return lm_logits(cfg, params, x)

