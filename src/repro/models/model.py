"""Composable model definition: one init/forward/prefill/decode_step API over
six families (dense, moe, ssm, hybrid, audio enc-dec, vlm).

Layer parameters are stacked on a leading L axis and consumed with lax.scan,
so an 80-layer 76B model lowers as one scanned layer — this keeps the
multi-pod dry-run compiles tractable and is also what a production TPU stack
does (MaxText-style).

Cache layout (dict):
  len       (B,) int32                  tokens already decoded (incl. prefill)
  k, v      (L, B, KV, S_max, hd)       attention families
  ssm       SSMState, leading L         ssm / hybrid
  sh_k, sh_v (Ns, B, KV, S_max, hd)     hybrid shared-attention blocks
  cross_k, cross_v (L, B, KV, F, hd)    enc-dec cross attention (fixed)
  cross_len (B,)
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import mamba2 as M
from repro.models import moe as MOE
from repro.models.layers import KVCache
from repro.models.sharding import constrain

Params = Dict[str, Any]


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ===========================================================================
# Init
# ===========================================================================
def init_params(rng: jax.Array, cfg: ModelConfig) -> Params:
    if cfg.family == "afmoe":
        from repro.models import afmoe
        return afmoe.init_params(rng, cfg)
    dt = _dtype(cfg)
    ks = jax.random.split(rng, 8)
    d, V, nl = cfg.d_model, cfg.padded_vocab, cfg.num_layers
    p: Params = {
        "embed": jax.random.normal(ks[0], (V, d), dt) * 0.02,
        "final_norm": jnp.ones((d,), dt),
        "lm_head": jax.random.normal(ks[1], (d, V), dt) * d ** -0.5,
    }
    fam = cfg.family
    if fam in ("dense", "vlm"):
        p["layers"] = {
            "attn": L.init_attn(ks[2], cfg, nl, dt),
            "mlp": L.init_mlp(ks[3], cfg, nl, dt),
            "ln1": jnp.ones((nl, d), dt), "ln2": jnp.ones((nl, d), dt),
        }
    elif fam == "moe":
        p["layers"] = {
            "attn": L.init_attn(ks[2], cfg, nl, dt),
            "moe": MOE.init_moe(ks[3], cfg, nl, dt),
            "ln1": jnp.ones((nl, d), dt), "ln2": jnp.ones((nl, d), dt),
        }
    elif fam == "ssm":
        p["layers"] = {
            "mamba": M.init_mamba2(ks[2], cfg, nl, dt),
            "ln": jnp.ones((nl, d), dt),
        }
    elif fam == "hybrid":
        p["layers"] = {
            "mamba": M.init_mamba2(ks[2], cfg, nl, dt),
            "ln": jnp.ones((nl, d), dt),
        }
        p["shared"] = {  # ONE shared attention+MLP block (Zamba2-style)
            "attn": L.init_attn(ks[4], cfg, 1, dt),
            "mlp": L.init_mlp(ks[5], cfg, 1, dt),
            "ln1": jnp.ones((1, d), dt), "ln2": jnp.ones((1, d), dt),
        }
        p["shared"] = jax.tree.map(lambda a: a[0], p["shared"])  # unstack
    elif fam == "audio":
        ne = cfg.encoder_layers
        p["encoder"] = {
            "attn": L.init_attn(ks[2], cfg, ne, dt),
            "mlp": L.init_mlp(ks[3], cfg, ne, dt),
            "ln1": jnp.ones((ne, d), dt), "ln2": jnp.ones((ne, d), dt),
        }
        p["enc_norm"] = jnp.ones((d,), dt)
        p["layers"] = {  # decoder
            "attn": L.init_attn(ks[4], cfg, nl, dt),
            "xattn": L.init_attn(ks[5], cfg, nl, dt),
            "mlp": L.init_mlp(ks[6], cfg, nl, dt),
            "ln1": jnp.ones((nl, d), dt), "ln2": jnp.ones((nl, d), dt),
            "ln3": jnp.ones((nl, d), dt),
        }
    else:
        raise ValueError(fam)
    return p


def layer_at(layers: Params, i) -> Params:
    """Layer `i` (may be a traced index) of the stacked layer parameters —
    how a program runs a slice of layers without copying the slice."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), layers)


# ===========================================================================
# Full-sequence forward (train / prefill)
# ===========================================================================
def _dense_layer(cfg, pl, x, positions, *, sliding_window, impl, write_cache,
                 attn_fn=None):
    h = L.attention_block(cfg, pl["attn"], L.rms_norm(x, pl["ln1"], cfg.norm_eps),
                          positions, sliding_window=sliding_window,
                          write_cache=write_cache, impl=impl, attn_fn=attn_fn)
    if write_cache:
        h, kv = h
    x = x + h
    x = x + L.swiglu(L.rms_norm(x, pl["ln2"], cfg.norm_eps), pl["mlp"])
    x = constrain(x, "batch", "seq", None)
    return (x, kv) if write_cache else (x, None)


def _moe_layer(cfg, pl, x, positions, *, impl, write_cache, moe_cf=None):
    h = L.attention_block(cfg, pl["attn"], L.rms_norm(x, pl["ln1"], cfg.norm_eps),
                          positions, write_cache=write_cache, impl=impl)
    if write_cache:
        h, kv = h
    x = x + h
    y, aux = MOE.moe_block(cfg, pl["moe"], L.rms_norm(x, pl["ln2"], cfg.norm_eps),
                           capacity_factor=moe_cf)
    x = constrain(x + y, "batch", "seq", None)
    return x, (kv if write_cache else None), aux


def _shared_block(cfg, ps, x, positions, *, impl, write_cache):
    h = L.attention_block(cfg, ps["attn"], L.rms_norm(x, ps["ln1"], cfg.norm_eps),
                          positions, write_cache=write_cache, impl=impl)
    if write_cache:
        h, kv = h
    x = x + h
    x = x + L.swiglu(L.rms_norm(x, ps["ln2"], cfg.norm_eps), ps["mlp"])
    return (x, kv) if write_cache else (x, None)


def forward(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array], *,
            impl: str = "auto", remat: bool = False, write_cache: bool = False,
            sliding_window: Optional[int] = None, moe_cf: Optional[float] = None
            ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Teacher-forced full-sequence forward.

    batch: tokens (B, S) [, embeds (B, P, d) for vlm][, frames (B, F, d) audio].
    Returns (logits (B, S_total, V), aux). aux carries moe losses and (when
    write_cache) the stacked per-layer KV for prefill.
    """
    fam = cfg.family
    dt = _dtype(cfg)
    sw = cfg.sliding_window if sliding_window is None else sliding_window
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = params["embed"][tokens].astype(dt)
    x = constrain(x, "batch", "seq", None)
    n_prefix = 0
    if fam == "vlm":
        emb = batch["embeds"].astype(dt)                    # (B, P, d)
        x = jnp.concatenate([emb, x], axis=1)
        n_prefix = emb.shape[1]
    positions = jnp.arange(x.shape[1])[None]                # (1, S_total)
    positions = jnp.broadcast_to(positions, (B, x.shape[1]))
    aux: Dict[str, Any] = {}

    if fam in ("dense", "vlm", "moe"):
        def body(carry, pl):
            x = carry
            if fam == "moe":
                x, kv, a = _moe_layer(cfg, pl, x, positions, impl=impl,
                                      write_cache=write_cache, moe_cf=moe_cf)
                return x, (kv, a)
            x, kv = _dense_layer(cfg, pl, x, positions, sliding_window=sw,
                                 impl=impl, write_cache=write_cache)
            return x, kv
        body_fn = jax.checkpoint(body) if remat else body
        x, ys = jax.lax.scan(body_fn, x, params["layers"])
        if fam == "moe":
            kvs, a = ys
            aux["lb_loss"] = a["lb_loss"].mean()
            aux["dropped_frac"] = a["dropped_frac"].mean()
        else:
            kvs = ys
        if write_cache:
            aux["kv"] = kvs

    elif fam == "ssm":
        def body(carry, pl):
            x = carry
            h = M.mamba2_block(cfg, pl["mamba"],
                               L.rms_norm(x, pl["ln"], cfg.norm_eps),
                               return_state=write_cache, impl=impl)
            if write_cache:
                h, st = h
                return constrain(x + h, "batch", "seq", None), st
            return constrain(x + h, "batch", "seq", None), None
        body_fn = jax.checkpoint(body) if remat else body
        x, sts = jax.lax.scan(body_fn, x, params["layers"])
        if write_cache:
            aux["ssm"] = sts

    elif fam == "hybrid":
        # Two-level scan (§Perf iter A'): outer over segments, inner over the
        # attn_every Mamba2 layers, shared attention block closed over —
        # ONE HLO copy of the segment instead of n_seg python-unrolled copies
        # (compile size, bf16-legalization copies and remat residency all
        # shrink by ~n_seg).
        k = cfg.attn_every
        nl = cfg.num_layers
        assert nl % k == 0, "hybrid layers must be a multiple of attn_every"
        n_seg = nl // k
        seg_params = jax.tree.map(
            lambda a: a.reshape((n_seg, k) + a.shape[1:]), params["layers"])
        shared = params["shared"]

        def seg_body(carry, pseg):
            x = carry

            def body(c, pl):
                h = M.mamba2_block(cfg, pl["mamba"],
                                   L.rms_norm(c, pl["ln"], cfg.norm_eps),
                                   return_state=write_cache, impl=impl)
                if write_cache:
                    h, st = h
                    return constrain(c + h, "batch", "seq", None), st
                return constrain(c + h, "batch", "seq", None), None
            x, st = jax.lax.scan(body, x, pseg)
            x, shkv = _shared_block(cfg, shared, x, positions,
                                    impl=impl, write_cache=write_cache)
            if write_cache:
                return x, (st, shkv)
            return x, None
        seg_fn = jax.checkpoint(seg_body) if remat else seg_body
        x, ys = jax.lax.scan(seg_fn, x, seg_params)
        if write_cache:
            sts, sh_kvs = ys
            aux["ssm"] = jax.tree.map(
                lambda a: a.reshape((nl,) + a.shape[2:]), sts)
            aux["sh_kv"] = sh_kvs

    elif fam == "audio":
        enc_x = batch["frames"].astype(dt)                  # (B, F, d)
        enc_pos = jnp.broadcast_to(jnp.arange(enc_x.shape[1])[None],
                                   (B, enc_x.shape[1]))

        def enc_body(carry, pl):
            x = carry
            h = L.attention_block(cfg, pl["attn"],
                                  L.rms_norm(x, pl["ln1"], cfg.norm_eps),
                                  enc_pos, causal=False, impl=impl)
            x = x + h
            x = x + L.swiglu(L.rms_norm(x, pl["ln2"], cfg.norm_eps), pl["mlp"])
            return x, None
        enc_fn = jax.checkpoint(enc_body) if remat else enc_body
        enc_x, _ = jax.lax.scan(enc_fn, enc_x, params["encoder"])
        enc_out = L.rms_norm(enc_x, params["enc_norm"], cfg.norm_eps)
        aux["enc_out"] = enc_out

        def dec_body(carry, pl):
            x = carry
            h = L.attention_block(cfg, pl["attn"],
                                  L.rms_norm(x, pl["ln1"], cfg.norm_eps),
                                  positions, write_cache=write_cache, impl=impl)
            if write_cache:
                h, kv = h
            x = x + h
            # cross attention: project enc_out to K/V each layer
            cross_kv = _project_cross(cfg, pl["xattn"], enc_out)
            xh = L.attention_block(
                cfg, pl["xattn"], L.rms_norm(x, pl["ln2"], cfg.norm_eps),
                positions, impl=impl, cross_kv=cross_kv)
            x = x + xh
            x = x + L.swiglu(L.rms_norm(x, pl["ln3"], cfg.norm_eps), pl["mlp"])
            if write_cache:
                return x, (kv, cross_kv)
            return x, None
        dec_fn = jax.checkpoint(dec_body) if remat else dec_body
        x, ys = jax.lax.scan(dec_fn, x, params["layers"])
        if write_cache:
            aux["kv"], aux["cross_kv"] = ys
    else:
        raise ValueError(fam)

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if n_prefix:
        x = x[:, n_prefix:]
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(dt))
    logits = constrain(logits, "batch", None, "vocab")  # vocab priority
    logits = _mask_padded_vocab(cfg, logits)
    return logits, aux


def _mask_padded_vocab(cfg: ModelConfig, logits: jax.Array) -> jax.Array:
    """Vocab is padded to a TP-friendly multiple (ModelConfig.padded_vocab);
    padding positions never win softmax/argmax."""
    if cfg.padded_vocab == cfg.vocab_size:
        return logits
    pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
    return jnp.where(pad_mask, jnp.asarray(-1e30, logits.dtype), logits)


def _project_cross(cfg: ModelConfig, p, enc_out: jax.Array) -> KVCache:
    """Project encoder output to a cross-attention KVCache (B, KV, F, hd)."""
    Bsz, F, _ = enc_out.shape
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    k = L.linear(enc_out, p["wk"], p.get("bk")).reshape(Bsz, F, KV, hd)
    v = L.linear(enc_out, p["wv"], p.get("bv")).reshape(Bsz, F, KV, hd)
    return KVCache(k=k.transpose(0, 2, 1, 3), v=v.transpose(0, 2, 1, 3))


def loss_fn(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array], *,
            impl: str = "auto", remat: bool = False, loss_chunk: int = 512):
    logits, aux = forward(cfg, params, batch, impl=impl, remat=remat)
    tokens = batch["tokens"]
    labels = batch.get("labels")
    if labels is None:
        labels = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)), constant_values=-1)
    B, S, V = logits.shape
    # §Perf iter D: chunk the f32 softmax over the sequence — the full
    # (B,S,V) f32 log-softmax (+ its backward) dominated train memory for
    # 200K+ vocabs (minitron/internvl); per-chunk peak is (B,chunk,V).
    ck = min(loss_chunk, S)
    while S % ck:
        ck -= 1          # largest divisor of S below the target chunk

    def chunk_nll(args):
        lg, lb = args                              # (B, ck, V), (B, ck)
        logp = jax.nn.log_softmax(lg.astype(jnp.float32), axis=-1)
        valid = lb >= 0
        safe = jnp.where(valid, lb, 0)
        nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        return (nll * valid).sum(), valid.sum()

    n = S // ck
    lg_c = logits.reshape(B, n, ck, V).transpose(1, 0, 2, 3)
    lb_c = labels.reshape(B, n, ck).transpose(1, 0, 2)
    sums, counts = jax.lax.map(jax.checkpoint(chunk_nll), (lg_c, lb_c))
    loss = sums.sum() / jnp.maximum(counts.sum(), 1)
    if "lb_loss" in aux:
        loss = loss + 0.01 * aux["lb_loss"]
    aux["ce_loss"] = loss
    return loss, aux


# ===========================================================================
# Serving: cache init / prefill / decode_step
# ===========================================================================
def init_cache(cfg: ModelConfig, batch: int, max_len: int, *,
               enc_len: int = 0) -> Dict[str, Any]:
    dt = _dtype(cfg)
    nl, KV, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    cache: Dict[str, Any] = {"len": jnp.zeros((batch,), jnp.int32)}
    fam = cfg.family
    if fam in ("dense", "vlm", "moe", "audio"):
        cache["k"] = jnp.zeros((nl, batch, KV, max_len, hd), dt)
        cache["v"] = jnp.zeros((nl, batch, KV, max_len, hd), dt)
    if fam in ("ssm", "hybrid"):
        cache["ssm"] = M.init_ssm_state(cfg, nl, batch, dt)
    if fam == "hybrid":
        ns = -(-nl // cfg.attn_every)
        cache["sh_k"] = jnp.zeros((ns, batch, KV, max_len, hd), dt)
        cache["sh_v"] = jnp.zeros((ns, batch, KV, max_len, hd), dt)
    if fam == "audio":
        cache["cross_k"] = jnp.zeros((nl, batch, KV, enc_len, hd), dt)
        cache["cross_v"] = jnp.zeros((nl, batch, KV, enc_len, hd), dt)
        cache["cross_len"] = jnp.zeros((batch,), jnp.int32)
    return cache


def prefill(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array],
            cache: Dict[str, Any], *, impl: str = "auto",
            sliding_window: Optional[int] = None,
            moe_cf: Optional[float] = None):
    """Run full-sequence prefill, fill the cache, return (last-token logits, cache).

    For audio (enc-dec), batch["frames"] is encoded and only BOS enters the
    decoder; batch["tokens"] should then be (B, 1).
    """
    logits, aux = forward(cfg, params, batch, impl=impl, write_cache=True,
                          sliding_window=sliding_window, moe_cf=moe_cf)
    tokens = batch["tokens"]
    B, S = tokens.shape
    S_total = S + (batch["embeds"].shape[1] if cfg.family == "vlm" else 0)

    if "kv" in aux:  # stacked (L, B, KV, S_total, hd)
        kvs = aux["kv"]
        cache["k"] = _write_prefix(cache["k"], kvs.k)
        cache["v"] = _write_prefix(cache["v"], kvs.v)
    if "ssm" in aux:
        cache["ssm"] = M.SSMState(conv=aux["ssm"].conv.astype(cache["ssm"].conv.dtype),
                                  ssm=aux["ssm"].ssm)
    if "sh_kv" in aux:
        cache["sh_k"] = _write_prefix(cache["sh_k"], aux["sh_kv"].k)
        cache["sh_v"] = _write_prefix(cache["sh_v"], aux["sh_kv"].v)
    if "cross_kv" in aux:
        cache["cross_k"] = _write_prefix(cache["cross_k"], aux["cross_kv"].k)
        cache["cross_v"] = _write_prefix(cache["cross_v"], aux["cross_kv"].v)
        cache["cross_len"] = jnp.full((B,), aux["enc_out"].shape[1], jnp.int32)
    cache["len"] = jnp.full((B,), S_total, jnp.int32)
    return logits[:, -1], cache


def _cache_maxlen(cache, cfg):
    if "k" in cache:
        return cache["k"].shape[3]
    return cache["sh_k"].shape[3] if "sh_k" in cache else 0


def _write_prefix(dst: jax.Array, src: jax.Array) -> jax.Array:
    """dst (L,B,KV,S_max,hd) <- src (L,B,KV,S,hd) at offset 0 (or truncate)."""
    S_max, S = dst.shape[3], src.shape[3]
    if S <= S_max:
        return jax.lax.dynamic_update_slice_in_dim(dst, src.astype(dst.dtype), 0, axis=3)
    # SWA ring buffer: keep the most recent window, placed so that token t
    # sits at slot t % S_max (decode writes at cache_len % S_max)
    recent = src[:, :, :, S - S_max:].astype(dst.dtype)
    return jnp.roll(recent, S % S_max, axis=3)


def decode_step(cfg: ModelConfig, params: Params, cache: Dict[str, Any],
                token: jax.Array, *, impl: str = "auto",
                ring_buffer: bool = False):
    """token (B,) int32 -> (logits (B, V), new cache). One serve_step."""
    fam = cfg.family
    dt = _dtype(cfg)
    x = params["embed"][token].astype(dt)                   # (B, d)
    x = constrain(x, "batch", None)
    clen = cache["len"]
    sw = cfg.sliding_window

    if fam in ("dense", "vlm", "moe"):
        def body(x, inp):
            pl, (ck, cv) = inp
            h, kv = L.decode_attention_block(
                cfg, pl["attn"], L.rms_norm(x, pl["ln1"], cfg.norm_eps),
                KVCache(ck, cv), clen, sliding_window=0 if ring_buffer else sw,
                ring_buffer=ring_buffer, impl=impl)
            x = x + h
            if fam == "moe":
                # decode capacity: bounded cf (§Perf iter B) unless the
                # config asks for the provably-dropless cf=E
                cf = min(float(cfg.decode_capacity_factor),
                         float(cfg.num_experts))
                y, _ = MOE.moe_block(cfg, pl["moe"],
                                     L.rms_norm(x, pl["ln2"], cfg.norm_eps)[:, None],
                                     capacity_factor=cf)
                x = x + y[:, 0]
            else:
                x = x + L.swiglu(L.rms_norm(x, pl["ln2"], cfg.norm_eps), pl["mlp"])
            return x, kv
        x, kvs = jax.lax.scan(body, x, (params["layers"], (cache["k"], cache["v"])))
        cache = dict(cache, k=kvs.k, v=kvs.v)

    elif fam == "ssm":
        def body(x, inp):
            pl, st = inp
            h, st2 = M.mamba2_step(cfg, pl["mamba"],
                                   L.rms_norm(x, pl["ln"], cfg.norm_eps), st)
            return x + h, st2
        x, sts = jax.lax.scan(body, x, (params["layers"], cache["ssm"]))
        cache = dict(cache, ssm=sts)

    elif fam == "hybrid":
        k = cfg.attn_every
        nl = cfg.num_layers
        assert nl % k == 0
        n_seg = nl // k
        seg_params = jax.tree.map(
            lambda a: a.reshape((n_seg, k) + a.shape[1:]), params["layers"])
        seg_state = jax.tree.map(
            lambda a: a.reshape((n_seg, k) + a.shape[1:]), cache["ssm"])
        ps = params["shared"]

        def seg_body(x, inp):
            pseg, st_seg, shk, shv = inp

            def body(c, inner):
                pl, st = inner
                h, st2 = M.mamba2_step(cfg, pl["mamba"],
                                       L.rms_norm(c, pl["ln"], cfg.norm_eps),
                                       st)
                return c + h, st2
            x, sts = jax.lax.scan(body, x, (pseg, st_seg))
            h, shkv = L.decode_attention_block(
                cfg, ps["attn"], L.rms_norm(x, ps["ln1"], cfg.norm_eps),
                KVCache(shk, shv), clen, ring_buffer=ring_buffer, impl=impl)
            x = x + h
            x = x + L.swiglu(L.rms_norm(x, ps["ln2"], cfg.norm_eps), ps["mlp"])
            return x, (sts, shkv.k, shkv.v)
        x, (new_ssm, shk, shv) = jax.lax.scan(
            seg_body, x, (seg_params, seg_state, cache["sh_k"], cache["sh_v"]))
        cache = dict(cache,
                     ssm=jax.tree.map(
                         lambda a: a.reshape((nl,) + a.shape[2:]), new_ssm),
                     sh_k=shk, sh_v=shv)

    elif fam == "audio":
        def body(x, inp):
            pl, (ck, cv, xk, xv) = inp
            h, kv = L.decode_attention_block(
                cfg, pl["attn"], L.rms_norm(x, pl["ln1"], cfg.norm_eps),
                KVCache(ck, cv), clen, impl=impl)
            x = x + h
            h2, _ = L.decode_attention_block(
                cfg, pl["xattn"], L.rms_norm(x, pl["ln2"], cfg.norm_eps),
                KVCache(xk, xv), clen, cross=True, cross_len=cache["cross_len"],
                impl=impl)
            x = x + h2
            x = x + L.swiglu(L.rms_norm(x, pl["ln3"], cfg.norm_eps), pl["mlp"])
            return x, kv
        x, kvs = jax.lax.scan(body, x, (params["layers"],
                                        (cache["k"], cache["v"],
                                         cache["cross_k"], cache["cross_v"])))
        cache = dict(cache, k=kvs.k, v=kvs.v)
    else:
        raise ValueError(fam)

    cache["len"] = clen + 1
    return lm_logits(cfg, params, x), cache


def lm_logits(cfg: ModelConfig, params: Params, x: jax.Array) -> jax.Array:
    """Final norm + LM head over hidden states (..., d) -> (..., V), padded
    vocab masked — the head every serving path (prefill, decode, gang SP)
    shares."""
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("...d,dv->...v", x, params["lm_head"].astype(x.dtype))
    return _mask_padded_vocab(cfg, logits)


def param_count(params: Params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))
