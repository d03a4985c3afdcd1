"""Replica engine: real JAX execution with LAYER-GRANULAR preemptible prefill.

This is the execution-level counterpart of the simulator: PecSched's §5.1
preemption state ("KV of completed layers + one layer's intermediate data")
is exactly what PrefillState holds. A preempted prefill resumes from its
layer index with bit-identical results (asserted in tests).

KV storage is block-granular: every resident request's KV lives in the
replica's `PagedKVCache` (serving/kvcache.py), whether it arrived through
`admit` (a finished local prefill, §5.2 migration), `scatter_kv` (a gang-SP
prefill scattering its sharded KV back to the home replica) or grows token
by token during decode.  Decode slots are thin identities over the pool: a
slot binds a rid into the batched decode step, whose program takes the
pool (donated) and a page table of the bound slots' blocks, writes each
active slot's new KV into its own block and attends over the pool in
place, up to each slot's live length — one KV path for gang scatter,
preemption eviction and decode alike.

The engine serves the dense family (the paper's evaluation models) and
afmoe (`models/afmoe.py`: windowed layers beside full ones, sigmoid-routed
experts of which the chip holds a share).  For afmoe the pool keeps the
windowed layers' KV in rings of the window (serving/kvcache.py): a prefill
keeps only the last `sliding_window` positions of those layers, `admit`
moves only those, and decode writes and reads each windowed layer's ring
from the window's start.  Decode runs slot-batched with per-slot cache
lengths — continuous batching at the iteration level.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ModelConfig
from repro.kernels import flash_decode as fd
from repro.kernels import ops
from repro.models import afmoe
from repro.models import layers as L
from repro.models import model as mdl
from repro.serving.kvcache import PagedKVCache, PrefixHit


class SlotsFull(RuntimeError):
    """A ReplicaEngine cannot admit another resident request.

    Raised consistently for BOTH exhaustion modes — no free decode slot, or
    not enough free KV blocks in the paged pool (e.g. a gang scatter larger
    than the remaining block budget).  Callers (EngineBackend's slot-chunked
    decode, the decode-queue drain) catch it and wait for an eviction rather
    than crashing the serving loop.
    """


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("tokens", "x", "kv_k", "kv_v", "prefix_k",
                                "prefix_v"),
                   meta_fields=("rid", "layer", "host_tokens"))
@dataclass
class PrefillState:
    """Suspension state of a paused prefill (paper §5.1).

    A prefix-cache hit turns this into a SUFFIX prefill: `x` covers only
    the uncached suffix tokens (prefix_len fewer positions of compute per
    layer) while `prefix_k`/`prefix_v` carry the reused KV gathered from
    the pool — `tokens` stays the FULL prompt, and admit() re-assembles
    full-sequence KV, so everything downstream is oblivious to the hit."""
    rid: int
    tokens: jnp.ndarray                   # (1, S) int32 — ALWAYS full prompt
    x: jnp.ndarray                        # (1, S_suffix, d) — intermediate
    layer: int                            # next layer to execute
    #: per layer (1, KV, S_suffix, hd); a windowed layer's keeps only its
    #: last `sliding_window` positions
    kv_k: List[jnp.ndarray] = field(default_factory=list)
    kv_v: List[jnp.ndarray] = field(default_factory=list)
    prefix_k: Optional[jnp.ndarray] = None   # (L, KV, P, hd) reused KV
    prefix_v: Optional[jnp.ndarray] = None
    host_tokens: Optional[Tuple[int, ...]] = None  # full prompt, host ints

    @property
    def prefix_len(self) -> int:
        return 0 if self.prefix_k is None else self.prefix_k.shape[2]

    def intermediate_bytes(self) -> int:
        return self.x.size * self.x.dtype.itemsize

    def kv_bytes(self) -> int:
        return sum(a.size * a.dtype.itemsize for a in self.kv_k) * 2


# ---- compiled programs -----------------------------------------------------
# Module-level and keyed on the (frozen, hashable) config, so every engine of
# one model shares one program per (prompt length, quantum size).  The
# weights are arguments, never constants folded into a program.  What a
# family changes in them is its `Family` entry (end of this section).
@functools.partial(jax.jit, static_argnames=("cfg",))
def _embed(params, tokens, *, cfg: ModelConfig):
    return FAMILIES[cfg.family].embed(cfg, params, tokens)


@functools.partial(jax.jit, static_argnames=("cfg", "n"))
def _prefill_slice(params, x, lo, pk, pv, *, cfg: ModelConfig, n: int):
    """Layers [lo, lo + n) over x (B, S, d).  With pk/pv ((n, KV, P, hd),
    the reused prefix KV of these layers) x covers only the suffix: RoPE
    starts at position P and attention runs over [prefix ‖ suffix] at query
    offset P, through the same layer body as a full prefill.  Returns x,
    the layers' K, V and the (token, chosen expert) pairs that landed on
    held experts."""
    B, S, _ = x.shape
    P = 0 if pk is None else pk.shape[2]
    positions = jnp.broadcast_to(jnp.arange(P, P + S)[None], (B, S))

    def prefix_attn(pkl, pvl):
        if pkl is None:
            return None

        def attn_fn(qh, kh, vh, *, causal, sliding_window):
            k = jnp.concatenate([pkl[None].astype(kh.dtype), kh], axis=2)
            v = jnp.concatenate([pvl[None].astype(vh.dtype), vh], axis=2)
            return ops.attention(qh, k, v, causal=causal,
                                 sliding_window=sliding_window,
                                 q_offset=P, impl="xla")
        return attn_fn

    layer = FAMILIES[cfg.family].prefill_layer

    def body(carry, inp):
        x, held = carry
        i, pkl, pvl = inp
        x, kv, m = layer(cfg, params, i, x, positions, prefix_attn(pkl, pvl))
        return (x, None if m is None else held + m), kv
    held = None if cfg.held_experts == 0 else jnp.int32(0)
    (x, held), kvs = jax.lax.scan(body, (x, held),
                                  (lo + jnp.arange(n), pk, pv))
    return x, kvs, held


@functools.partial(jax.jit, static_argnames=("cfg",))
def _finalize(params, x, *, cfg: ModelConfig):
    return mdl.lm_logits(cfg, params, x[:, -1])


def _decode(params, pool_k, pool_v, table, step, *, cfg: ModelConfig,
            impl: Optional[str] = None):
    """One decode step on the paged pool.  pool_k/pool_v (L, N, KV, page,
    hd), returned updated; table (slots, P) int32, each slot's blocks in
    order; step (3, slots) int32, each slot's token, length and whether it
    is active.  Per layer: each active slot's new K/V goes into its own
    block at `length % page` (an inactive slot's into the pool's last
    block, which no table owns), then attention runs over the slot's
    [0, length + 1) through the table, as `models.model.decode_step` runs
    it over a dense cache.  `impl` defaults to what
    `ops.paged_decode_impl` picks here.

    On an accelerator the pool is donated and updated in place: a copy of
    it is what a step must not make.  On the host the caller's arrays stay
    valid, so a stand-in that hands its input pool back still runs (the
    benchmark's fault test of a step that leaves its state unchanged)."""
    impl = impl or ops.paged_decode_impl(cfg.head_dim)
    program = _decode_program(jax.default_backend() != "cpu")
    return program(params, pool_k, pool_v, table, step, cfg=cfg, impl=impl)


@functools.lru_cache(maxsize=None)
def _decode_program(donate: bool):
    """`_decode`'s program, compiled under that name; `donate` hands it the
    pool to update in place."""
    return jax.jit(_decode_step, static_argnames=("cfg", "impl"),
                   donate_argnums=(1, 2) if donate else ())


def _decode_step(params, pool_k, pool_v, table, step, *, cfg, impl):
    tok, lens, active = step[0], step[1], step[2] > 0
    nl, nb, kvh, page, hd = pool_k.shape
    B, H = tok.shape[0], cfg.num_heads
    # the layer folds into the page axis: layer i's block b is page
    # i * nb + b of one (L * N, KV, page, hd) array the kernel gets whole
    pk = pool_k.reshape(nl * nb, kvh, page, hd)
    pv = pool_v.reshape(nl * nb, kvh, page, hd)
    own = jnp.take_along_axis(table, (lens // page)[:, None], 1)[:, 0]
    dest = jnp.where(active, own, nb - 1)
    off = jnp.where(active, lens % page, 0)
    kv_len = jnp.where(active, lens + 1, 0)
    x = params["embed"][tok].astype(jnp.dtype(cfg.dtype))     # (B, d)

    def body(carry, inp):
        x, pk, pv = carry
        i, pl = inp
        a = pl["attn"]
        h = L.rms_norm(x, pl["ln1"], cfg.norm_eps)
        q = L.linear(h, a["wq"], a.get("bq")).reshape(B, H, hd)
        k = L.linear(h, a["wk"], a.get("bk")).reshape(B, kvh, hd)
        v = L.linear(h, a["wv"], a.get("bv")).reshape(B, kvh, hd)
        q = L.rope(q[:, None], lens[:, None], cfg.rope_theta)[:, 0]
        k = L.rope(k[:, None], lens[:, None], cfg.rope_theta)[:, 0]
        for s in range(B):
            at = (i * nb + dest[s], 0, off[s], 0)
            pk = jax.lax.dynamic_update_slice(
                pk, k[s][None, :, None].astype(pk.dtype), at)
            pv = jax.lax.dynamic_update_slice(
                pv, v[s][None, :, None].astype(pv.dtype), at)
        o = ops.paged_decode_attention(q, pk, pv, table + i * nb, kv_len,
                                       sliding_window=cfg.sliding_window,
                                       impl=impl)
        x = x + L.linear(o.reshape(B, H * hd), a["wo"])
        x = x + L.swiglu(L.rms_norm(x, pl["ln2"], cfg.norm_eps), pl["mlp"])
        return (x, pk, pv), None

    (x, pk, pv), _ = jax.lax.scan(body, (x, pk, pv),
                                  (jnp.arange(nl), params["layers"]))
    return (mdl.lm_logits(cfg, params, x), pk.reshape(pool_k.shape),
            pv.reshape(pool_v.shape))


def _decode_afmoe(params, pools, table, wtable, step, *, cfg: ModelConfig,
                  impl: Optional[str] = None):
    """`_decode` for afmoe: pools = (K, V of the full layers (Lf, N, KV,
    page, hd), K, V of the windowed layers' rings (Lw, Nw, KV, page,
    hd)), returned updated; table (slots, P) each slot's full blocks,
    wtable (slots, Pw) each slot's ring, logical block j at entry j %
    ring.  A windowed layer writes the new KV into the ring and attends
    over [length + 1 - window, length + 1); a full layer as the dense
    step does.  Also returns the number of (active row, chosen expert)
    pairs that landed on held experts."""
    impl = impl or ops.paged_decode_impl(cfg.head_dim)
    program = _decode_afmoe_program(jax.default_backend() != "cpu")
    return program(params, *pools, table, wtable, step, cfg=cfg, impl=impl)


@functools.lru_cache(maxsize=None)
def _decode_afmoe_program(donate: bool):
    return jax.jit(_decode_afmoe_step, static_argnames=("cfg", "impl"),
                   donate_argnums=(1, 2, 3, 4) if donate else ())


def _decode_afmoe_step(params, pool_k, pool_v, wpool_k, wpool_v, table,
                       wtable, step, *, cfg, impl):
    tok, lens, active = step[0], step[1], step[2] > 0
    nf, nb, kvh, page, hd = pool_k.shape
    nw, nwb = wpool_k.shape[:2]
    B, H, W = tok.shape[0], cfg.num_heads, cfg.sliding_window
    ring = -(-W // page) + 1
    eps = cfg.norm_eps
    blk = lens // page
    dest = [jnp.where(active, jnp.take_along_axis(t, b[:, None], 1)[:, 0],
                      n - 1)
            for t, b, n in ((table, blk, nb), (wtable, blk % ring, nwb))]
    off = jnp.where(active, lens % page, 0)
    kv_len = jnp.where(active, lens + 1, 0)
    # per layer: windowed or not, and its index among the layers of its
    # kind.  A layer writes the other kind's pool too, into that pool's
    # scratch block: a pool that passed through a `cond` branch unwritten
    # would be copied whole, where a one-token write costs nothing.
    slide = np.array([cfg.layer_window(i) > 0
                      for i in range(cfg.num_layers)])
    jf = np.minimum(np.cumsum(~slide) - ~slide, nf - 1)
    jw = np.minimum(np.cumsum(slide) - slide, nw - 1)

    def layer(i, carry, dense_lead):
        x, pk, pv, wk, wv, held = carry
        pl = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            params["layers"])
        sl, f, w = (jnp.asarray(a)[i] for a in (slide, jf, jw))
        q, k, v, g = afmoe.project(cfg, pl["attn"],
                                   L.rms_norm(x, pl["ln_in"], eps))
        q = jnp.where(sl, L.rope(q[:, None], lens[:, None],
                                 cfg.rope_theta)[:, 0], q)
        k = jnp.where(sl, L.rope(k[:, None], lens[:, None],
                                 cfg.rope_theta)[:, 0], k)
        for s in range(B):
            kk, vv = k[s][None, :, None], v[s][None, :, None]
            at = (f * nb + jnp.where(sl, nb - 1, dest[0][s]), 0, off[s], 0)
            pk = jax.lax.dynamic_update_slice(pk, kk.astype(pk.dtype), at)
            pv = jax.lax.dynamic_update_slice(pv, vv.astype(pv.dtype), at)
            at = (w * nwb + jnp.where(sl, dest[1][s], nwb - 1), 0, off[s], 0)
            wk = jax.lax.dynamic_update_slice(wk, kk.astype(wk.dtype), at)
            wv = jax.lax.dynamic_update_slice(wv, vv.astype(wv.dtype), at)
        o = jax.lax.cond(
            sl,
            lambda: ops.paged_decode_attention(
                q, wk, wv, wtable + w * nwb, kv_len, sliding_window=W,
                ring=ring, impl=impl),
            lambda: ops.paged_decode_attention(
                q, pk, pv, table + f * nb, kv_len, impl=impl))
        a = L.linear(o.reshape(B, H * hd) * g, pl["attn"]["wo"])
        x = x + L.rms_norm(a, pl["ln_post_attn"], eps)
        y, m = afmoe.mlp(cfg, params, i, L.rms_norm(x, pl["ln_pre_mlp"], eps),
                         active, dense_lead=dense_lead)
        x = x + L.rms_norm(y, pl["ln_post_mlp"], eps)
        return x, pk, pv, wk, wv, held + m

    carry = (afmoe.embed(cfg, params, tok),
             pool_k.reshape(nf * nb, kvh, page, hd),
             pool_v.reshape(nf * nb, kvh, page, hd),
             wpool_k.reshape(nw * nwb, kvh, page, hd),
             wpool_v.reshape(nw * nwb, kvh, page, hd), jnp.int32(0))
    # the dense lead runs unrolled and the expert layers in a loop of
    # their own, so each layer's MLP is known where it is traced: a `cond`
    # between the two in one loop reads the lead's weights on every layer
    nd = cfg.num_dense_layers
    for i in range(nd):
        carry = layer(i, carry, True)
    carry = jax.lax.fori_loop(nd, cfg.num_layers,
                              lambda i, c: layer(i, c, False), carry)
    x, *pools, held = carry
    shapes = (pool_k.shape, pool_v.shape, wpool_k.shape, wpool_v.shape)
    return (mdl.lm_logits(cfg, params, x),
            *(a.reshape(sh) for a, sh in zip(pools, shapes)), held)


# the device trace and the benchmark's readers find the programs by this name
_decode_step.__name__ = _decode_step.__qualname__ = "_decode"
_decode_afmoe_step.__name__ = _decode_afmoe_step.__qualname__ = "_decode"


@dataclass(frozen=True)
class Family:
    """What one model family puts into the engine's programs: its token
    embedding, one prefill layer (cfg, params, i, x, positions, attn_fn) ->
    (x, the layer's KV, held assignments), and its decode step (params,
    the pool's arrays, the page tables, step) -> (logits, arrays, held
    assignments).  `gang` says whether a §5.3 gang (`sp/gang.py`, the
    dense layer body) can prefill it."""
    embed: Callable
    prefill_layer: Callable
    decode: Callable
    gang: bool


def _dense_prefill_layer(cfg, params, i, x, positions, attn_fn):
    x, kv = mdl._dense_layer(cfg, mdl.layer_at(params["layers"], i), x,
                             positions, sliding_window=cfg.sliding_window,
                             impl="xla", write_cache=True, attn_fn=attn_fn)
    return x, kv, None


def _afmoe_prefill_layer(cfg, params, i, x, positions, attn_fn):
    x, (k, v), held = afmoe.layer(cfg, params, i, x, positions,
                                  attn_fn=attn_fn)
    return x, L.KVCache(k=k, v=v), held


def _dense_decode(params, arrays, tables, step, *, cfg):
    logits, k, v = _decode(params, *arrays, *tables, step, cfg=cfg)
    return logits, (k, v), None


def _afmoe_decode(params, arrays, tables, step, *, cfg):
    logits, *arrays, held = _decode_afmoe(params, arrays, *tables, step,
                                          cfg=cfg)
    return logits, arrays, held


FAMILIES = {
    "dense": Family(
        embed=lambda cfg, params, tokens:
            params["embed"][tokens].astype(jnp.dtype(cfg.dtype)),
        prefill_layer=_dense_prefill_layer, decode=_dense_decode,
        gang=True),
    "afmoe": Family(embed=afmoe.embed, prefill_layer=_afmoe_prefill_layer,
                    decode=_afmoe_decode, gang=False),
}


class ReplicaEngine:
    """One model replica: preemptible prefill + slot-batched decode over a
    paged KV pool."""

    def __init__(self, cfg: ModelConfig, params, *, max_slots: int = 8,
                 max_len: int = 512, layers_per_quantum: int = 2,
                 block_size: int = 16, n_blocks: Optional[int] = None):
        if cfg.family not in FAMILIES:
            raise ValueError(f"the engine serves the {tuple(FAMILIES)} "
                             f"families, not {cfg.family!r}")
        self.family = FAMILIES[cfg.family]
        self.cfg = cfg
        self.params = params
        self.max_slots = max_slots
        self.max_len = max_len
        self.lpq = layers_per_quantum
        KV, hd, nl = cfg.num_kv_heads, cfg.head_dim, cfg.num_layers
        dt = jnp.dtype(cfg.dtype)
        self.block_size = block_size
        self.blocks_per_seq = -(-max_len // block_size)
        # Pool invariant: a BOUND slot reserves its full max_len block
        # budget at admission (kvpool.reserve), so decode-time appends can
        # never run out of blocks mid-iteration — admission, where callers
        # know how to wait for evictions, is the only failure point and it
        # reports SlotsFull for slot and block exhaustion alike.  Default
        # sizing = every slot's full budget + one spare sequence of
        # headroom for a slotless gang-scattered resident awaiting its
        # decode slot; a smaller explicit n_blocks makes the block budget
        # the binding constraint.
        n_blocks = (n_blocks if n_blocks is not None
                    else (max_slots + 1) * self.blocks_per_seq)
        #: windowed layers (afmoe) keep rings of the window, the same
        #: budget of sequences deep
        self.window_layers = cfg.window_layers
        ring = -(-cfg.sliding_window // block_size) + 1 \
            if self.window_layers else 0
        n_ring = (max_slots + 1) * min(self.blocks_per_seq, ring)
        # one block more than the budget, the last, which no table owns:
        # decode writes of inactive slots and page-table padding point at it
        self.kvpool = PagedKVCache.create(
            nl, n_blocks + 1, KV, block_size, hd, dt,
            window_layers=self.window_layers, window=cfg.sliding_window,
            n_window_blocks=n_ring + 1 if ring else 0)
        self.scratch = self.kvpool.free.pop()
        self.wscratch = self.kvpool.wfree.pop() if ring else None
        #: decode page table widths: the kernel walks a table a whole
        #: number of steps at a time
        self.pages_per_seq = self._width(self.blocks_per_seq)
        self.ring_width = self._width(ring) if ring else 0
        self.slot_rid: List[Optional[int]] = [None] * max_slots
        self._table = None           # device page tables, rebuilt on rebinding

    @staticmethod
    def _width(pages: int) -> int:
        return -(-pages // fd.PAGES_PER_STEP) * fd.PAGES_PER_STEP

    # ---- prefill (preemptible) ---------------------------------------------
    def start_prefill(self, rid: int, tokens: jnp.ndarray,
                      *, prefix_k: Optional[jnp.ndarray] = None,
                      prefix_v: Optional[jnp.ndarray] = None,
                      host_tokens: Optional[Tuple[int, ...]] = None
                      ) -> PrefillState:
        """Begin a (preemptible) prefill.  With `prefix_k`/`prefix_v`
        ((L, KV, P, hd), e.g. from `lookup_cached_prefix`) only the suffix
        beyond P is embedded and computed — the prefix's KV is reused."""
        if prefix_k is not None:
            P = prefix_k.shape[2]
            x = _embed(self.params, tokens[:, P:], cfg=self.cfg)
            return PrefillState(rid=rid, tokens=tokens, x=x, layer=0,
                                prefix_k=prefix_k, prefix_v=prefix_v,
                                host_tokens=host_tokens)
        x = _embed(self.params, tokens, cfg=self.cfg)
        return PrefillState(rid=rid, tokens=tokens, x=x, layer=0,
                            host_tokens=host_tokens)

    def prefill_quantum(self, st: PrefillState) -> Tuple[PrefillState, bool]:
        """Run up to layers_per_quantum layers; returns (state, done)."""
        with obs.span("prefill_quantum", rid=st.rid):
            lo = st.layer
            hi = min(lo + self.lpq, self.cfg.num_layers)
            pk = pv = None
            if st.prefix_k is not None:
                pk, pv = st.prefix_k[lo:hi], st.prefix_v[lo:hi]
            with obs.span("prefill_quantum/program"):
                out = _prefill_slice(self.params, st.x, lo, pk, pv,
                                     cfg=self.cfg, n=hi - lo)
            x, kvs, held = out
            S = x.shape[1]
            if self.cfg.held_experts:
                obs.count_device("moe/held_assignments", held)
                obs.count("moe/token_layers",
                          S * len(range(max(lo, self.cfg.num_dense_layers),
                                        hi)))
            st.x = x
            keep = min(S, self.cfg.sliding_window)
            with obs.span("prefill_quantum/kv_split"):
                for i in range(hi - lo):
                    k, v = kvs.k[i], kvs.v[i]
                    if lo + i in self.window_layers:   # the window's tail
                        k, v = k[:, :, S - keep:], v[:, :, S - keep:]
                    st.kv_k.append(k)
                    st.kv_v.append(v)
            st.layer = hi
        return st, hi == self.cfg.num_layers

    def prefill_logits(self, st: PrefillState) -> jnp.ndarray:
        assert st.layer == self.cfg.num_layers
        return _finalize(self.params, st.x, cfg=self.cfg)

    # ---- resident KV (paged pool) ------------------------------------------
    def resident(self, rid: int) -> bool:
        return rid in self.kvpool.tables

    def scatter_kv(self, rid: int, k: jnp.ndarray, v: jnp.ndarray) -> None:
        """Install a request's KV block-granularly without binding a decode
        slot — the gang-SP scatter path (§5.3: the SP group's sharded KV
        lands on the long's home replica).  k/v: (L, KV, S, hd)."""
        S = k.shape[2]
        if S > self.max_len:
            raise ValueError("sequence longer than engine max_len")
        if not self.kvpool.can_admit(S):
            raise SlotsFull(
                f"KV pool of replica cannot hold {S} tokens for request "
                f"{rid}: {len(self.kvpool.free)} of {self.kvpool.n_blocks} "
                f"blocks free")
        self.kvpool.admit(rid, k, v)

    def release_kv(self, rid: int) -> None:
        """Drop a resident request's blocks (preemption eviction / cleanup).
        The decode page table is rebuilt, so no slot's row keeps them."""
        if rid in self.kvpool.tables:
            self.kvpool.release(rid)
            self._table = None

    def clear(self) -> None:
        """Evict every slot, release every resident request AND forget the
        prefix cache — a cleared engine is bit-identical to a fresh one
        (cross-run determinism for the policy-comparison harnesses)."""
        self.slot_rid = [None] * self.max_slots
        self._table = None
        for rid in list(self.kvpool.tables):
            self.kvpool.release(rid)
        self.kvpool.drop_cache()

    # ---- prefix cache --------------------------------------------------
    def lookup_cached_prefix(self, host_tokens: Sequence[int]
                             ) -> Tuple[PrefixHit, Optional[jnp.ndarray],
                                        Optional[jnp.ndarray]]:
        """Probe the pool's block-hash index for a resident prefix of
        `host_tokens` and gather its KV.  Only FULL-block matches feed the
        suffix-prefill (block-quantized prefix lengths keep the jit shape
        set bounded); partial-tail hits still count in the pool's stats.
        Returns (hit, prefix_k, prefix_v) — arrays are None on a miss."""
        hit = self.kvpool.lookup_prefix(host_tokens)
        # never reuse the WHOLE prompt: at least one suffix token must run
        # so prefill_logits has a real last-position hidden state
        while hit.blocks and hit.n_tokens >= len(host_tokens):
            hit.blocks.pop()
            hit.n_tokens -= self.block_size
        if not hit.blocks:
            return hit, None, None
        full = PrefixHit(blocks=hit.blocks, n_tokens=hit.n_tokens)
        pk, pv = self.kvpool.gather_prefix(full)
        return hit, pk, pv

    def cache_prompt(self, rid: int, k: jnp.ndarray, v: jnp.ndarray,
                     host_tokens: Sequence[int]) -> None:
        """Park a completed prompt's KV in the prefix cache: admit registers
        the blocks in the hash index, the immediate release (refcount -> 0)
        moves them to the cached-free list where future admits can share
        them — and where any later allocation may evict them (LRU)."""
        if rid in self.kvpool.tables:
            return
        if not self.kvpool.can_admit(k.shape[2]):
            return                      # pool too tight to cache; skip
        self.kvpool.admit(rid, k, v, tokens=host_tokens)
        self.kvpool.release(rid)

    # ---- decode slots -------------------------------------------------------
    def free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_rid) if r is None]

    def bind_slot(self, rid: int) -> int:
        """Bind an already-resident request (scatter_kv) into a decode slot,
        reserving its full decode block budget (see pool invariant)."""
        if not self.resident(rid):
            raise KeyError(f"request {rid} has no KV in the pool")
        free = self.free_slots()
        if not free:
            raise SlotsFull(
                f"engine has no free decode slot for request {rid} "
                f"({self.max_slots} occupied)")
        try:
            self.kvpool.reserve(rid, self.max_len)
        except MemoryError as e:
            raise SlotsFull(str(e)) from e
        slot = free[0]
        self.slot_rid[slot] = rid
        self._table = None
        return slot

    def admit(self, rid: int, st: PrefillState) -> int:
        """Install a finished prefill's KV into the pool and bind a decode
        slot (the §5.2 KV migration — here an in-memory copy).  Raises
        `SlotsFull` when every slot is occupied OR the pool lacks the block
        budget — both mean "wait for an eviction"."""
        free = self.free_slots()
        if not free:
            raise SlotsFull(
                f"engine has no free decode slot for request {rid} "
                f"({self.max_slots} occupied)")
        S = st.tokens.shape[1]
        if S > self.max_len:
            raise ValueError("sequence longer than engine max_len")
        # full decode budget (cached-free blocks are evictable, so they
        # count as available)
        pool = self.kvpool
        if (len(pool.free) + len(pool.cached) < self.blocks_per_seq
                or len(pool.wfree) < pool.ring_needed(self.max_len)):
            raise SlotsFull(
                f"KV pool cannot reserve a decode lane for request {rid}: "
                f"{len(pool.free)} of {pool.n_blocks} blocks and "
                f"{len(pool.wfree)} ring blocks free, {self.blocks_per_seq} "
                f"and {pool.ring_needed(self.max_len)} needed")
        with obs.span("admit", rid=rid):
            with obs.span("admit/assemble"):
                k, v, wk, wv = self.assemble_kv(st)
            with obs.span("admit/pool_write"):
                pool.admit(rid, k, v, tokens=st.host_tokens, wk=wk, wv=wv)
                pool.reserve(rid, self.max_len)
            obs.count("admit/tokens", S)
            if pool.ring:
                obs.count("admit/blocks_full",
                          len(pool.full_layers) * len(pool.tables[rid]))
                obs.count("admit/blocks_window",
                          len(pool.window_layers) * len(pool.rings[rid]))
            slot = free[0]
            self.slot_rid[slot] = rid
            self._table = None
        return slot

    def assemble_kv(self, st: PrefillState):
        """A finished prefill's KV over its FULL sequence, the prefix's
        included: (k, v, None, None) with k, v (L, KV, S, hd); with
        windowed layers (k, v) of the full layers and (wk, wv) (Lw, KV, T,
        hd) the windowed layers' last T = min(S, window) positions, all a
        prefill keeps of them (a pool with rings reuses no prefix)."""
        def stack(kv, layers):
            return jnp.stack([kv[i] for i in layers], 0)[:, 0]
        if self.window_layers:
            full, win = self.cfg.full_layers, self.window_layers
            return (stack(st.kv_k, full), stack(st.kv_v, full),
                    stack(st.kv_k, win), stack(st.kv_v, win))
        layers = range(self.cfg.num_layers)
        k, v = stack(st.kv_k, layers), stack(st.kv_v, layers)
        if st.prefix_k is not None:      # re-assemble FULL-sequence KV
            k = jnp.concatenate([st.prefix_k.astype(k.dtype), k], axis=2)
            v = jnp.concatenate([st.prefix_v.astype(v.dtype), v], axis=2)
        return k, v, None, None

    def evict(self, slot: int) -> None:
        rid = self.slot_rid[slot]
        self.slot_rid[slot] = None
        if rid is not None:
            self.release_kv(rid)

    def slot_lengths(self) -> List[int]:
        return [self.kvpool.lengths.get(rid, 0) if rid is not None else 0
                for rid in self.slot_rid]

    # ---- decode -------------------------------------------------------------
    def _page_table(self) -> jax.Array:
        """(max_slots, pages_per_seq) int32: each bound slot's blocks, the
        rest of its row and every unbound row the scratch block."""
        return self._tables()[0]

    def _tables(self) -> Tuple[jax.Array, ...]:
        """The decode program's page tables: `_page_table`, then with
        windowed layers the slots' rings likewise."""
        if self._table is None:
            def table(width, scratch, rows):
                t = np.full((self.max_slots, width), scratch, np.int32)
                for s, rid in enumerate(self.slot_rid):
                    blocks = rows.get(rid, ())
                    t[s, :len(blocks)] = blocks
                return jnp.asarray(t)
            full = table(self.pages_per_seq, self.scratch,
                         self.kvpool.tables)
            self._table = (full,) if not self.window_layers else (
                full, table(self.ring_width, self.wscratch,
                            self.kvpool.rings))
        return self._table

    def decode_logits(self, tokens: Dict[int, int]) -> jax.Array:
        """One continuous-batching iteration over the active slots.
        tokens: slot -> last token id.  Writes each active slot's new KV
        into the pool and returns the (max_slots, V) logits."""
        with obs.span("decode_iteration/prep"):
            lens = self.slot_lengths()
            step = np.zeros((3, self.max_slots), np.int32)
            step[1] = lens
            for s, t in tokens.items():
                rid = self.slot_rid[s]
                if rid is None:
                    raise ValueError(f"decode on unbound slot {s}")
                if lens[s] >= self.max_len:
                    raise ValueError("decode past engine max_len")
                # slots reserved their full budget at admission, so this
                # only forks a block another table shares
                if self.kvpool.own_block(rid, lens[s]):
                    self._table = None
                step[0, s], step[2, s] = t, 1
            tables = self._tables()
            step = jnp.asarray(step)
        pool = self.kvpool
        with obs.span("decode_iteration/program"):
            logits, pool.arrays, held = self.family.decode(
                self.params, pool.arrays, tables, step, cfg=self.cfg)
            if held is not None:
                obs.count_device("moe/held_assignments", held)
                obs.count("moe/token_layers", len(tokens) * (
                    self.cfg.num_layers - self.cfg.num_dense_layers))
        for s in tokens:
            pool.lengths[self.slot_rid[s]] += 1
        obs.count("decode_iteration/slots", len(tokens))
        obs.count("decode_iteration/kv_tokens", sum(lens[s] for s in tokens))
        if pool.ring:
            obs.count("decode_iteration/window_kv_tokens",
                      sum(min(lens[s], pool.window) for s in tokens))
        return logits

    def decode_iteration(self, tokens: Dict[int, int]) -> Dict[int, int]:
        """`decode_logits`, greedy: returns slot -> next token id."""
        with obs.span("decode_iteration"):
            logits = self.decode_logits(tokens)
            with obs.span("decode_iteration/readback"):
                nxt = np.asarray(jnp.argmax(logits, -1))
                return {s: int(nxt[s]) for s in tokens}
