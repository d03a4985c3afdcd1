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
slot binds a rid into the batched decode step; the dense (L, slots, KV,
S_max, hd) view the jitted decode kernel consumes is gathered from the pool
per iteration, and the new token's KV is appended back block-granularly —
one KV path for gang scatter, preemption eviction and decode alike.

The engine targets the dense family (the paper's evaluation models are all
dense); decode runs slot-batched with per-slot cache lengths — continuous
batching at the iteration level.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ModelConfig
from repro.kernels import ops
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
    kv_k: List[jnp.ndarray] = field(default_factory=list)   # per-layer (1,KV,S,hd)
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
# weights are arguments, never constants folded into a program.
@functools.partial(jax.jit, static_argnames=("cfg",))
def _embed(params, tokens, *, cfg: ModelConfig):
    return params["embed"][tokens].astype(jnp.dtype(cfg.dtype))


@functools.partial(jax.jit, static_argnames=("cfg", "n"))
def _prefill_slice(params, x, lo, pk, pv, *, cfg: ModelConfig, n: int):
    """Layers [lo, lo + n) over x (B, S, d).  With pk/pv ((n, KV, P, hd),
    the reused prefix KV of these layers) x covers only the suffix: RoPE
    starts at position P and attention runs over [prefix ‖ suffix] at query
    offset P, through the same `_dense_layer` body as a full prefill."""
    B, S, _ = x.shape
    P = 0 if pk is None else pk.shape[2]
    positions = jnp.broadcast_to(jnp.arange(P, P + S)[None], (B, S))

    def body(x, inp):
        i, pkl, pvl = inp
        attn_fn = None
        if pkl is not None:
            def attn_fn(qh, kh, vh, *, causal, sliding_window):
                k = jnp.concatenate([pkl[None].astype(kh.dtype), kh], axis=2)
                v = jnp.concatenate([pvl[None].astype(vh.dtype), vh], axis=2)
                return ops.attention(qh, k, v, causal=causal,
                                     sliding_window=sliding_window,
                                     q_offset=P, impl="xla")
        return mdl._dense_layer(cfg, mdl.layer_at(params["layers"], i), x,
                                positions, sliding_window=cfg.sliding_window,
                                impl="xla", write_cache=True, attn_fn=attn_fn)
    return jax.lax.scan(body, x, (lo + jnp.arange(n), pk, pv))


@functools.partial(jax.jit, static_argnames=("cfg",))
def _finalize(params, x, *, cfg: ModelConfig):
    return mdl.lm_logits(cfg, params, x[:, -1])


@functools.partial(jax.jit, static_argnames=("cfg",))
def _decode(params, cache_k, cache_v, slot_len, tokens, *, cfg: ModelConfig):
    cache = {"len": slot_len, "k": cache_k, "v": cache_v}
    logits, cache = mdl.decode_step(cfg, params, cache, tokens, impl="xla")
    return logits, cache["k"], cache["v"]


class ReplicaEngine:
    """One model replica: preemptible prefill + slot-batched decode over a
    paged KV pool."""

    def __init__(self, cfg: ModelConfig, params, *, max_slots: int = 8,
                 max_len: int = 512, layers_per_quantum: int = 2,
                 block_size: int = 16, n_blocks: Optional[int] = None):
        assert cfg.family in ("dense",), "engine demo targets dense family"
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
        self.kvpool = PagedKVCache.create(
            nl, n_blocks if n_blocks is not None
            else (max_slots + 1) * self.blocks_per_seq, KV, block_size,
            hd, dt)
        self.slot_rid: List[Optional[int]] = [None] * max_slots
        self._view = None                      # cached dense decode view

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
                x, kvs = _prefill_slice(self.params, st.x, lo, pk, pv,
                                        cfg=self.cfg, n=hi - lo)
            st.x = x
            with obs.span("prefill_quantum/kv_split"):
                for i in range(hi - lo):
                    st.kv_k.append(kvs.k[i])
                    st.kv_v.append(kvs.v[i])
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

        Invalidates the cached dense decode view: releasing a rid that is
        (or was) slot-visible would otherwise leave its stale KV in the
        cached view until the next admit/bind — the next decode iteration
        must see the pool without the released blocks."""
        if rid in self.kvpool.tables:
            self.kvpool.release(rid)
            self._invalidate_view()

    def clear(self) -> None:
        """Evict every slot, release every resident request AND forget the
        prefix cache — a cleared engine is bit-identical to a fresh one
        (cross-run determinism for the policy-comparison harnesses)."""
        self.slot_rid = [None] * self.max_slots
        self._invalidate_view()
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
        self._invalidate_view()
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
        if (len(self.kvpool.free) + len(self.kvpool.cached)
                < self.blocks_per_seq):
            raise SlotsFull(
                f"KV pool cannot reserve a decode lane for request {rid}: "
                f"{len(self.kvpool.free)} of {self.kvpool.n_blocks} "
                f"blocks free, {self.blocks_per_seq} needed")
        with obs.span("admit", rid=rid):
            with obs.span("admit/assemble"):
                k = jnp.stack(st.kv_k, 0)[:, 0]      # (L, KV, S, hd)
                v = jnp.stack(st.kv_v, 0)[:, 0]
                if st.prefix_k is not None:      # re-assemble FULL-sequence KV
                    k = jnp.concatenate([st.prefix_k.astype(k.dtype), k],
                                        axis=2)
                    v = jnp.concatenate([st.prefix_v.astype(v.dtype), v],
                                        axis=2)
            with obs.span("admit/pool_write"):
                self.kvpool.admit(rid, k, v, tokens=st.host_tokens)
                self.kvpool.reserve(rid, self.max_len)
            obs.count("admit/tokens", S)
            slot = free[0]
            self.slot_rid[slot] = rid
            self._invalidate_view()
        return slot

    def evict(self, slot: int) -> None:
        rid = self.slot_rid[slot]
        self.slot_rid[slot] = None
        if rid is not None:
            self.release_kv(rid)    # invalidates the cached dense view

    def slot_lengths(self) -> List[int]:
        return [self.kvpool.lengths.get(rid, 0) if rid is not None else 0
                for rid in self.slot_rid]

    # ---- decode -------------------------------------------------------------
    def _invalidate_view(self) -> None:
        self._view = None

    def _dense_view(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """The slot-batched dense cache the jitted decode step consumes,
        gathered from the pool.  Cached between iterations: decode itself
        is the only writer while slot bindings are stable (the returned
        updated cache from `_decode` already carries the appended tokens),
        so a full rebuild happens only after admit/bind/evict/clear —
        per-token cost stays proportional to the step, not the pool."""
        if self._view is not None:
            return self._view
        with obs.span("decode_iteration/view"):
            cfg = self.cfg
            nl, KV, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
            dt = jnp.dtype(cfg.dtype)
            ck = jnp.zeros((nl, self.max_slots, KV, self.max_len, hd), dt)
            cv = jnp.zeros((nl, self.max_slots, KV, self.max_len, hd), dt)
            for s, rid in enumerate(self.slot_rid):
                if rid is None or not self.resident(rid):
                    continue
                k, v = self.kvpool.gather(rid)
                pad = self.max_len - k.shape[2]
                k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
                v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
                ck = ck.at[:, s].set(k)
                cv = cv.at[:, s].set(v)
            self._view = (ck, cv)
        return self._view

    def decode_logits(self, tokens: Dict[int, int]) -> jax.Array:
        """One continuous-batching iteration over the active slots.
        tokens: slot -> last token id.  Appends each active slot's new KV
        to the pool and returns the (max_slots, V) logits."""
        with obs.span("decode_iteration/prep"):
            tok = np.zeros((self.max_slots,), np.int32)
            for s, t in tokens.items():
                tok[s] = t
            cache_k, cache_v = self._dense_view()
            lens = self.slot_lengths()
            slot_len, tok = jnp.asarray(lens, jnp.int32), jnp.asarray(tok)
        with obs.span("decode_iteration/program"):
            logits, new_k, new_v = _decode(self.params, cache_k, cache_v,
                                           slot_len, tok, cfg=self.cfg)
        obs.count("decode_iteration/slots", len(tokens))
        # the updated dense cache carries the appended tokens (inactive
        # slots' writes land at masked positions, same as the pre-paged
        # engine) — keep it as the live view
        self._view = (new_k, new_v)
        # append the new token's KV back to the pool — active slots only.
        # Slots reserved their full budget at admission, so this never
        # allocates and cannot fail mid-iteration.
        with obs.span("decode_iteration/append"):
            for s in tokens:
                rid = self.slot_rid[s]
                pos = lens[s]
                if pos >= self.max_len:
                    raise ValueError("decode past engine max_len")
                self.kvpool.append_token(rid, new_k[:, s, :, pos],
                                         new_v[:, s, :, pos])
        return logits

    def decode_iteration(self, tokens: Dict[int, int]) -> Dict[int, int]:
        """`decode_logits`, greedy: returns slot -> next token id."""
        with obs.span("decode_iteration"):
            logits = self.decode_logits(tokens)
            with obs.span("decode_iteration/readback"):
                nxt = np.asarray(jnp.argmax(logits, -1))
                return {s: int(nxt[s]) for s in tokens}
