"""Paged KV cache (PagedAttention-style, the mechanism of the paper's vLLM
substrate): a fixed pool of fixed-size blocks + per-request block tables.
Non-contiguous physical storage eliminates fragmentation.  Decode reads and
writes the pool in place through the tables (`serving/engine.py`); gather
by block table materializes contiguous KV where a caller needs it (a
prefix hit's suffix prefill, parking KV off the device).

Prefix reuse (vLLM-v1-style): full blocks are indexed by a chain hash over
their token content (h_i = hash((h_{i-1}, block_tokens))), so an admit whose
prompt shares a cached prefix links the resident blocks into its own table
(refcount++) instead of re-writing them — and the caller can skip those
blocks' prefill compute entirely.  Blocks whose refcount drops to zero but
that still carry a registered hash are parked on a `cached` free list (data
retained, LRU-evicted only when a plain allocation needs room), so a prefix
survives between requests — the property cross-turn chat reuse depends on.
Tables are copy-on-write: a write into a block another table still
references forks a private copy first (`own_block`).

Two kinds of layer (a model with windowed layers beside full ones, given
`window_layers` at creation): full layers keep the growing tables above;
windowed layers keep, per request, a RING of `ceil(window / block) + 1`
blocks in a pool of their own, the logical block j of the sequence in ring
slot j % ring, so a block whose positions fell out of every later window
takes the next positions in place.  A request then holds
`full_layers x ceil(len / block)` plus `window_layers x min(ceil(len /
block), ring)` layer-blocks.  Ring blocks are never shared, and a pool
with rings reuses no prefix: `lookup_prefix` finds no hit there.

Pure JAX: the pool is a pytree; allocation metadata is host-side (block
tables are tiny and scheduler-owned, exactly as in vLLM).
"""
from __future__ import annotations

import functools
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp


def _chain_hash(parent: Optional[int], tokens: Tuple[int, ...]) -> int:
    """Prefix-chain hash: identifies block CONTENT + everything before it.
    Collisions are assumed absent (the standard vLLM trade; a collision
    would silently alias two prefixes, acceptable for a simulator/repro)."""
    return hash((parent, tokens))


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _write_blocks(pool_k, pool_v, idx, k, v):
    """Write k/v (L, KV, S, hd), padded to whole blocks, into the pool's
    blocks `idx` in place (the pool is donated)."""
    L, KV, S, hd = k.shape
    n, bs = idx.shape[0], pool_k.shape[3]
    pad = ((0, 0), (0, 0), (0, n * bs - S), (0, 0))

    def blocks(x):
        return jnp.pad(x, pad).reshape(L, KV, n, bs, hd).transpose(0, 2, 1, 3,
                                                                    4)
    return (pool_k.at[:, idx].set(blocks(k).astype(pool_k.dtype)),
            pool_v.at[:, idx].set(blocks(v).astype(pool_v.dtype)))


@dataclass
class PrefixHit:
    """Result of `lookup_prefix`: resident blocks covering a prompt prefix.

    `blocks` are fully-matched blocks (every token identical); `tail_block`
    (if any) matches only its first `tail_tokens` tokens — its KV rows can
    be gathered to skip compute, but the block itself is never shared."""
    blocks: List[int] = field(default_factory=list)
    n_tokens: int = 0              # tokens covered by fully-matched blocks
    tail_block: Optional[int] = None
    tail_tokens: int = 0           # extra tokens matched inside tail_block

    @property
    def total_tokens(self) -> int:
        return self.n_tokens + self.tail_tokens


@dataclass
class PagedKVCache:
    """Pool: k/v (L, n_blocks, KV, block_size, hd)."""
    k: jax.Array
    v: jax.Array
    block_size: int
    free: List[int] = field(default_factory=list)
    #: refcount-0 blocks with live hash registrations, oldest-first (LRU);
    #: data is retained until a plain allocation evicts them
    cached: "OrderedDict[int, None]" = field(default_factory=OrderedDict)
    tables: Dict[int, List[int]] = field(default_factory=dict)   # rid -> blocks
    lengths: Dict[int, int] = field(default_factory=dict)        # rid -> tokens
    ref: Dict[int, int] = field(default_factory=dict)            # block -> refs
    # --- block-hash index (full blocks) + partial-tail registry ---
    chain: Dict[int, int] = field(default_factory=dict)          # hash -> block
    block_hash: Dict[int, int] = field(default_factory=dict)     # block -> hash
    tails: Dict[Optional[int], List[int]] = field(default_factory=dict)
    tail_meta: Dict[int, Tuple[Optional[int], Tuple[int, ...]]] = \
        field(default_factory=dict)
    stats: Dict[str, int] = field(default_factory=lambda: dict(
        lookups=0, hits=0, hit_tokens=0, blocks_shared=0, cow_forks=0))
    # --- windowed layers: ring pool (None without window layers) ---
    wk: Optional[jax.Array] = None          # (Lw, Nw, KV, block, hd)
    wv: Optional[jax.Array] = None
    window: int = 0
    #: layer index of each windowed layer, in the order of wk's first axis
    window_layers: Tuple[int, ...] = ()
    n_layers: int = 0
    wfree: List[int] = field(default_factory=list)
    rings: Dict[int, List[int]] = field(default_factory=dict)  # rid -> ring

    # ------------------------------------------------------------------
    @classmethod
    def create(cls, n_layers: int, n_blocks: int, kv_heads: int,
               block_size: int, head_dim: int, dtype=jnp.bfloat16, *,
               window_layers: Sequence[int] = (), window: int = 0,
               n_window_blocks: int = 0) -> "PagedKVCache":
        """`n_layers` layers, of which `window_layers` keep rings of the
        `window` in `n_window_blocks` blocks of their own; `n_blocks`
        blocks for the others."""
        n_full = n_layers - len(window_layers)
        shape = (n_full, n_blocks, kv_heads, block_size, head_dim)
        pool = cls(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   block_size=block_size, free=list(range(n_blocks)),
                   n_layers=n_layers)
        if window_layers:
            wshape = (len(window_layers), n_window_blocks, kv_heads,
                      block_size, head_dim)
            pool.wk, pool.wv = jnp.zeros(wshape, dtype), \
                jnp.zeros(wshape, dtype)
            pool.window, pool.window_layers = window, tuple(window_layers)
            pool.wfree = list(range(n_window_blocks))
        return pool

    @property
    def arrays(self) -> Tuple[jax.Array, ...]:
        """The pool's device arrays, as the decode programs take them:
        (k, v), then (wk, wv) with windowed layers."""
        return (self.k, self.v) + ((self.wk, self.wv) if self.ring else ())

    @arrays.setter
    def arrays(self, arrays: Sequence[jax.Array]) -> None:
        self.k, self.v, *ring = arrays
        if ring:
            self.wk, self.wv = ring

    @property
    def n_blocks(self) -> int:
        return self.k.shape[1]

    @property
    def ring(self) -> int:
        """Blocks of a windowed layer's ring: any `window` consecutive
        positions and the next one span at most this many (0: no ring)."""
        return -(-self.window // self.block_size) + 1 if self.window else 0

    @property
    def full_layers(self) -> Tuple[int, ...]:
        ring = set(self.window_layers)
        return tuple(i for i in range(self.n_layers) if i not in ring)

    def blocks_needed(self, tokens: int) -> int:
        return -(-tokens // self.block_size)

    def ring_needed(self, tokens: int) -> int:
        return min(self.blocks_needed(tokens), self.ring)

    def layer_blocks(self, tokens: int) -> Tuple[int, int]:
        """(full, windowed) layer-blocks `tokens` positions hold."""
        return (len(self.full_layers) * self.blocks_needed(tokens),
                len(self.window_layers) * self.ring_needed(tokens))

    def can_admit(self, tokens: int) -> bool:
        return (len(self.free) + len(self.cached)
                >= self.blocks_needed(tokens)
                and len(self.wfree) >= self.ring_needed(tokens))

    # ------------------------------------------------------------------
    # allocation: blank blocks first, then LRU-evict the cached-prefix list
    # ------------------------------------------------------------------
    def _alloc(self, n: int) -> List[int]:
        if len(self.free) + len(self.cached) < n:
            raise MemoryError(f"need {n} blocks, {len(self.free)} free + "
                              f"{len(self.cached)} cached")
        out = [self.free.pop() for _ in range(min(n, len(self.free)))]
        while len(out) < n:
            b, _ = self.cached.popitem(last=False)     # oldest first
            self._unregister(b)
            out.append(b)
        return out

    def _unregister(self, b: int) -> None:
        h = self.block_hash.pop(b, None)
        if h is not None and self.chain.get(h) == b:
            del self.chain[h]
        tm = self.tail_meta.pop(b, None)
        if tm is not None:
            lst = self.tails.get(tm[0])
            if lst is not None:
                lst.remove(b)
                if not lst:
                    del self.tails[tm[0]]

    def _acquire(self, b: int) -> None:
        """Take (or add) a reference on a resident block, reviving it from
        the cached list if it was refcount-0."""
        if b in self.cached:
            del self.cached[b]
        self.ref[b] = self.ref.get(b, 0) + 1

    # ------------------------------------------------------------------
    # prefix lookup
    # ------------------------------------------------------------------
    def lookup_prefix(self, tokens: Sequence[int]) -> PrefixHit:
        """Longest resident prefix of `tokens`: fully-matched whole blocks
        via the chain-hash index, plus a partial match inside one registered
        tail block.  Read-only (no refcounts taken); callers that need the
        blocks to survive a subsequent allocation must `admit` (full blocks)
        or `gather_prefix` (copy out) before allocating.  A pool with
        rings finds none: its windowed layers keep no prefix's KV."""
        hit = PrefixHit()
        self.stats["lookups"] += 1
        if self.ring:
            return hit
        bs = self.block_size
        h: Optional[int] = None
        i = 0
        while i + bs <= len(tokens):
            nh = _chain_hash(h, tuple(tokens[i:i + bs]))
            b = self.chain.get(nh)
            if b is None:
                break
            hit.blocks.append(b)
            if b in self.cached:                   # LRU touch
                self.cached.move_to_end(b)
            h = nh
            i += bs
        hit.n_tokens = i
        rem = tokens[i:]
        if len(rem):
            # partial-tail match: longest common prefix against registered
            # tails hanging off the matched prefix's chain hash
            best_b, best_n = None, 0
            for tb in self.tails.get(h, []):
                _, ttoks = self.tail_meta[tb]
                n = 0
                for a, c in zip(ttoks, rem):
                    if a != c:
                        break
                    n += 1
                if n > best_n:
                    best_b, best_n = tb, n
            if best_b is not None:
                hit.tail_block, hit.tail_tokens = best_b, best_n
                if best_b in self.cached:
                    self.cached.move_to_end(best_b)
        return self._count_hit(hit)

    def _count_hit(self, hit: PrefixHit) -> PrefixHit:
        if hit.total_tokens:
            self.stats["hits"] += 1
            self.stats["hit_tokens"] += hit.total_tokens
        return hit

    def gather_prefix(self, hit: PrefixHit):
        """Materialize a hit's KV as contiguous (L, KV, total_tokens, hd) —
        the past-KV a suffix-only prefill attends over."""
        blocks = list(hit.blocks)
        if hit.tail_block is not None:
            blocks.append(hit.tail_block)
        return self._contiguous(self.k, self.v, blocks, hit.total_tokens)

    @staticmethod
    def _contiguous(pk, pv, blocks: List[int], T: int):
        idx = jnp.asarray(blocks, jnp.int32)
        k, v = pk[:, idx], pv[:, idx]          # (L, n, KV, bs, hd)
        L, n, KV, bs, hd = k.shape
        k = k.transpose(0, 2, 1, 3, 4).reshape(L, KV, n * bs, hd)[:, :, :T]
        v = v.transpose(0, 2, 1, 3, 4).reshape(L, KV, n * bs, hd)[:, :, :T]
        return k, v

    def _interleave(self, full: jax.Array, win: jax.Array) -> jax.Array:
        """Full-layer and windowed-layer stacks back in layer order."""
        order = self.full_layers + self.window_layers
        inv = [order.index(i) for i in range(self.n_layers)]
        return jnp.concatenate([full, win.astype(full.dtype)], 0)[
            jnp.asarray(inv)]

    def split_layers(self, x: jax.Array):
        """(L, ...) in layer order -> (full layers, windowed layers)."""
        return (x[jnp.asarray(self.full_layers)],
                x[jnp.asarray(self.window_layers)])

    # ------------------------------------------------------------------
    def admit(self, rid: int, k: jax.Array, v: jax.Array,
              tokens: Optional[Sequence[int]] = None, *,
              wk: Optional[jax.Array] = None,
              wv: Optional[jax.Array] = None) -> PrefixHit:
        """Install a request's prefill KV. k/v: (L, KV, S, hd) — always the
        FULL sequence (a cache-hit caller still passes full KV; the matched
        blocks' slices simply are not written).  With windowed layers, k/v
        hold every layer and the windowed ones keep the last `window`
        positions; or k/v hold the full layers only and wk/wv (Lw, KV, T,
        hd) the windowed layers' last T positions of the S.

        With `tokens` (the prompt's token ids), fully-matched resident
        blocks are linked into the table by reference (refcount++, data
        untouched) and the newly-written full blocks + partial tail are
        registered in the hash index for future admits.  Without `tokens`
        the cache is opaque: plain allocate-and-write, nothing registered.
        Returns the PrefixHit describing what was shared (empty when
        tokens is None)."""
        if rid in self.tables:
            raise KeyError(f"rid {rid} already resident")
        S = k.shape[2]
        bs = self.block_size
        need = self.blocks_needed(S)
        if self.ring:
            if wk is None:
                k, wk = self.split_layers(k)
                v, wv = self.split_layers(v)
                T = min(S, self.window)
                wk, wv = wk[:, :, S - T:], wv[:, :, S - T:]
            if len(self.wfree) < self.ring_needed(S):
                raise MemoryError(f"need {self.ring_needed(S)} ring blocks, "
                                  f"{len(self.wfree)} free")
        hit = PrefixHit()
        shared: List[int] = []
        if tokens is not None:
            if len(tokens) != S:
                raise ValueError(f"tokens length {len(tokens)} != KV "
                                 f"sequence length {S}")
            h: Optional[int] = None
            i = 0
            while i + bs <= S:
                h = _chain_hash(h, tuple(tokens[i:i + bs]))
                b = self.chain.get(h)
                if b is None:
                    break
                shared.append(b)
                i += bs
            # acquire BEFORE allocating: a shared block must not be evicted
            # by our own suffix allocation
            for b in shared:
                self._acquire(b)
            hit.blocks, hit.n_tokens = list(shared), i
        n_shared = len(shared)
        try:
            new_blocks = self._alloc(need - n_shared)
        except MemoryError:
            for b in shared:                    # undo the acquisition
                self._release_block(b)
            raise
        if new_blocks:
            lo = n_shared * bs
            self.k, self.v = _write_blocks(self.k, self.v,
                                           jnp.asarray(new_blocks, jnp.int32),
                                           k[:, :, lo:], v[:, :, lo:])
            for b in new_blocks:
                self.ref[b] = 1
        table = shared + new_blocks
        self.tables[rid] = table
        self.lengths[rid] = S
        if self.ring:
            self._write_ring(rid, wk, wv, S)
        if tokens is not None:
            self._register(table, tokens)
            self.stats["blocks_shared"] += n_shared
        return hit

    def _write_ring(self, rid: int, wk: jax.Array, wv: jax.Array,
                    S: int) -> None:
        """Allocate a ring for S positions and write the windowed layers'
        last T = wk.shape[2] of them: logical block j into ring slot
        j % ring."""
        bs, T = self.block_size, wk.shape[2]
        ring = [self.wfree.pop() for _ in range(self.ring_needed(S))]
        self.rings[rid] = ring
        first, last = (S - T) // bs, (S - 1) // bs
        front = (0, 0), (0, 0), (S - T - first * bs, 0), (0, 0)
        self.wk, self.wv = _write_blocks(
            self.wk, self.wv,
            jnp.asarray([ring[j % self.ring] for j in range(first, last + 1)],
                        jnp.int32),
            jnp.pad(wk, front), jnp.pad(wv, front))

    def _register(self, table: List[int], tokens: Sequence[int]) -> None:
        """Index a freshly-admitted table: chain hashes for full blocks,
        tail registry for a trailing partial block."""
        bs = self.block_size
        S = len(tokens)
        h: Optional[int] = None
        for bi in range(S // bs):
            h = _chain_hash(h, tuple(tokens[bi * bs:(bi + 1) * bs]))
            b = table[bi]
            if h not in self.chain:
                self.chain[h] = b
                self.block_hash[b] = h
        rem = tuple(tokens[(S // bs) * bs:])
        if rem:
            tb = table[S // bs]
            if tb not in self.tail_meta:
                self.tail_meta[tb] = (h, rem)
                self.tails.setdefault(h, []).append(tb)
                # writing a private tail where a sibling tail already
                # diverged is the admit-side copy-on-write fork
                if len(self.tails[h]) > 1:
                    self.stats["cow_forks"] += 1

    def own_block(self, rid: int, pos: int) -> bool:
        """Make the block that takes token `pos` of `rid` its own, so a
        write there disturbs no other table: grow the table by a block at
        its end, or fork a block another table shares (copy-on-write).
        Returns whether the table changed.  On windowed layers the ring
        slot of `pos` grows the ring, or is reused in place: the block's
        old positions are out of every window from here on."""
        grew = self._own_ring_block(rid, pos) if self.ring else False
        return self._own_full_block(rid, pos) or grew

    def _own_ring_block(self, rid: int, pos: int) -> bool:
        ring = self.rings[rid]
        slot = (pos // self.block_size) % self.ring
        if slot < len(ring):
            return False
        if not self.wfree:
            raise MemoryError("no free ring block")
        ring.append(self.wfree.pop())
        return True

    def _own_full_block(self, rid: int, pos: int) -> bool:
        blocks = self.tables[rid]
        bi = pos // self.block_size
        if bi >= len(blocks):
            blocks.append(self._alloc(1)[0])
            self.ref[blocks[-1]] = 1
            return True
        b = blocks[bi]
        if self.ref.get(b, 0) <= 1:
            return False
        nb = self._alloc(1)[0]
        self.k = self.k.at[:, nb].set(self.k[:, b])
        self.v = self.v.at[:, nb].set(self.v[:, b])
        self.ref[b] -= 1
        self.ref[nb] = 1
        blocks[bi] = nb
        self.stats["cow_forks"] += 1
        return True

    def append_token(self, rid: int, k: jax.Array, v: jax.Array) -> None:
        """Append one token's KV. k/v: (L, KV, hd), into a block of the
        request's own (`own_block`); on windowed layers into its ring."""
        pos = self.lengths[rid]
        self.own_block(rid, pos)
        off = pos % self.block_size
        if self.ring:
            k, wk = self.split_layers(k)
            v, wv = self.split_layers(v)
            r = self.rings[rid][(pos // self.block_size) % self.ring]
            self.wk = self.wk.at[:, r, :, off].set(wk)
            self.wv = self.wv.at[:, r, :, off].set(wv)
        b = self.tables[rid][pos // self.block_size]
        self.k = self.k.at[:, b, :, off].set(k)
        self.v = self.v.at[:, b, :, off].set(v)
        self.lengths[rid] = pos + 1

    def gather(self, rid: int):
        """A request's KV, contiguous: (L, KV, S, hd).  On windowed
        layers the positions out of the next token's window are zeros."""
        S = self.lengths[rid]
        k, v = self._contiguous(self.k, self.v, self.tables[rid], S)
        if not self.ring:
            return k, v
        bs, ring = self.block_size, self.rings[rid]
        first = max(0, S - self.window) // bs
        blocks = [ring[j % self.ring] for j in range(first, -(-S // bs))]
        wk, wv = self._contiguous(self.wk, self.wv, blocks, S - first * bs)
        lo = max(0, S - self.window)
        keep = (jnp.arange(S - first * bs) >= lo - first * bs)[:, None]
        pad = ((0, 0), (0, 0), (first * bs, 0), (0, 0))
        wk = jnp.pad(jnp.where(keep, wk, 0), pad)
        wv = jnp.pad(jnp.where(keep, wv, 0), pad)
        return self._interleave(k, wk), self._interleave(v, wv)

    def reserve(self, rid: int, capacity_tokens: int) -> None:
        """Grow a resident request's block table to hold `capacity_tokens`
        WITHOUT writing data — allocated-but-unused growth room.  Decode
        slots reserve their sequence's full budget up front so a
        decode write never has to allocate (and so admission, where
        callers know how to wait, is the only place that can run out of
        blocks)."""
        blocks = self.tables[rid]
        need = self.blocks_needed(capacity_tokens) - len(blocks)
        if self.ring:
            ring = self.rings[rid]
            wneed = self.ring_needed(capacity_tokens) - len(ring)
            if wneed > len(self.wfree) or len(self.free) + len(
                    self.cached) < max(need, 0):
                raise MemoryError(f"need {max(need, 0)} blocks and "
                                  f"{wneed} ring blocks to reserve "
                                  f"{capacity_tokens} tokens for rid {rid}")
            for _ in range(max(wneed, 0)):
                ring.append(self.wfree.pop())
        if need <= 0:
            return
        if len(self.free) + len(self.cached) < need:
            raise MemoryError(f"need {need} blocks to reserve "
                              f"{capacity_tokens} tokens for rid {rid}, "
                              f"{len(self.free)} free")
        for b in self._alloc(need):
            blocks.append(b)
            self.ref[b] = 1

    def _release_block(self, b: int) -> None:
        self.ref[b] -= 1
        if self.ref[b] > 0:
            return
        del self.ref[b]
        if b in self.block_hash or b in self.tail_meta:
            self.cached[b] = None          # park: data + hash stay live
        else:
            self.free.append(b)

    def release(self, rid: int) -> None:
        # children park after parents (reverse table order) so LRU eviction
        # (oldest first) drops chain leaves before the prefixes they extend
        for b in reversed(self.tables.pop(rid)):
            self._release_block(b)
        self.lengths.pop(rid)
        self.wfree.extend(self.rings.pop(rid, ()))

    def drop_cache(self) -> None:
        """Forget every cached prefix: parked blocks return to the blank
        free list, the hash index and counters reset — the cross-run
        determinism hook (engine.clear())."""
        for b in self.cached:
            self.free.append(b)
        self.cached.clear()
        self.chain.clear()
        self.block_hash.clear()
        self.tails.clear()
        self.tail_meta.clear()
        for key in self.stats:
            self.stats[key] = 0

    # ------------------------------------------------------------------
    # accounting: physical occupancy, tail slack, and reserve headroom are
    # three different questions — keep them separate (a freshly-reserved
    # decode slot is headroom, not fragmentation)
    # ------------------------------------------------------------------
    def utilization(self) -> float:
        """Fraction of physical blocks held by live tables or the cached
        prefix list (shared blocks count once — that is the point)."""
        busy = self.n_blocks - len(self.free) - len(self.cached)
        return busy / self.n_blocks

    def written_tokens(self) -> int:
        """Token positions actually written across live tables (per-table:
        a block shared by two tables holds tokens for both)."""
        return sum(self.lengths.values())

    def reserved_tokens(self) -> int:
        """Capacity held by reserve() headroom beyond each sequence's
        written blocks — allocated-on-purpose, NOT fragmentation."""
        bs = self.block_size
        return sum((len(t) - self.blocks_needed(self.lengths[rid])) * bs
                   for rid, t in self.tables.items())

    def fragmentation(self) -> float:
        """True internal fragmentation: unusable slack inside each
        sequence's written blocks (the partial last block), over the blocks
        the written tokens occupy.  reserve()d headroom is excluded — see
        `reserved_tokens` for that."""
        bs = self.block_size
        denom = sum(self.blocks_needed(n) for n in self.lengths.values()) * bs
        if denom == 0:
            return 0.0
        return 1.0 - sum(self.lengths.values()) / denom
