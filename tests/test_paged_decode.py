"""The engine's decode step on the paged pool, in place, against
`models.model.decode_step` on a dense cache built from the same KV.

Each case binds requests of given lengths into an engine's slots straight
from random KV (`scatter_kv` + `bind_slot`), runs decode steps, and runs
the same steps through `decode_step` over a (L, slots, KV, max_len, hd)
cache holding the same KV.  Both paths of the engine are covered: the XLA
gather it runs here, and the Pallas kernel in interpret mode.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.kernels import ops
from repro.models import init_params
from repro.models import model as mdl
from repro.serving import engine as engine_mod
from repro.serving.engine import ReplicaEngine

MAX_LEN, BLOCK = 64, 16


def _cfg(n_heads, n_kv, d_model, window=0):
    return dataclasses.replace(
        reduced_config(get_config("mistral_7b"), layers=2, d_model=d_model,
                       n_heads=n_heads, n_kv=n_kv),
        dtype="float32", sliding_window=window)


#: 4 and 7 query heads per KV head
CFGS = {"g4": _cfg(8, 2, 256), "g7": _cfg(14, 2, 224)}
_PARAMS = {}


def _model(name):
    cfg = CFGS[name] if name in CFGS else _cfg(8, 2, 256, window=8)
    if name not in _PARAMS:
        _PARAMS[name] = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, _PARAMS[name]


@pytest.fixture
def use(monkeypatch):
    """use(impl): the engine's decode program runs `impl` (None: what it
    picks here, the XLA gather on a CPU)."""
    def pick(impl):
        if impl is not None:
            monkeypatch.setattr(engine_mod, "_decode", functools.partial(
                engine_mod._decode, impl=impl))
    return pick


class Pair:
    """An engine and the dense cache `decode_step` runs beside it."""

    def __init__(self, cfg, params, max_slots=4):
        self.cfg, self.params = cfg, params
        self.eng = ReplicaEngine(cfg, params, max_slots=max_slots,
                                 max_len=MAX_LEN, block_size=BLOCK)
        shape = (cfg.num_layers, max_slots, cfg.num_kv_heads, MAX_LEN,
                 cfg.head_dim)
        self.k = np.zeros(shape, np.float32)
        self.v = np.zeros(shape, np.float32)
        self.lens = np.zeros(max_slots, np.int32)
        self.rng = np.random.default_rng(len(cfg.name))

    def bind(self, rid, n):
        cfg = self.cfg
        k, v = (self.rng.normal(size=(cfg.num_layers, cfg.num_kv_heads, n,
                                      cfg.head_dim)).astype(np.float32)
                for _ in range(2))
        self.eng.scatter_kv(rid, jnp.asarray(k), jnp.asarray(v))
        s = self.eng.bind_slot(rid)
        self.k[:, s] = 0
        self.v[:, s] = 0
        self.k[:, s, :, :n], self.v[:, s, :, :n] = k, v
        self.lens[s] = n
        return s

    def step(self, tokens):
        """One step on both; returns (engine logits, dense logits)."""
        got = np.asarray(self.eng.decode_logits(tokens))
        tok = np.zeros(len(self.lens), np.int32)
        for s, t in tokens.items():
            tok[s] = t
        cache = {"len": jnp.asarray(self.lens), "k": jnp.asarray(self.k),
                 "v": jnp.asarray(self.v)}
        want, cache = mdl.decode_step(self.cfg, self.params, cache,
                                      jnp.asarray(tok), impl="xla")
        for s in tokens:               # only active slots took a token
            pos = self.lens[s]
            self.k[:, s, :, pos] = np.asarray(cache["k"])[:, s, :, pos]
            self.v[:, s, :, pos] = np.asarray(cache["v"])[:, s, :, pos]
            self.lens[s] += 1
        return got, np.asarray(want)

    def check_pool(self, slot):
        rid = self.eng.slot_rid[slot]
        n = self.lens[slot]
        k, v = self.eng.kvpool.gather(rid)
        np.testing.assert_allclose(k, self.k[:, slot, :, :n], rtol=2e-5,
                                   atol=2e-5)
        np.testing.assert_allclose(v, self.v[:, slot, :, :n], rtol=2e-5,
                                   atol=2e-5)


def _close(got, want, slots):
    for s in slots:
        np.testing.assert_allclose(got[s], want[s], rtol=2e-5, atol=2e-5)


IMPLS = [None, "pallas_interpret"]     # None: what the engine picks here


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("name", sorted(CFGS))
def test_live_lengths_cross_blocks_and_match_dense_decode(name, impl, use):
    """Mixed lengths per batch, three steps each crossing or touching a
    block boundary (15->18, 31->34, 47->50)."""
    use(impl)
    p = Pair(*_model(name))
    slots = [p.bind(10, 15), p.bind(11, 31), p.bind(12, 47)]
    toks = {s: 3 + s for s in slots}
    for _ in range(3):
        got, want = p.step(toks)
        _close(got, want, slots)
        toks = {s: int(np.argmax(got[s])) for s in slots}
    for s in slots:
        p.check_pool(s)
    assert p.eng.slot_lengths()[:3] == [18, 34, 50]


@pytest.mark.parametrize("impl", IMPLS)
def test_inactive_and_unbound_slots_leave_the_pool_bit_identical(impl, use):
    """A bound slot outside `tokens` and an unbound slot write nothing: the
    pool after a step differs from the pool before only at the active
    slot's new position, in every layer, and in the scratch block that no
    table owns (where the inactive slots' writes go)."""
    use(impl)
    p = Pair(*_model("g4"))
    active = p.bind(20, 21)
    idle = p.bind(21, 9)                   # bound, not in tokens
    before_k = np.asarray(p.eng.kvpool.k).copy()
    before_v = np.asarray(p.eng.kvpool.v).copy()
    got, want = p.step({active: 5})
    _close(got, want, [active])
    blk = p.eng.kvpool.tables[20][21 // BLOCK]
    for before, after in ((before_k, np.asarray(p.eng.kvpool.k)),
                          (before_v, np.asarray(p.eng.kvpool.v))):
        diff = (before != after).any(axis=(2, 4))
        diff[:, p.eng.scratch] = False
        changed = np.argwhere(diff)
        # (layer, block, offset) of every changed row
        assert {tuple(c) for c in changed} <= {
            (i, blk, 21 % BLOCK) for i in range(p.cfg.num_layers)}
        assert len(changed) == p.cfg.num_layers
    assert not any(p.eng.scratch in t for t in p.eng.kvpool.tables.values())
    assert p.eng.slot_lengths() == [22, 9, 0, 0]
    p.check_pool(idle)
    with pytest.raises(ValueError):
        p.eng.decode_logits({3: 1})        # an unbound slot cannot decode


def test_a_shared_block_is_forked_before_the_write():
    """Copy-on-write: the block that takes the new token is also in another
    table; the step writes into a fork, the sharer's bytes stay."""
    p = Pair(*_model("g4"))
    s = p.bind(30, 20)
    p.step({s: 6})                         # the page table is built
    pool = p.eng.kvpool
    shared = pool.tables[30][21 // BLOCK]
    pool._acquire(shared)                  # a second table references it
    pool.tables[99], pool.lengths[99] = [shared], 4
    before = np.asarray(pool.k[:, shared]).copy()
    got, want = p.step({s: 7})
    _close(got, want, [s])
    assert pool.tables[30][21 // BLOCK] != shared
    assert pool.ref[shared] == 1 and pool.stats["cow_forks"] == 1
    np.testing.assert_array_equal(np.asarray(pool.k[:, shared]), before)
    p.check_pool(s)


def test_a_slot_reused_after_evict_reads_its_new_request():
    p = Pair(*_model("g7"), max_slots=2)
    s = p.bind(40, 30)
    p.step({s: 1})
    p.eng.evict(s)
    assert p.bind(41, 12) == s             # the same slot, new blocks
    for t in (2, 3):
        got, want = p.step({s: t})
        _close(got, want, [s])
    p.check_pool(s)


def test_sliding_window_through_the_xla_paged_path():
    cfg, params = _model("window")
    assert ops.paged_decode_impl(cfg.head_dim) == "xla"
    p = Pair(cfg, params)
    slots = [p.bind(50, 5), p.bind(51, 30)]
    toks = {s: 1 for s in slots}
    for _ in range(2):
        got, want = p.step(toks)
        _close(got, want, slots)
        toks = {s: int(np.argmax(got[s])) for s in slots}
