"""Compiles for a TPU v5e at Mistral-7B widths, with no chip attached.

The TPU compiler is installed with JAX: it compiles for a chip that is
described (`v5e:2x2`) and not attached, from shapes alone.  That refuses
what the chip's compiler would refuse — a kernel tile that does not fit,
a program that overflows the chip's memory, a collective that cannot be
partitioned — at no chip time.  Nothing runs, so nothing here is a time.

The topology is described inside a module-scoped fixture (never while the
module is imported), which skips where it cannot be described; the
persistent compilation cache is off around these compiles, since their
entries could not be read back without a chip.

One CPU test at the end lowers every engine and gang program at a small
size and checks that the weights are arguments of the program, not
constants folded into it.
"""
import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config, reduced_config
from repro.configs.mistral_7b import PIPELINE_STAGE
from repro.configs.trinity_mini import EP8_SHARE
from repro.kernels import flash_attention as fa
from repro.kernels import flash_decode as fd
from repro.models import init_params
from repro.serving import engine
from repro.sp import gang

CFG = PIPELINE_STAGE
SLOTS, MAX_LEN = 4, 4096          # the engine sizing chip_smoke.py serves
PREFILL_LEN = 3584                # its long prompt
V5E_HBM = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.fixture(scope="module")
def params_shape():
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), CFG))


def _fits_one_chip(compiled, args_bytes_max):
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes <= args_bytes_max
    assert m.argument_size_in_bytes + m.temp_size_in_bytes \
        + m.output_size_in_bytes < V5E_HBM


def test_flash_attention_compiles_for_v5e(one_chip):
    bf = jnp.bfloat16
    q = _spec((1, 32, 2048, 128), bf, one_chip)
    kv = _spec((1, 8, 2048, 128), bf, one_chip)
    fn = functools.partial(fa.flash_attention, causal=True, interpret=False)
    compiled = jax.jit(fn).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_decode_compiles_for_v5e(one_chip):
    bf = jnp.bfloat16
    q = _spec((SLOTS, 32, 128), bf, one_chip)
    kv = _spec((SLOTS, 8, MAX_LEN, 128), bf, one_chip)
    lens = _spec((SLOTS,), jnp.int32, one_chip)
    fn = functools.partial(fd.flash_decode, interpret=False)
    compiled = jax.jit(fn).lower(q, kv, kv, lens).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_engine_prefill_quantum_compiles_for_v5e(one_chip, params_shape):
    params = _shapes(params_shape, one_chip)
    x = _spec((1, PREFILL_LEN, CFG.d_model), jnp.bfloat16, one_chip)
    lo = _spec((), jnp.int32, one_chip)
    compiled = engine._prefill_slice.lower(
        params, x, lo, None, None, cfg=CFG, n=2).compile()
    _fits_one_chip(compiled, 4.1e9 + x.size * 2)


def _decode_args(params_shape, sharding, max_len):
    """The engine's decode program's arguments at `max_len` tokens a slot:
    weights, K and V pools of (slots + 1) sequences and the scratch
    block, page table, step vector."""
    eng_shape = jax.eval_shape(
        lambda: engine.ReplicaEngine(CFG, None, max_slots=SLOTS,
                                     max_len=max_len).kvpool.k)
    pool = _spec(eng_shape.shape, jnp.bfloat16, sharding)
    pages = -(-max_len // 16)
    pages = -(-pages // fd.PAGES_PER_STEP) * fd.PAGES_PER_STEP
    return (_shapes(params_shape, sharding), pool, pool,
            _spec((SLOTS, pages), jnp.int32, sharding),
            _spec((3, SLOTS), jnp.int32, sharding))


def test_engine_decode_step_compiles_for_v5e(one_chip, params_shape):
    args = _decode_args(params_shape, one_chip, MAX_LEN)
    compiled = engine._decode_program(True).lower(
        *args, cfg=CFG, impl="pallas").compile()
    pool = args[1]
    _fits_one_chip(compiled, 4.1e9 + 2 * pool.size * 2 + 64 * 1024)
    assert "tpu_custom_call" in compiled.as_text()       # the paged kernel


@pytest.mark.parametrize("max_len", [MAX_LEN, 9136])
def test_engine_decode_updates_the_pool_in_place_on_v5e(one_chip,
                                                        params_shape,
                                                        max_len):
    """The decode program writes the donated pool in place and hands it
    whole to the kernel: its outputs alias both pools, and no temporary
    buffer holds a copy of either (9136 tokens: the benchmark's slots)."""
    args = _decode_args(params_shape, one_chip, max_len)
    compiled = engine._decode_program(True).lower(
        *args, cfg=CFG, impl="pallas").compile()
    m = compiled.memory_analysis()
    pool_bytes = args[1].size * 2
    assert m.alias_size_in_bytes >= 2 * pool_bytes
    assert m.temp_size_in_bytes < pool_bytes / 100


def test_windowed_paged_decode_compiles_for_v5e(one_chip):
    """The paged kernel with a window over a ring of pages (Trinity-Mini's
    windowed layers: 32 heads over 4 KV heads, a 129-page ring in a table
    of 144)."""
    bf = jnp.bfloat16
    q = _spec((SLOTS, 32, 128), bf, one_chip)
    pool = _spec((24 * 646, 4, 16, 128), bf, one_chip)
    pages = _spec((SLOTS, 144), jnp.int32, one_chip)
    lens = _spec((SLOTS,), jnp.int32, one_chip)
    fn = functools.partial(fd.paged_flash_decode, ring=129, interpret=False)
    compiled = jax.jit(fn).lower(q, pool, pool, pages, lens, lens).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_afmoe_decode_updates_both_pools_in_place_on_v5e(one_chip):
    """Trinity-Mini at this chip's share (all 32 layers, 16 of 128 experts)
    with the benchmark's slots: the decode program aliases the full pool
    and the windowed layers' rings, copies neither, and reads the weights
    where they lie (no relayout of a stacked weight)."""
    cfg = EP8_SHARE
    params = _shapes(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)), one_chip)
    pools = jax.eval_shape(lambda: (lambda e: (e.kvpool.k, e.kvpool.wk))(
        engine.ReplicaEngine(cfg, None, max_slots=SLOTS, max_len=9136)))
    pk, wk = (_spec(p.shape, jnp.bfloat16, one_chip) for p in pools)
    compiled = engine._decode_afmoe_program(True).lower(
        params, pk, pk, wk, wk, _spec((SLOTS, 576), jnp.int32, one_chip),
        _spec((SLOTS, 144), jnp.int32, one_chip),
        _spec((3, SLOTS), jnp.int32, one_chip), cfg=cfg,
        impl="pallas").compile()
    m = compiled.memory_analysis()
    pool_bytes = 2 * (pk.size + wk.size) * 2
    assert m.alias_size_in_bytes >= pool_bytes
    assert m.temp_size_in_bytes < 16 * 2**20
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # nor is a pool copied into its own output buffer (what a pool passed
    # through a `cond` branch unwritten costs)
    pools = "|".join(f"{p.shape[0] * p.shape[1]},{','.join(map(str, p.shape[2:]))}"
                     for p in (pk, wk))
    assert not re.search(rf"= bf16\[({pools})\]\S* copy\(", text)


def test_gang_layer_slice_compiles_on_v5e_2x2(topo, params_shape):
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), gang.SEQ_AXES)
    layers = _shapes(params_shape["layers"], NamedSharding(mesh, P()))
    x = _spec((1, PREFILL_LEN, CFG.d_model), jnp.bfloat16,
              NamedSharding(mesh, P(None, gang.SEQ_AXES, None)))
    lo = _spec((), jnp.int32, NamedSharding(mesh, P()))
    for strategy in ("a2a", "allgather"):
        compiled = gang._gang_slice.lower(
            layers, x, lo, cfg=CFG, mesh=mesh, n=4,
            strategy=strategy).compile()
        text = compiled.as_text()
        assert "collective-permute" in text        # the outer ring
        m = compiled.memory_analysis()
        assert m.argument_size_in_bytes + m.temp_size_in_bytes < V5E_HBM


# ---------------------------------------------------------------------------
# CPU: weights are program arguments
# ---------------------------------------------------------------------------
_CONST = re.compile(r"stablehlo\.constant dense<[^>]*> : tensor<([0-9x]*)x?\w+>")


def _largest_constant(text: str) -> int:
    sizes = [int(np.prod([int(d) for d in m.group(1).split("x") if d]))
             for m in _CONST.finditer(text)]
    return max(sizes, default=0)


def _small_programs():
    cfg = reduced_config(get_config("mistral_7b"), layers=4)
    params = init_params(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((1, 16, cfg.d_model), jnp.bfloat16)
    pk = jnp.zeros((2, cfg.num_kv_heads, 8, cfg.head_dim), jnp.bfloat16)
    pool = jnp.zeros((cfg.num_layers, 5, cfg.num_kv_heads, 16,
                      cfg.head_dim), jnp.bfloat16)
    table = jnp.zeros((2, 2), jnp.int32)
    step = jnp.zeros((3, 2), jnp.int32)
    toks = jnp.zeros((1, 16), jnp.int32)
    mesh = gang.make_gang_mesh(1, cfg.num_heads)
    acfg = dataclasses.replace(EP8_SHARE, num_layers=4, d_model=256,
                               num_heads=4, num_kv_heads=2, head_dim=64,
                               d_ff=512, moe_d_ff=128, shared_d_ff=128,
                               vocab_size=512, num_experts=8,
                               experts_per_token=2, experts_held=4,
                               num_dense_layers=1, sliding_window=16,
                               layer_types=EP8_SHARE.layer_types[:4])
    aparams = init_params(jax.random.PRNGKey(1), acfg)
    ax = jnp.zeros((1, 16, acfg.d_model), jnp.bfloat16)
    apool = jnp.zeros((1, 5, 2, 8, 64), jnp.bfloat16)
    awpool = jnp.zeros((3, 7, 2, 8, 64), jnp.bfloat16)
    return params, aparams, {
        "embed": lambda: engine._embed.lower(params, toks, cfg=cfg),
        "prefill": lambda: engine._prefill_slice.lower(
            params, x, 0, None, None, cfg=cfg, n=2),
        "suffix_prefill": lambda: engine._prefill_slice.lower(
            params, x, 2, pk, pk, cfg=cfg, n=2),
        "finalize": lambda: engine._finalize.lower(params, x, cfg=cfg),
        "decode": lambda: engine._decode_program(True).lower(
            params, pool, pool, table, step, cfg=cfg, impl="xla"),
        "gang_embed": lambda: gang._embed.lower(params, toks, cfg=cfg),
        "gang_slice": lambda: gang._gang_slice.lower(
            params["layers"], x, 0, cfg=cfg, mesh=mesh, n=2,
            strategy="a2a"),
        "gang_logits": lambda: gang._last_logits.lower(params, x, cfg=cfg,
                                                       s_real=16),
        "afmoe_prefill": lambda: engine._prefill_slice.lower(
            aparams, ax, 0, None, None, cfg=acfg, n=2),
        "afmoe_decode": lambda: engine._decode_afmoe_program(True).lower(
            aparams, apool, apool, awpool, awpool, table, table, step,
            cfg=acfg, impl="xla"),
    }


#: program -> the weight whose shape must appear among its arguments
USES = {"embed": "embed", "prefill": "w_gate", "suffix_prefill": "w_gate",
        "finalize": "lm_head", "decode": "w_gate", "gang_embed": "embed",
        "gang_slice": "w_gate", "gang_logits": "lm_head",
        "afmoe_prefill": "moe_w_gate", "afmoe_decode": "moe_w_gate"}


@pytest.mark.parametrize("program", sorted(USES))
def test_weights_are_program_arguments_not_constants(program):
    params, aparams, programs = _small_programs()
    text = programs[program]().as_text()
    if program.startswith("afmoe"):
        params = aparams
    smallest_weight = min(a.size for a in jax.tree.leaves(params))
    assert _largest_constant(text) < smallest_weight, \
        f"{program} folds an array of a weight's size into the program"
    weight = {"embed": params["embed"], "lm_head": params["lm_head"],
              "w_gate": params["layers"].get("mlp", {}).get("w_gate"),
              "moe_w_gate": params.get("moe", {}).get("w_gate")
              }[USES[program]]
    main = next(line for line in text.splitlines()
                if "func.func public @main" in line)
    assert "tensor<" + "x".join(map(str, weight.shape)) + "x" in main, \
        f"{program}: {USES[program]} is not an argument"
