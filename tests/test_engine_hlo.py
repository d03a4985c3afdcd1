"""The dense family's engine programs lower to the same HLO as before the
afmoe family joined the engine: the fingerprints below were taken from
that tree (a 4-layer Mistral-7B reduction, lowered for the CPU and, for
the decode program a TPU runs with the Pallas kernel, for a TPU), so any
change this family's path makes to what a dense model runs shows here.  A
change meant for the dense path updates them, and says why."""
import base64
import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, reduced_config
from repro.models import init_params
from jax._src.lib.mlir import ir

from repro.serving import engine as E

#: sha256 (first 16 hex digits) of `Lowered.as_text()` of each program,
#: each Pallas kernel's serialized body read back without source locations
FINGERPRINTS = {
    "embed": "81a348561f8618d9",
    "prefill": "ff0dff9357614ce7",
    "prefill_prefix": "c45d30cc34612f33",
    "finalize": "856e13643fb81115",
    "decode": "25637132c31e459f",
    "decode_pallas_tpu": "671f7752adbdbfb3",
}

_KERNEL_BODY = re.compile(r"\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22")


def _text(lowered) -> str:
    """The lowered program, with each Mosaic kernel's body (MLIR bytecode
    that carries the source lines it was traced from) as its module's text
    without them: the same kernel traced from moved lines reads the same."""
    def kernel(m):
        with ir.Context() as ctx:
            ctx.allow_unregistered_dialects = True
            mod = ir.Module.parse(base64.b64decode(m.group(1)))
            return mod.operation.get_asm(enable_debug_info=False)
    return _KERNEL_BODY.sub(kernel, lowered.as_text())


@pytest.fixture(scope="module")
def lowered():
    cfg = dataclasses.replace(
        reduced_config(get_config("mistral_7b"), layers=4), sliding_window=0)
    params = init_params(jax.random.PRNGKey(0), cfg)
    dt = jnp.dtype(cfg.dtype)
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    x = jnp.zeros((1, 48, cfg.d_model), dt)
    prefix = jnp.zeros((2, KV, 16, hd), dt)
    pool = jnp.zeros((4, 33, KV, 16, hd), dt)
    table = jnp.zeros((4, 16), jnp.int32)
    step = jnp.zeros((3, 4), jnp.int32)
    return {
        "embed": E._embed.lower(params, jnp.zeros((1, 48), jnp.int32),
                                cfg=cfg),
        "prefill": E._prefill_slice.lower(params, x, 0, None, None, cfg=cfg,
                                          n=2),
        "prefill_prefix": E._prefill_slice.lower(params, x, 0, prefix,
                                                 prefix, cfg=cfg, n=2),
        "finalize": E._finalize.lower(params, x, cfg=cfg),
        "decode": E._decode_program(False).lower(params, pool, pool, table,
                                                 step, cfg=cfg, impl="xla"),
        "decode_pallas_tpu": E._decode_program(True).trace(
            params, pool, pool, table, step, cfg=cfg, impl="pallas").lower(
                lowering_platforms=("tpu",)),
    }


@pytest.mark.parametrize("program", sorted(FINGERPRINTS))
def test_dense_program_lowers_as_before(lowered, program):
    text = _text(lowered[program])
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == FINGERPRINTS[program]
