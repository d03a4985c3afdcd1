"""Multi-device SP kernel equivalence (promoted from the old standalone
tests/multidevice/sp_check.py script into a proper pytest module).

Every SP composition — pure ring over a 1D mesh, hybrid fast-SP over
(outer, inner) meshes with both inner strategies, multi-pod 3-axis ring,
GQA/MQA head-count corners and distributed decode — must match the
single-device reference within float32 tolerance.

Skips unless jax sees >= 8 devices (see conftest.py for the invocation).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.kernels import ref
from repro.sp import (distributed_decode_attention, fast_sp_attention,
                      ring_attention_local)

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "set before jax initializes (see tests/multidevice/conftest.py)")

TOL = 2e-5
B, H, KV, S, D = 2, 4, 2, 64, 16


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(3)

    def t(*s):
        return jnp.asarray(rng.normal(size=s), jnp.float32)

    return t(B, H, S, D), t(B, KV, S, D), t(B, KV, S, D)


def test_ring_attention_matches_reference(qkv):
    q, k, v = qkv
    mesh = jax.make_mesh((8,), ("data",))
    want = ref.mha_reference(q, k, v, causal=True)
    fn = functools.partial(ring_attention_local, axis_name="data", causal=True)
    got = jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(P(None, None, "data", None),) * 3,
        out_specs=P(None, None, "data", None), check_vma=False))(q, k, v)
    assert float(jnp.abs(want - got).max()) < TOL


@pytest.mark.parametrize("strategy", ["a2a", "allgather"])
@pytest.mark.parametrize("window", [0, 24])
def test_hybrid_fast_sp_matches_reference(qkv, strategy, window):
    q, k, v = qkv
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    got = fast_sp_attention(q, k, v, mesh=mesh, strategy=strategy,
                            causal=True, sliding_window=window)
    want = ref.mha_reference(q, k, v, causal=True, sliding_window=window)
    err = float(jnp.abs(want - got).max())
    assert err < TOL, (strategy, window, err)


def test_multipod_three_axis_ring(qkv):
    q, k, v = qkv
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    got = fast_sp_attention(q, k, v, mesh=mesh, strategy="a2a", causal=True,
                            outer_axes=("pod", "data"))
    want = ref.mha_reference(q, k, v, causal=True)
    assert float(jnp.abs(want - got).max()) < TOL


@pytest.mark.parametrize("strategy", ["a2a", "allgather"])
def test_mqa_kv_heads_not_divisible_by_axis(strategy):
    """MQA: 1 KV head on a 2-wide inner axis exercises the replicate-KV
    corner of both strategies."""
    rng = np.random.default_rng(5)

    def t(*s):
        return jnp.asarray(rng.normal(size=s), jnp.float32)

    q, k, v = t(B, 8, S, D), t(B, 1, S, D), t(B, 1, S, D)
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    got = fast_sp_attention(q, k, v, mesh=mesh, strategy=strategy, causal=True)
    want = ref.mha_reference(q, k, v, causal=True)
    assert float(jnp.abs(want - got).max()) < TOL


@pytest.mark.parametrize("window", [0, 16])
def test_distributed_decode_matches_reference(window):
    rng = np.random.default_rng(7)

    def t(*s):
        return jnp.asarray(rng.normal(size=s), jnp.float32)

    qd, kd, vd = t(3, H, D), t(3, KV, S, D), t(3, KV, S, D)
    cl = jnp.asarray([10, 40, 64], jnp.int32)
    mesh = jax.make_mesh((8,), ("data",))
    want = ref.decode_attention_reference(qd, kd, vd, cl,
                                          sliding_window=window)
    got = distributed_decode_attention(qd, kd, vd, cl, mesh=mesh,
                                       seq_axes=("data",),
                                       sliding_window=window)
    assert float(jnp.abs(want - got).max()) < TOL
