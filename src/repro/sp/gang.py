"""Gang-scheduled sequence-parallel prefill: the mesh plumbing and the
shard_map layer quanta the EngineBackend runs when a scheduling policy
requests fast SP for a long input (paper §5.3, live on real engines).

A *gang* is N replicas atomically claimed by the policy for one long
prefill.  On the execution side the gang maps onto a (ring, sp) device
mesh: the sequence is sharded outer-major across both axes, the outer
axis runs ring attention (neighbour ppermute), and the inner axis runs
the planner-chosen strategy — `SPPlan.inner_impl`: "a2a" (Ulysses) or
"allgather" (Megatron-SP) — exactly the hybrid in `sp/hybrid.py`, here
driven quantum-by-quantum so the scheduler can preempt between quanta.

Quantum semantics: `layers_per_quantum` is calibrated for single-replica
execution; a gang of degree N advances `layers_per_quantum * N` layers per
quantum at equal per-device compute, so SP prefill completes in ~N x fewer
engine quanta while preemption latency (one quantum) stays bounded — the
discrete version of the paper's "fast SP shrinks the preemption window".

Tests/CI force host devices via XLA_FLAGS=--xla_force_host_platform_device_count=8
(tests/multidevice/); on a single-device host `gang_degree` returns 1 and
the backend falls back to the single-replica path.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import model as mdl
from repro.sp.hybrid import fast_sp_attention_local
from repro.sp.planner import SPPlan, TPU_V5E, HardwareSpec, plan_fast_sp

OUTER_AXIS = "ring"          # cross-"node" ring attention
INNER_AXIS = "sp"            # high-bandwidth inner domain (a2a / allgather)

SEQ_AXES = (OUTER_AXIS, INNER_AXIS)


def gang_degree(requested: int, *, n_devices: Optional[int] = None,
                cap: int = 0) -> int:
    """Realizable gang size: the replicas the policy claimed, clipped to the
    host's device count (and an optional cap).  Degrees whose inner axis
    would not divide the head count fall back to a pure-ring mesh, so any
    degree >= 2 is realizable; < 2 means "no gang, single-replica path"."""
    n = min(requested, n_devices if n_devices is not None
            else jax.device_count())
    if cap:
        n = min(n, cap)
    return n if n >= 2 else 1


def _mesh_shape(degree: int, num_heads: int) -> Tuple[int, int]:
    """(outer, inner): inner 2 when it divides both the degree and the head
    count (exercising the a2a/allgather strategies), else pure ring."""
    if degree % 2 == 0 and num_heads % 2 == 0:
        return degree // 2, 2
    return degree, 1


def make_gang_mesh(degree: int, num_heads: int) -> Mesh:
    outer, inner = _mesh_shape(degree, num_heads)
    devs = np.asarray(jax.devices()[:degree]).reshape(outer, inner)
    return Mesh(devs, SEQ_AXES)


def plan_for_gang(cfg: ModelConfig, input_len: int, mesh: Mesh,
                  hw: HardwareSpec = TPU_V5E) -> SPPlan:
    """The paper's four-combination search, shaped to this gang's mesh:
    outer axis ~ nodes, inner axis ~ GPUs per node.  `input_len` is the
    request's CLUSTER-scale length — strategy choice must reflect the real
    request even when the engine executes a scale-model prompt."""
    outer, inner = mesh.shape[OUTER_AXIS], mesh.shape[INNER_AXIS]
    return plan_fast_sp(cfg, input_len, n_nodes=outer,
                        gpus_per_node=max(inner, 1), tp=max(inner, 1), hw=hw)


# ---------------------------------------------------------------------------
# the shard_map layer quantum
# ---------------------------------------------------------------------------
def _sp_layer_slice_local(x, layers, lo, *, cfg: ModelConfig, strategy: str,
                          n: int):
    """Runs INSIDE shard_map.  x (1, s_loc, d) = this rank's sequence
    shard; layers = the stacked layer params, replicated; layers
    [lo, lo + n) run.  The layer body IS `model._dense_layer` — projections,
    RoPE, residuals, MLP all shared with the single-replica engine path —
    with the core attention swapped for the hybrid SP kernel (outer ring +
    inner a2a/allgather) via the `attn_fn` hook, and RoPE fed GLOBAL
    positions so shards agree with the single-replica computation."""
    pi = jax.lax.axis_size(INNER_AXIS)
    oidx = jax.lax.axis_index(OUTER_AXIS)
    iidx = jax.lax.axis_index(INNER_AXIS)
    B, s_loc, d = x.shape
    rank = oidx * pi + iidx                      # outer-major linear rank
    positions = rank * s_loc + jnp.broadcast_to(
        jnp.arange(s_loc)[None], (B, s_loc))
    attn_fn = functools.partial(fast_sp_attention_local,
                                outer_axes=OUTER_AXIS, inner_axis=INNER_AXIS,
                                strategy=strategy)

    def body(x, i):
        x, kv = mdl._dense_layer(cfg, mdl.layer_at(layers, i), x, positions,
                                 sliding_window=cfg.sliding_window,
                                 impl="xla", write_cache=True,
                                 attn_fn=attn_fn)
        return x, (kv.k, kv.v)

    return jax.lax.scan(body, x, lo + jnp.arange(n))


@functools.partial(jax.jit, static_argnames=("cfg",))
def _embed(params, toks, *, cfg: ModelConfig):
    return params["embed"][toks].astype(jnp.dtype(cfg.dtype))


@functools.partial(jax.jit, static_argnames=("cfg", "mesh", "n", "strategy"))
def _gang_slice(layers, x, lo, *, cfg: ModelConfig, mesh: Mesh, n: int,
                strategy: str):
    """Layers [lo, lo + n) of a sequence-sharded prefill on `mesh`; the
    weights enter as arguments, replicated."""
    seq = P(None, SEQ_AXES, None)
    kv_seq = P(None, None, None, SEQ_AXES, None)
    fn = functools.partial(_sp_layer_slice_local, cfg=cfg, strategy=strategy,
                           n=n)
    return jax.shard_map(fn, mesh=mesh, in_specs=(seq, P(), P()),
                         out_specs=(seq, (kv_seq, kv_seq)),
                         check_vma=False)(x, layers, lo)


@functools.partial(jax.jit, static_argnames=("cfg", "s_real"))
def _last_logits(params, x, *, cfg: ModelConfig, s_real: int):
    return mdl.lm_logits(cfg, params, x[:, s_real - 1])


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("tokens", "x", "kv_k", "kv_v"),
                   meta_fields=("rid", "s_real", "layer", "degree", "plan"))
@dataclass
class GangPrefillState:
    """Suspension state of a gang-SP prefill (§5.1 x §5.3): the sharded
    intermediate + per-layer sequence-sharded KV, resumable between quanta
    with bit-identical results."""
    rid: int
    tokens: jnp.ndarray                  # (1, S_pad) int32, padded
    s_real: int                          # unpadded prompt length
    x: jax.Array                         # (1, S_pad, d), mesh-sharded
    layer: int                           # next layer to execute
    degree: int
    plan: SPPlan
    kv_k: List[jax.Array] = field(default_factory=list)  # per-quantum stacks
    kv_v: List[jax.Array] = field(default_factory=list)  # (n, 1, KV, S_pad, hd)


class GangSPRunner:
    """Gang-SP prefill for one (model, mesh).

    The EngineBackend keeps one runner per gang degree.  The weights are
    placed replicated on the mesh once, here, and enter every program as
    arguments; the inner strategy (`SPPlan.inner_impl` of each request's
    plan) selects the compiled slice, so a policy sweep pays the shard_map
    compilation once per (prompt bucket, strategy)."""

    def __init__(self, cfg: ModelConfig, params, mesh: Mesh):
        self.cfg = cfg
        self.mesh = mesh
        self.params = jax.device_put(params, NamedSharding(mesh, P()))
        self.degree = int(np.prod([mesh.shape[a] for a in SEQ_AXES]))

    # ------------------------------------------------------------------
    def start(self, rid: int, tokens: np.ndarray,
              plan: SPPlan) -> GangPrefillState:
        """Embed + pad the prompt to a multiple of the gang degree (pad
        tokens sit AFTER the real ones; causality keeps them out of every
        real row's attention, and their KV is sliced away at scatter)."""
        toks = np.asarray(tokens, np.int32).reshape(-1)
        s_real = int(toks.shape[0])
        pad = (-s_real) % self.degree
        toks = jnp.asarray(np.pad(toks, (0, pad))[None])
        return GangPrefillState(rid=rid, tokens=toks, s_real=s_real,
                                x=_embed(self.params, toks, cfg=self.cfg),
                                layer=0, degree=self.degree, plan=plan)

    def quantum(self, st: GangPrefillState,
                layers: int) -> Tuple[GangPrefillState, bool]:
        """Advance up to `layers` layers (the gang-scaled quantum) with the
        request's planned inner strategy."""
        lo = st.layer
        hi = min(lo + layers, self.cfg.num_layers)
        x, (kh, vh) = _gang_slice(self.params["layers"], st.x, lo,
                                  cfg=self.cfg, mesh=self.mesh, n=hi - lo,
                                  strategy=st.plan.inner_impl)
        st.x = x
        st.kv_k.append(kh)
        st.kv_v.append(vh)
        st.layer = hi
        return st, hi == self.cfg.num_layers

    def logits(self, st: GangPrefillState) -> jnp.ndarray:
        assert st.layer == self.cfg.num_layers
        return _last_logits(self.params, st.x, cfg=self.cfg,
                            s_real=st.s_real)

    def gather_kv(self, st: GangPrefillState) -> Tuple[np.ndarray, np.ndarray]:
        """Pull the sequence-sharded per-layer KV to the host as contiguous
        (L, KV, S, hd) arrays — the §5.3 scatter back to the home replica."""
        k = jnp.concatenate(st.kv_k, axis=0)[:, 0, :, :st.s_real]
        v = jnp.concatenate(st.kv_v, axis=0)[:, 0, :, :st.s_real]
        return jax.device_get(k), jax.device_get(v)
