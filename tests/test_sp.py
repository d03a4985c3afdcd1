"""Sequence-parallelism tests.

Multi-device equivalence (ring / hybrid fast-SP / distributed decode vs the
single-device reference) needs >1 XLA device, so it runs in a SUBPROCESS
with XLA_FLAGS=--xla_force_host_platform_device_count=8 (tests in this
process must keep seeing 1 device per the harness contract).
"""
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.sp.common import finalize, merge_partials
from repro.sp.planner import plan_fast_sp, ring_hop_time, stage_costs


def test_multidevice_sp_equivalence():
    """Replay the multidevice kernel-equivalence module (a proper pytest
    module since the gang-SP PR) in a subprocess with the forced-8-device
    flag, so tier-1 keeps covering it while staying single-device itself.
    The heavier gang-scheduling integration tests in the same directory run
    in CI's dedicated multidevice-smoke job."""
    import os
    module = Path(__file__).parent / "multidevice" / "test_sp_kernels.py"
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    p = subprocess.run([sys.executable, "-m", "pytest", "-q", "-p",
                       "no:cacheprovider", str(module)],
                       capture_output=True, text=True, timeout=900, env=env)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    assert "passed" in p.stdout and "skipped" not in p.stdout


def test_multidevice_gang_warmup():
    """Replay tests/multidevice/test_gang_warmup.py on 4 forced host
    devices (a 2x2 mesh, as the 4-chip benchmark cell): no gang compiles
    after warm-up, the gang runners share one placement of the weights,
    and the benchmark's check fails a gang without the exchange between
    chips."""
    import os
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(root / "src"))
    p = subprocess.run([sys.executable, "-m", "pytest", "-q", "-p",
                        "no:cacheprovider",
                        str(root / "tests/multidevice/test_gang_warmup.py")],
                       capture_output=True, text=True, timeout=900, env=env)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    assert "3 passed" in p.stdout and "skipped" not in p.stdout


def test_merge_partials_identity_and_empty():
    o = jnp.ones((1, 2, 3, 4))
    lse = jnp.zeros((1, 2, 3))
    empty_o = jnp.zeros_like(o)
    empty_lse = jnp.full_like(lse, -jnp.inf)
    om, lm = merge_partials(o, lse, empty_o, empty_lse)
    np.testing.assert_allclose(om, o)
    np.testing.assert_allclose(lm, lse)
    # both empty stays empty, finalize zeroes it
    om, lm = merge_partials(empty_o, empty_lse, empty_o, empty_lse)
    assert np.all(np.isneginf(lm))
    np.testing.assert_allclose(finalize(om, lm, jnp.float32), 0.0)


def test_planner_four_combinations_positive():
    cfg = get_config("llama3_8b")
    vols = stage_costs(cfg, s=4096, T=4, G=8)
    for stage in vols.values():
        for v in stage.values():
            assert v > 0
    plan = plan_fast_sp(cfg, 131072, n_nodes=8, gpus_per_node=8, tp=8)
    assert plan.attn_strategy in ("megatron", "ulysses")
    assert plan.mlp_strategy in ("megatron", "ulysses")
    assert plan.est_time > 0
    assert plan.inner_impl in ("a2a", "allgather")


def test_planner_prefers_cheaper_comm_when_bandwidth_low():
    """With tiny link bandwidth the lower-comm-volume option must win the
    attention stage (the paper's Megatron-vs-Ulysses trade-off)."""
    from repro.sp.planner import HardwareSpec
    cfg = get_config("llama3_8b")
    slow = HardwareSpec(link_bw=1e9)
    fast = HardwareSpec(link_bw=1e12)
    p_slow = plan_fast_sp(cfg, 65536, n_nodes=8, gpus_per_node=8, tp=8, hw=slow)
    p_fast = plan_fast_sp(cfg, 65536, n_nodes=8, gpus_per_node=8, tp=8, hw=fast)
    vols = stage_costs(cfg, 65536 // 64, 8, 8)
    cheaper = min(("megatron", "ulysses"),
                  key=lambda n: vols["attn"][f"{n}_comm"])
    assert p_slow.attn_strategy == cheaper
    assert p_slow.est_time >= p_fast.est_time


def test_ring_hop_time_scales_with_segment():
    cfg = get_config("llama3_8b")
    assert ring_hop_time(cfg, 65536) > ring_hop_time(cfg, 4096)
