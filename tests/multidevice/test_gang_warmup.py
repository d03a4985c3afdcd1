"""Gang SP (§5.3) as the 4-chip benchmark cell serves it, on 4 forced host
devices (tests/test_sp.py replays this module with the flag set):

* after `EngineBackend.warmup` the window compiles nothing, though the
  fleet forms gangs of degree 2, 3 and 4;
* every gang runner reads one placement of the weights: a device holds
  them once, and device 0 lends the engines' own arrays;
* the benchmark's check fails a gang that skips the exchange between
  chips (each shard attends to its own keys only).

Runs the harness in `bench/` at a tiny size, through `bench_tiny`.
"""
import functools
import importlib.util
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.kernels import ops
from repro.models import model as mdl
import repro.sp.gang as gang_mod

pytestmark = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4 set "
           "before jax initializes (tests/test_sp.py replays this module)")

BENCH = Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH / "tests"))
import bench_tiny  # noqa: E402
from benchlib.spec import Cell  # noqa: E402

CELL = "tiny-dense.tiny_long4"
#: longs only, at three lengths the cost model gangs differently: with
#: `target_prefill_s` 2.5e-7 the tiny model's 96, 128 and 200-token
#: prefills claim 2, 3 and 4 of the 4 general engines
MIX = {"loop": "open", "arrival": {"process": "poisson"},
       "input": {"median": 128, "sigma": 0.5, "min": 96, "max": 200},
       "output": {"median": 6, "sigma": 0.8, "min": 1, "max": 12},
       "lengths": [96, 128, 200], "block": 10}
LAYOUT = {"policy": "pecsched", "n_engines": 4, "max_slots": 2,
          "max_len": 224, "layers_per_quantum": 1, "long_threshold": 96,
          "target_prefill_s": 2.5e-7, "rate_rps": 20.0, "virtual_span": 0,
          "sample": {"min_tokens": 40, "max_requests": 6},
          "limits": {"served_gap_max": 0.05}}
SEED = 2**31 + 4099


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = bench_tiny.make_root(tmp_path_factory.mktemp("bench"))
    (root / "bench/traffic/tiny_long.json").write_text(json.dumps(MIX))
    (root / f"bench/cells/{CELL}.json").write_text(json.dumps(LAYOUT))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": CELL, "config": "tiny-dense",
                               "traffic": "tiny_long", "chips": 4,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run(root, before_window=None):
    spec = importlib.util.spec_from_file_location("bench_run_gang",
                                                  root / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.run(Cell(CELL, root), SEED, 60.0, False, require_chip=False,
                   before_window=before_window)


@pytest.fixture(scope="module")
def sound(root):
    """The sound run, and the cluster it served on."""
    held = {}
    res = run(root, before_window=lambda mc: held.setdefault("mc", mc))
    return res, held["mc"]


def test_after_warmup_no_gang_of_any_degree_compiles(sound):
    res, mc = sound
    assert res["correct"] is True, res["checks"]
    assert res["checks"]["window_compiles"]["value"] == 0
    assert {2, 3, 4} <= set(mc.backend.sp_timings)
    assert set(mc.backend._gang_runners) == {2, 3, 4}


def test_gang_runners_share_one_placement_of_the_weights(sound):
    _, mc = sound
    be = mc.backend
    placed = [dict(jax.tree_util.tree_leaves_with_path(r.params))
              for r in be._gang_runners.values()]
    for path, leaf in jax.tree_util.tree_leaves_with_path(be.params):
        on = {}
        for leaves in placed:
            for s in leaves[path].addressable_shards:
                on.setdefault(s.device, set()).add(
                    s.data.unsafe_buffer_pointer())
        assert all(len(ptrs) == 1 for ptrs in on.values()), path
        assert on[jax.devices()[0]] == {leaf.unsafe_buffer_pointer()}, path


@functools.partial(jax.jit, static_argnames=("cfg", "mesh", "n",
                                             "strategy"))
def _slice_without_exchange(layers, x, lo, *, cfg, mesh, n, strategy):
    """`gang._gang_slice` with each shard attending to its own keys alone:
    no ring, no all-to-all between the devices."""
    del strategy
    seq = P(None, gang_mod.SEQ_AXES, None)
    kv_seq = P(None, None, None, gang_mod.SEQ_AXES, None)

    def local(x, layers, lo):
        pi = jax.lax.axis_size(gang_mod.INNER_AXIS)
        rank = jax.lax.axis_index(gang_mod.OUTER_AXIS) * pi \
            + jax.lax.axis_index(gang_mod.INNER_AXIS)
        B, s_loc, _ = x.shape
        positions = rank * s_loc + jnp.broadcast_to(
            jnp.arange(s_loc)[None], (B, s_loc))

        def attn(qh, kh, vh, *, causal, sliding_window):
            return ops.attention(qh, kh, vh, causal=True, impl="xla")

        def body(x, i):
            x, kv = mdl._dense_layer(cfg, mdl.layer_at(layers, i), x,
                                     positions, sliding_window=0, impl="xla",
                                     write_cache=True, attn_fn=attn)
            return x, (kv.k, kv.v)
        return jax.lax.scan(body, x, lo + jnp.arange(n))
    return jax.shard_map(local, mesh=mesh, in_specs=(seq, P(), P()),
                         out_specs=(seq, (kv_seq, kv_seq)),
                         check_vma=False)(x, layers, lo)


def test_a_gang_without_the_exchange_between_chips_is_not_correct(
        root, sound, monkeypatch):
    def before_window(mc):
        monkeypatch.setattr(gang_mod, "_gang_slice", _slice_without_exchange)
        mc.backend.warmup(MIX["lengths"], [])   # its programs, before the window
    res = run(root, before_window=before_window)
    gap = res["checks"]["served_gap_max"]
    assert res["correct"] is False
    assert gap["value"] > gap["limit"]
    assert res["checks"]["window_compiles"]["value"] == 0
    # the same seed, served soundly, reads far below the limit
    assert sound[0]["checks"]["served_gap_max"]["value"] < gap["limit"]
