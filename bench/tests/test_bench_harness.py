"""The harness end to end on the CPU at a tiny size: it finds a new
configuration, traffic mix, cell and metric by name, drives the served path
through MiniCluster, and decides `correct` by the reference comparison,
which a broken served path fails.  Without a TPU the command prints no
result and exits non-zero."""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_tiny  # noqa: E402
from benchlib.spec import Cell  # noqa: E402

import repro.serving.engine as engine_mod  # noqa: E402

SEED = 2**31 + 77
#: the seed the fault and control readings use (tiny cell, CPU): the sound
#: path reads 0.004, a token altered 4.9, a decode step that leaves its KV
#: unchanged 0.34, the float8 control 0.18, against the limit 0.05
FAULT_SEED = 1


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(tmp_path_factory.mktemp("bench"))


def bench_run(root):
    sys.path.insert(0, str(root / "bench"))
    import importlib.util
    spec = importlib.util.spec_from_file_location("bench_run_copy",
                                                  root / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_tiny(root, trace=False, before_window=None, seed=SEED):
    return bench_run(root).run(Cell(bench_tiny.CELL, root), seed, 60.0, trace,
                               require_chip=False,
                               before_window=before_window)


@pytest.mark.parametrize("workload", [
    "mistral-7b-8of32L.azure_mixed", bench_tiny.CELL])
def test_without_a_chip_no_result_and_nonzero_exit(root, workload):
    """From a directory that holds only BENCHMARK.json and `bench/`."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_rehearsal_finds_new_files_and_is_correct(root):
    res = run_tiny(root)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 10 and res["failed"] == 0
    assert set(res["metrics"]) == {"short_ttft_p50_s", "tpot_p50_s",
                                   "long_jct_s_per_ktok", "tokens_per_s",
                                   "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"
    assert res["checks"]["window_compiles"]["value"] == 0


def test_traced_rehearsal_reads_per_layer_metrics(root):
    res = run_tiny(root, trace=True)
    assert res["correct"] is True, res["checks"]
    # the metric only the copy has: found by its name, read from its file
    assert res["metrics"]["arrived_n"]["value"] == res["attempted"]
    for name in ("host_ms_per_event", "short_qd_p95_s", "decode_step_ms",
                 "prefill_layer_ms.long", "short_ttft_p95_s", "tpot_p95_s",
                 "long_jct_p50_s"):
        assert name in res["metrics"]
    # no chip: nothing is written under a device metric's name
    for name in ("mfu", "prefill_roofline", "decode_roofline"):
        assert name not in res["metrics"]
    assert "busy_s" in res["device"] and "window_s" in res["device"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def _alter_token(mc):
    """A token altered where it is produced."""
    for eng in mc.backend._engines.values():
        di = eng.decode_iteration

        def broken(tokens, di=di):
            out = di(tokens)
            return {s: (t + 1) % 256 for s, t in out.items()}
        eng.decode_iteration = broken


def _state_unchanged(mc, monkeypatch):
    """A decode step that returns its KV state unchanged."""
    real = engine_mod._decode

    def broken(params, cache_k, cache_v, slot_len, tokens, *, cfg):
        logits, _, _ = real(params, cache_k, cache_v, slot_len, tokens,
                            cfg=cfg)
        return logits, cache_k, cache_v
    monkeypatch.setattr(engine_mod, "_decode", broken)


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
def test_broken_served_path_is_not_correct(root, fault, monkeypatch):
    if fault == "token_altered":
        bw = _alter_token
    else:
        def bw(mc):
            _state_unchanged(mc, monkeypatch)
    res = run_tiny(root, before_window=bw, seed=FAULT_SEED)
    assert res["correct"] is False
    gap = res["checks"]["served_gap_max"]
    assert gap["value"] > gap["limit"]


def test_control_reads_above_the_program(root):
    """The float8 control, put in the served tokens' place, ranks other
    tokens first where the bf16 program agrees with the reference, and the
    run's own checks find it not correct (the chip readings at full size
    set the limits; see PERF.md)."""
    mod = bench_run(root)
    res = mod.run(Cell(bench_tiny.CELL, root), FAULT_SEED, 60.0, False,
                  require_chip=False, control=True)
    gap = res["checks"]["served_gap_max"]
    assert res["correct"] is False
    assert np.isfinite(gap["value"]) and gap["value"] > gap["limit"]
    assert res["program_gap_max"] < gap["limit"]
    assert all(c["value"] <= c["limit"] for k, c in res["checks"].items()
               if k in ("failed", "window_compiles"))
