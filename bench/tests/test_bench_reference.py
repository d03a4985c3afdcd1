"""The plain reference computes what the program's model computes: both in
float32 on the same bench-made weights, at a tiny size on the CPU."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_tiny  # noqa: E402
from benchlib.spec import load_module  # noqa: E402

from repro.models import model as mdl  # noqa: E402

REF = load_module(bench_tiny.BENCH / "references" / "llama_dense.py")
ADAPT = load_module(bench_tiny.BENCH / "adapters" / "llama_dense.py")


@pytest.mark.parametrize("theta", [10000.0, 5e6])
def test_reference_matches_model_forward(theta):
    c = dict(bench_tiny.TINY_CONFIG, torch_dtype="float32", rope_theta=theta)
    params = ADAPT.make_params(c, 2**32 + 3)
    cfg = ADAPT.program_config(c)
    seq = np.random.default_rng(0).integers(0, c["vocab_size"], 150)
    with jax.default_matmul_precision("highest"):
        want, _ = mdl.forward(cfg, params, {"tokens": jnp.asarray(seq[None])},
                              impl="ref")
    rows = np.arange(100, 150)
    got = REF.logits(c, params, seq, rows)
    np.testing.assert_allclose(got, np.asarray(want[0, rows]), rtol=2e-4,
                               atol=2e-4)


def test_fp8_control_departs():
    c = dict(bench_tiny.TINY_CONFIG, torch_dtype="float32")
    params = ADAPT.make_params(c, 5)
    seq = np.arange(60) % c["vocab_size"]
    rows = np.arange(60)
    a = REF.logits(c, params, seq, rows)
    b = REF.logits(c, params, seq, rows, weights="fp8")
    rel = np.linalg.norm(a - b) / np.linalg.norm(a)
    assert 0.005 < rel < 0.5
