"""Operation and byte counts against hand counts of both configurations."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_tiny  # noqa: E402
from benchlib import flops, peaks  # noqa: E402


def dims(name):
    return flops.Dims.of(json.loads(
        (bench_tiny.BENCH / "configs" / f"{name}.json").read_text()))


@pytest.mark.parametrize("name,per_layer,params,gflop_tok,kv_kib", [
    # 4096*4096*2 + 2*4096*1024 + 3*4096*14336 = 218,103,808
    ("mistral-7b-8of32L", 218_103_808, 2.013e9, 3.4897, 32),
    # 7168*7168*2 + 2*7168*1024 + 3*7168*20480 = 557,842,432
    ("yi-34b-4of60L", 557_842_432, 3.149e9, 4.4627, 16),
])
def test_hand_counts(name, per_layer, params, gflop_tok, kv_kib):
    m = dims(name)
    assert flops.layer_matmul_params(m) == per_layer
    total = per_layer * m.layers + 2 * m.d * m.vocab
    assert abs(total - params) / params < 1e-3
    # one prompt token through the stage's matmuls, attention aside
    f = flops.prefill_flops(m, 1, m.layers) - 4 * m.heads * m.head_dim \
        * m.layers
    assert abs(f / 1e9 - gflop_tok) < 1e-3
    assert flops.kv_bytes_per_token_layer(m) * m.layers == kv_kib * 1024


def test_causal_attention_count():
    m = flops.Dims(1, 8, 2, 1, 4, 16, 32)
    # 3 queries, no prefix: 1 + 2 + 3 pairs; each 4 * heads * head_dim
    assert flops.causal_pairs(3) == 6
    assert flops.prefill_flops(m, 3, 1) == \
        2 * 3 * flops.layer_matmul_params(m) + 4 * 2 * 4 * 6
    assert flops.causal_pairs(2, prefix=5) == 2 * 5 + 3


def test_decode_counts_scale_with_live_lengths():
    m = dims("mistral-7b-8of32L")
    one = flops.decode_bytes(m, [100])
    two = flops.decode_bytes(m, [100, 300])
    assert two - one == flops.kv_bytes_per_token_layer(m) * m.layers * 301
    # weights dominate one step: 8 layers and the head, 1.88e9 parameters
    # at 2 bytes (the embedding table is read one row at a time)
    assert 3.75e9 < one < 3.77e9
    assert flops.decode_flops(m, [0, 0]) == 2 * flops.decode_flops(m, [0])


def test_roofline_picks_larger_bound():
    pk = peaks.peak_for("TPU v5 lite")
    assert flops.roofline_s(197e12, 1.0, pk) == (1.0, "compute")
    assert flops.roofline_s(1.0, 819e9, pk) == (1.0, "memory")


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peak_for("TPU v9000")
