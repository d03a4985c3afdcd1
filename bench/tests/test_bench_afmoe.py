"""The afmoe (Trinity) pieces of the benchmark on the CPU: the plain
reference against the program's model, the float8 control, the operation
counts by hand, and the readers of the program's new counters on
hand-made windows."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_tiny  # noqa: E402
from benchlib.spec import load_module  # noqa: E402

from repro import obs  # noqa: E402


def reader(name):
    return load_module(bench_tiny.BENCH / "metrics" / f"{name}.py")


def tracer(counters):
    tr = obs.Tracer()
    tr.counters.update(counters)
    return tr


def ctx_with(backend):
    win = SimpleNamespace(requests=[], v_end=10.0)
    return SimpleNamespace(backend=backend, window=win, long_threshold=2048)
AFMOE_REF = load_module(bench_tiny.BENCH / "references" / "afmoe.py")
AFMOE = load_module(bench_tiny.BENCH / "adapters" / "afmoe.py")


def _afmoe_config(**kw):
    import json
    c = json.loads((bench_tiny.BENCH / "configs"
                    / "trinity-mini-16of128E.json").read_text())
    c.update(num_hidden_layers=4, hidden_size=128, num_attention_heads=4,
             num_key_value_heads=2, head_dim=32, intermediate_size=256,
             moe_intermediate_size=64, router_experts=8, num_experts=4,
             num_experts_per_tok=2, num_dense_layers=1, sliding_window=24,
             vocab_size=256, torch_dtype="float32")
    c.update(kw)
    return c


@pytest.mark.parametrize("offset", [0, 4])
def test_afmoe_reference_matches_model_forward(offset):
    """The afmoe reference and the program's full-sequence forward agree
    in float32, windows wrapped (150 tokens, window 24), for either half
    of the experts held."""
    from repro.models import afmoe
    c = _afmoe_config(expert_offset=offset)
    params = AFMOE.make_params(c, 2**32 + 5)
    cfg = AFMOE.program_config(c)
    seq = np.random.default_rng(1).integers(0, c["vocab_size"], 150)
    with jax.default_matmul_precision("highest"):
        want = afmoe.forward(cfg, params, jnp.asarray(seq[None]))
    rows = np.arange(90, 150)
    got = AFMOE_REF.logits(c, params, seq, rows)
    np.testing.assert_allclose(got, np.asarray(want[0, rows]), rtol=2e-4,
                               atol=2e-4)


def test_afmoe_fp8_control_departs():
    c = _afmoe_config()
    params = AFMOE.make_params(c, 6)
    seq = np.arange(60) % c["vocab_size"]
    a = AFMOE_REF.logits(c, params, seq, np.arange(60))
    b = AFMOE_REF.logits(c, params, seq, np.arange(60), weights="fp8")
    rel = np.linalg.norm(a - b) / np.linalg.norm(a)
    assert 0.005 < rel < 0.5


def test_afmoe_hand_counts():
    """Trinity-Mini at this chip's share, by hand: attention and gate
    2048*128*(2*32 + 2*4) + 32*128*2048 = 27,262,976 a layer; a MoE layer's
    router 2048*128, shared expert 3*2048*1024, and one held expert a
    token on average (8 * 16 / 128)."""
    from benchlib import flops_afmoe as fa
    m = fa.Dims.of(json.loads((bench_tiny.BENCH / "configs"
                               / "trinity-mini-16of128E.json").read_text()))
    assert fa.attn_params(m) == 27_262_976
    assert m.reach == 1.0 and sum(m.windowed) == 24
    moe = 2048 * 128 + 2 * 3 * 2048 * 1024
    want = 27_262_976 + (2 * 3 * 2048 * 6144 + 30 * moe) / 32
    assert fa.mean_layer_params(m, 1.0) == pytest.approx(want)
    # windowed pairs: capped at the window, the prefix counted
    for S, P, W in [(5, 0, 3), (10, 4, 3), (9000, 100, 2048), (7, 0, 0)]:
        brute = sum(min(q + 1, W) if W else q + 1 for q in range(P, P + S))
        assert fa.pairs(m, S, P, W) == brute
    # a decode step at 100 live tokens reads every layer's 101 positions
    assert fa.decode_kv_tokens(m, [100]) == 32 * 101
    assert fa.decode_kv_tokens(m, [5000]) == 8 * 5001 + 24 * 2048


def _afmoe_ctx(counters):
    cfg = SimpleNamespace(name="trinity-mini-16of128E")
    be = SimpleNamespace(obs=tracer(counters), cfg=cfg)
    return ctx_with(be)


def test_window_kv_share_scales_full_blocks_to_the_windowed_layers():
    # 10 migrations: 8 full layers x 571 blocks, 24 windowed x 129 each
    ctx = _afmoe_ctx({"admit/blocks_full": 10 * 8 * 571,
                      "admit/blocks_window": 10 * 24 * 129})
    got = reader("window_kv_share").read(ctx)
    assert got == pytest.approx(100 * 129 / 571)


def test_moe_held_share_counts_assignments_per_expert_slot():
    # 1000 token-layers of 8 choices, 1000 of them on held experts
    ctx = _afmoe_ctx({"moe/token_layers": 1000,
                      "moe/held_assignments": 1000})
    assert reader("moe_held_share").read(ctx) == pytest.approx(12.5)


def test_mfu_trinity_counts_the_served_work_over_the_window():
    """A 4096-token prompt's 2-layer quantum, its first-token head and
    two decode steps, served in a 2 s window on one chip."""
    from benchlib import flops_afmoe as fa
    ctx = _afmoe_ctx({})
    ctx.peak, ctx.chips = {"flops_bf16": 197e12}, 1
    ctx.window.wall_s = 2.0
    ctx.taps = SimpleNamespace(prefill_quanta=[(4096, 2, 0)],
                               finalize=[4096],
                               decode_steps=[(0.01, (100,)),
                                             (0.03, (3000, 5))])
    m = fa.of_ctx(ctx)
    # by hand: 2 x 4096 tokens through the mean layer's matmuls, 4 x 32 x
    # 128 a query-key pair over each layer's pairs (windowed capped at
    # 2048), the head once, and each step's rows and KV positions
    pairs = (24 * fa.pairs(m, 4096, 0, 2048)
             + 8 * 4096 * 4097 // 2) / 32
    prefill = 2 * (2 * 4096 * fa.mean_layer_params(m, 1.0)
                   + 4 * 32 * 128 * pairs)
    head = 2 * 2048 * 200192
    per_row = 2 * 32 * fa.mean_layer_params(m, 1.0) + head
    kv = 32 * 101 + (8 * 3001 + 24 * 2048) + 32 * 6
    decode = 3 * per_row + 4 * 32 * 128 * kv
    want = 100 * (prefill + head + decode) / (2.0 * 197e12)
    assert reader("mfu.trinity").read(ctx) == pytest.approx(want)


@pytest.mark.parametrize("name", ["window_kv_share", "moe_held_share",
                                  "mfu.trinity"])
def test_afmoe_readers_read_nothing_of_another_model(name):
    be = SimpleNamespace(obs=tracer({"moe/token_layers": 5}),
                         cfg=SimpleNamespace(name="mistral-7b-8of32L"))
    ctx = ctx_with(be)
    ctx.peak, ctx.trace = {"flops_bf16": 1.0}, None
    assert reader(name).read(ctx) is None


AFMOE_CELL = "tiny-afmoe.tiny_mixed"


@pytest.fixture(scope="module")
def afmoe_root(tmp_path_factory):
    """The tiny benchmark copy with a tiny afmoe configuration (window 24
    over 16-token blocks, 4 of 8 experts held) served on the tiny mix, in
    float32: at this size bfloat16 already flips near-tied routing choices
    (served gaps 0.04-0.53 over three seeds on the CPU, the float8
    control 0.71-1.69), so the rehearsal holds the path to the float32
    reference at the tiny cell's limit instead."""
    root = bench_tiny.make_root(tmp_path_factory.mktemp("bench"))
    conf = _afmoe_config(name="tiny-afmoe")
    (root / "bench/configs/tiny-afmoe.json").write_text(json.dumps(conf))
    (root / f"bench/cells/{AFMOE_CELL}.json").write_text(
        json.dumps(bench_tiny.TINY_CELL))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-afmoe", "source": "test",
                             "file": "bench/configs/tiny-afmoe.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": AFMOE_CELL, "config": "tiny-afmoe",
                               "traffic": "tiny_mixed", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "trinity-mini-16of128E.azure_mixed" in m.get("workloads", ()):
            m["workloads"].append(AFMOE_CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_afmoe_traced_rehearsal_is_correct_and_reads_its_counters(
        afmoe_root, monkeypatch):
    """The afmoe cell through `bench/run.py`'s run on the CPU: served
    through pecsched with rings and held experts, compared with the
    float32 reference, its new counters read."""
    # the copy's readers find its configuration through the copy's
    # `benchlib`, not one imported from the repository's `bench/`
    for name in [m for m in sys.modules if m.split(".")[0] == "benchlib"]:
        monkeypatch.delitem(sys.modules, name)
    monkeypatch.syspath_prepend(str(afmoe_root / "bench"))
    from benchlib.spec import Cell
    run = load_module(afmoe_root / "bench" / "run.py")
    res = run.run(Cell(AFMOE_CELL, afmoe_root), bench_tiny_seed(), 60.0,
                  True, require_chip=False)
    assert res["correct"] is True, res["checks"]
    assert res["checks"]["window_compiles"]["value"] == 0
    m = {k: v["value"] for k, v in res["metrics"].items()}
    # 24 windowed positions (3 blocks) of max_len 224 (14 blocks) a slot
    assert m["window_kv_share"] == pytest.approx(100 * 3 / 14)
    assert 0 < m["moe_held_share"] < 100
    for name in ("decode_step_ms", "kv_migrate_ms", "tpot_own_p50_s"):
        assert name in m
    # no chip: nothing under a device metric's name
    assert "mfu.trinity" not in m


def bench_tiny_seed():
    return 2**31 + 4242
