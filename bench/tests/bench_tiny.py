"""A tiny copy of the benchmark for CPU tests: the repository's `bench/`
copied into a temporary checkout, plus a tiny configuration, traffic mix,
cell and metric added as new files and entries, as a later PR would."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CELL = "tiny-dense.tiny_mixed"

TINY_CONFIG = {
    "name": "tiny-dense", "source": "test", "reference": "llama_dense",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 4,
    "vocab_size": 256, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "torch_dtype": "bfloat16"}

TINY_MIX = {
    "loop": "open", "arrival": {"process": "poisson"},
    "input": {"median": 40, "sigma": 1.6, "min": 8, "max": 200},
    "output": {"median": 8, "sigma": 1.0, "min": 1, "max": 16},
    "lengths": [32, 96, 200], "block": 20}

#: 64 requests (`virtual_span` 0), which drain well inside a test's window,
#: so every run of one seed finishes, samples and compares the same requests
TINY_CELL = {
    "policy": "pecsched", "n_engines": 2, "max_slots": 4, "max_len": 224,
    "layers_per_quantum": 2, "long_threshold": 96, "target_prefill_s": 15.0,
    "rate_rps": 20.0, "virtual_span": 0,
    "sample": {"min_tokens": 80, "max_requests": 12},
    "limits": {"served_gap_max": 0.05}}

#: a per-layer metric that only this copy has
NEW_METRIC = '''"""Requests that arrived in the window."""


def read(ctx):
    return len(ctx.window.requests)
'''


def make_root(tmp: Path) -> Path:
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "bench/configs/tiny-dense.json").write_text(
        json.dumps(TINY_CONFIG))
    (root / "bench/traffic/tiny_mixed.json").write_text(json.dumps(TINY_MIX))
    (root / f"bench/cells/{CELL}.json").write_text(json.dumps(TINY_CELL))
    (root / "bench/metrics/arrived_n.py").write_text(NEW_METRIC)
    bench["configs"].append({"name": "tiny-dense", "source": "test",
                             "file": "bench/configs/tiny-dense.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-dense",
                               "traffic": "tiny_mixed", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    bench["per_layer"].append({"name": "arrived_n", "unit": "requests",
                               "better": "higher", "source": "program_counter",
                               "layer": "event loop and policy",
                               "moves": "tokens_per_s", "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
