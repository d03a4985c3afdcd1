"""Metric readers on a hand-made window, with the numbers worked by hand."""
import sys
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_tiny  # noqa: E402
from benchlib.spec import load_module  # noqa: E402


def reader(name):
    return load_module(bench_tiny.BENCH / "metrics" / f"{name}.py")


def test_tokens_per_s_counts_running_prefills_by_layers_done():
    # rid 0 finished (100 + 5 tokens), rid 1 served its first token
    # (9000 + 1), rid 2 prefilled 6 of 8 layers of 8000 (6000), rid 3 queued
    by_rid = {0: 100, 1: 9000, 2: 8000, 3: 4000}
    win = SimpleNamespace(served={0: [1] * 5, 1: [7], 3: []},
                          prefill_layers={2: 6}, n_layers=8, wall_s=2.0)
    ctx = SimpleNamespace(
        window=win,
        by_rid={r: SimpleNamespace(prompt_len=n) for r, n in by_rid.items()})
    assert reader("tokens_per_s").read(ctx) == (105 + 9001 + 6000) / 2.0


def test_long_jct_s_per_ktok_weighs_longs_by_size():
    # longs of 4000 and 8000 tokens: one finished 2 s after arrival, one
    # still running at the close (virtual 10 s), so it enters at age 4 s
    req = SimpleNamespace
    win = SimpleNamespace(v_end=10.0, requests=[
        req(input_len=4000, arrival=1.0, finish=3.0),
        req(input_len=8000, arrival=6.0, finish=None),
        req(input_len=500, arrival=2.0, finish=2.5)])
    ctx = SimpleNamespace(window=win, long_threshold=2048)
    assert reader("long_jct_s_per_ktok").read(ctx) == 1e3 * 6.0 / 12000
