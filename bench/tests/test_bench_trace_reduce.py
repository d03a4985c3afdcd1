"""The reduction from trace events to busy time, per-program device time
and the breakdown: on hand-made events with known answers, and on a small
trace recorded on a TPU v5e (`data/`)."""
import gzip
import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_tiny  # noqa: E402,F401
from benchlib import trace_reduce as tr  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def test_program_name():
    assert tr.program_name("jit__decode(12)") == "_decode"
    assert tr.program_name("jit__prefill_slice") == "_prefill_slice"
    assert tr.program_name("fusion.3") == "fusion.3"


def test_busy_union_idle_and_labels():
    host = [(0.0, 10.0, tr.WINDOW), (1.0, 4.0, "submit:short_decode"),
            (2.0, 3.0, "decode_iteration"), (6.0, 9.0, "dispatch")]
    ops = [(1.0, 2.0, "a"), (1.5, 2.5, "b"),      # overlap: union 1.0-2.5
           (5.0, 6.0, "a"), (11.0, 12.0, "a")]    # last one outside window
    mods = [(1.0, 2.5, "jit__decode"), (5.0, 6.0, "jit__prefill_slice(3)")]
    # a program's span covers its operations and the gaps between them
    t = tr.reduce_events({"/device:TPU:0": {"XLA Ops": ops,
                                            "XLA Modules": mods}}, host)
    assert t.window_s == 10.0
    assert t.busy_s == pytest.approx(2.5)
    assert t.program_s(("_decode",)) == pytest.approx(1.5)
    assert t.program_s(("_prefill_slice", "_decode")) == pytest.approx(2.5)
    # idle 0-1 (no span), 2.5-5 (mid 3.75: submit), 6-10 (mid 8: dispatch)
    assert t.idle_by_span == pytest.approx(
        {"no span": 1.0, "submit:short_decode": 2.5, "dispatch": 4.0})
    b = t.breakdown()
    assert b["device_ops"] == [["_decode", 1.5], ["_prefill_slice", 1.0]]
    assert b["idle_gaps"][0] == ["dispatch", 4.0]


def test_busy_is_the_mean_over_chips():
    host = [(0.0, 4.0, tr.WINDOW)]
    dev = {"/device:TPU:0": {"XLA Ops": [(0.0, 4.0, "x")]},
           "/device:TPU:1": {"XLA Ops": [(0.0, 2.0, "x")]}}
    t = tr.reduce_events(dev, host)
    assert t.n_chips == 2 and t.busy_s == pytest.approx(3.0)


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_events({}, [(0.0, 1.0, "dispatch")])


def test_recorded_chip_trace():
    rec = json.loads(gzip.decompress(
        (DATA / "v5e_trace_events.json.gz").read_bytes()))
    dev = {k: {ln: [tuple(e) for e in evs] for ln, evs in v.items()}
           for k, v in rec["device"].items()}
    host = [tuple(e) for e in rec["host"]]
    t = tr.reduce_events(dev, host)
    want = rec["expect"]
    assert t.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert t.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0.0 < t.busy_s <= t.window_s
    for prog, s in want["program_s"].items():
        assert t.program_s((prog,)) == pytest.approx(s, rel=1e-9)
    # busy by brute force: a 1 us grid over the window, marked where any
    # program or operation ran
    lo, hi = [(a, b) for a, b, n in host if n == tr.WINDOW][0]
    grid = np.zeros(int(round((hi - lo) * 1e6)), bool)
    for line in dev["/device:TPU:0"].values():
        for a, b, _ in line:
            i0 = max(int(round((a - lo) * 1e6)), 0)
            i1 = min(int(round((b - lo) * 1e6)), grid.size)
            grid[i0:i1] = True
    assert t.busy_s == pytest.approx(grid.sum() * 1e-6, rel=1e-3)
    # every program's device time lies inside the busy time
    assert sum(t.module_s.values()) <= t.busy_s * (1 + 1e-9)
    assert sum(t.idle_by_span.values()) == pytest.approx(
        t.window_s - t.busy_s, rel=1e-6)
