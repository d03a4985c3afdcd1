"""The readers of the program's spans and counters, on hand-made windows
with the numbers worked by hand; each reads nothing from a program that
lacks what it reads; and a traced run of the tiny cell on the CPU prints
all six."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_tiny  # noqa: E402
from benchlib.spec import Cell, load_module  # noqa: E402

from repro import obs  # noqa: E402

READERS = ("prefill_host_ms", "decode_host_ms", "kv_migrate_ms",
           "policy_ms_per_event", "long_suspended_share", "tpot_own_p50_s")


def reader(name):
    return load_module(bench_tiny.BENCH / "metrics" / f"{name}.py")


def tracer(spans, counters=None):
    tr = obs.Tracer()
    for name, (n, total) in spans.items():
        agg = tr.spans[name] = obs.Agg()
        agg.n, agg.total_s, agg.self_s = n, total, total
    tr.counters.update(counters or {})
    return tr


def ctx_with(backend, requests=(), v_end=10.0):
    win = SimpleNamespace(requests=list(requests), v_end=v_end)
    return SimpleNamespace(backend=backend, window=win, long_threshold=2048)


def test_prefill_host_ms_is_the_mean_quantum_span():
    # 4 quanta held the host 26 ms in all
    tr = tracer({"prefill_quantum": (4, 0.026),
                 "prefill_quantum/program": (4, 0.005),
                 "prefill_quantum/kv_split": (4, 0.020)})
    got = reader("prefill_host_ms").read(ctx_with(SimpleNamespace(obs=tr)))
    assert got == pytest.approx(6.5)


def test_decode_host_ms_counts_prep_and_dispatch_per_step():
    # 10 steps: 15 ms of preparation and 5 ms of dispatch before the chip
    # had the program; the view, append and readback come after
    tr = tracer({"decode_iteration": (10, 0.340),
                 "decode_iteration/prep": (10, 0.015),
                 "decode_iteration/view": (2, 0.004),
                 "decode_iteration/program": (10, 0.005),
                 "decode_iteration/append": (10, 0.030),
                 "decode_iteration/readback": (10, 0.280)},
                {"decode_iteration/slots": 35})
    got = reader("decode_host_ms").read(ctx_with(SimpleNamespace(obs=tr)))
    assert got == pytest.approx(2.0)


def test_kv_migrate_ms_is_the_mean_admit_span():
    tr = tracer({"admit": (3, 0.048), "admit/assemble": (3, 0.006),
                 "admit/pool_write": (3, 0.040)}, {"admit/tokens": 1500})
    got = reader("kv_migrate_ms").read(ctx_with(SimpleNamespace(obs=tr)))
    assert got == pytest.approx(16.0)


def test_policy_ms_per_event_reads_the_policys_own_time():
    # 0.5 s of policy over 1000 events; the backend's 10 s is left out
    sim = SimpleNamespace(sched_time=0.5, backend_time=10.0, n_events=1000)
    be = SimpleNamespace(sim=sim, host_s=10.0)
    got = reader("policy_ms_per_event").read(ctx_with(be))
    assert got == pytest.approx(0.5)


def test_long_suspended_share_over_the_longs_prefill_time():
    req = SimpleNamespace
    reqs = [
        # prefill 1 -> 3 s, 0.5 s of it suspended
        req(input_len=4000, prefill_start=1.0, first_token=3.0,
            suspended_s=0.5, paused_at=None, n_preemptions=1),
        # prefill from 6 s, still running at the close (10 s): 1 s closed
        # and one suspension open since 9 s
        req(input_len=9000, prefill_start=6.0, first_token=None,
            suspended_s=1.0, paused_at=9.0, n_preemptions=2),
        # queued at the close: not counted
        req(input_len=3000, prefill_start=None, first_token=None,
            suspended_s=0.0, paused_at=None, n_preemptions=0),
        # a short: not counted
        req(input_len=500, prefill_start=0.0, first_token=0.2,
            suspended_s=0.0, paused_at=None, n_preemptions=0)]
    got = reader("long_suspended_share").read(ctx_with(None, reqs))
    assert got == pytest.approx(100.0 * (0.5 + 2.0) / (2.0 + 4.0))


def test_tpot_own_p50_s_uses_each_requests_last_token():
    req = SimpleNamespace
    reqs = [req(rid=0, finish=5.0, first_token=1.0, output_len=5),
            req(rid=1, finish=5.0, first_token=1.0, output_len=3),
            req(rid=2, finish=4.0, first_token=4.0, output_len=1),
            req(rid=3, finish=None, first_token=2.0, output_len=9),
            req(rid=4, finish=8.0, first_token=2.0, output_len=4)]
    times = {0: [0.9, 2.0, 2.5, 2.8, 3.0], 1: [0.9, 3.0, 5.0], 2: [4.0],
             3: [1.5, 3.0], 4: [1.9, 3.0, 4.0, 5.0]}
    got = reader("tpot_own_p50_s").read(
        ctx_with(SimpleNamespace(token_times=times), reqs))
    # (3 - 1) / 4 = 0.5, (5 - 1) / 2 = 2.0, (5 - 2) / 3 = 1.0
    assert got == pytest.approx(1.0)


@pytest.mark.parametrize("name", READERS)
def test_nothing_is_read_from_a_program_without_it(name):
    """A program without the spans, counters and times reads nothing and
    raises nothing (the parent of the change that added them)."""
    req = SimpleNamespace(rid=0, input_len=4000, prefill_start=1.0,
                          first_token=2.0, finish=3.0, output_len=3)
    be = SimpleNamespace(sim=SimpleNamespace(sched_time=1.0, n_events=10),
                         stats={}, sp_timings={})
    assert reader(name).read(ctx_with(be, [req])) is None


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(tmp_path_factory.mktemp("bench_obs"))


def test_traced_tiny_run_prints_all_six(root):
    sys.path.insert(0, str(root / "bench"))
    run = load_module(root / "bench" / "run.py")
    res = run.run(Cell(bench_tiny.CELL, root), 2**31 + 5, 60.0, True,
                  require_chip=False)
    assert res["correct"] is True, res["checks"]
    got = {k: res["metrics"][k]["value"] for k in READERS}
    assert all(v >= 0 for v in got.values()), got
    assert got["prefill_host_ms"] > 0 and got["decode_host_ms"] > 0
    assert got["kv_migrate_ms"] > 0 and got["policy_ms_per_event"] > 0
    assert got["tpot_own_p50_s"] > 0
