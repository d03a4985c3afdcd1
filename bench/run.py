"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up makes the weights on the chip from the seed, generates the cell's
traffic from the seed, and compiles (or loads from `<checkout>/.jax_cache`)
every program the cell's prompt lengths need.  The window then serves the
traffic through `MiniCluster` (`make_policy` -> `Simulator` ->
`EngineBackend` -> `ReplicaEngine`) for `--seconds` wall seconds on the
measured virtual clock.  Afterwards the served tokens of a sample of
finished requests are compared with the plain float32 reference.

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the end-to-end metrics, or with
`--trace 1` the per-layer ones read from a profiler trace of the window),
`device`, with `--trace 1` a `breakdown`, and last `checks`: each number
compared beside its limit.  Without a TPU, or with fewer chips than the
cell asks for, it prints no result and exits non-zero.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from benchlib.spec import Cell  # noqa: E402


class NoChip(SystemExit):
    pass


def device_info(chips: int, require_chip: bool) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": min(len(devs), chips)}
    if require_chip and (info["platform"] != "tpu" or len(devs) < chips):
        raise NoChip(f"no TPU with {chips} chip(s): JAX platform "
                     f"{info['platform']!r}, {len(devs)} device(s); "
                     f"nothing was measured")
    return info


def memory_peak(chips: int) -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def enable_cache(root: Path) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (the path is part of the cache key), for every program however small;
    an explicit JAX_COMPILATION_CACHE_DIR wins."""
    import os
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        require_chip: bool = True, rate: float = 0.0, control: bool = False,
        trace_dir: str = "", before_window=None) -> dict:
    """One run of `cell`; returns the result object.  `before_window(mc)`
    may replace parts of the served path (the fault tests do)."""
    info = device_info(cell.chips, require_chip)
    import jax
    from benchlib import (check, flops, peaks, serve, stats, trace_reduce,
                          traffic)

    if info["platform"] == "tpu":
        enable_cache(cell.root)
    counter = serve.CompileCounter()
    lay, mix, conf = cell.layout, cell.mix, cell.config
    adapter = cell.adapter()
    cfg = adapter.program_config(conf)
    params = adapter.make_params(conf, seed)
    rate = rate or lay["rate_rps"]
    n = int(rate * seconds * lay["virtual_span"]) + 64
    reqs = traffic.generate(mix, rate=rate, n=n, vocab=conf["vocab_size"],
                            seed=seed)
    by_rid = {r.rid: r for r in reqs}
    mc = serve.build_cluster(cfg, params, lay)
    serve.warm(mc, traffic.used_lengths(mix),
               lay["n_engines"] + (1 if lay["policy"].startswith("pecsched")
                                   else 0))
    if before_window is not None:
        before_window(mc)
    taps = serve.Taps()
    tdir = None
    if trace:
        tdir = trace_dir or tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tdir, profiler_options=opts)
    setup_s = time.perf_counter() - T_START
    if trace:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            win = serve.run_window(mc, reqs, seconds, True, counter, taps)
        jax.profiler.stop_trace()
    else:
        win = serve.run_window(mc, reqs, seconds, False, counter, taps)
    info["memory_peak_bytes"] = memory_peak(cell.chips)

    thr = lay["long_threshold"]
    finished = [r.rid for r in win.requests if r.finish is not None]
    failed = sum(1 for r in win.requests
                 if r.shed or r.phase.value == "starved"
                 or (r.finish is not None
                     and len(win.served.get(r.rid, ())) != r.output_len))
    ctx = SimpleNamespace(
        window=win, by_rid=by_rid, long_threshold=thr, setup_s=setup_s,
        taps=taps, backend=mc.backend, dims=flops.Dims.of(conf),
        peak=peaks.peak_for(info["kind"]) if info["platform"] == "tpu"
        else None, chips=cell.chips, trace=None)
    result = {"correct": False, "attempted": len(win.requests),
              "failed": failed, "metrics": {}, "device": info}
    if trace:
        device, host = trace_reduce.load(trace_reduce.find_xplane(tdir),
                                         cell.chips)
        if not trace_dir:
            shutil.rmtree(tdir, ignore_errors=True)
        ctx.trace = trace_reduce.reduce_events(
            device, trace_reduce.host_spans(host, serve.SPAN_NAMES))
        info["busy_s"] = ctx.trace.busy_s
        info["window_s"] = ctx.trace.window_s
    for m in cell.metrics(trace):
        v = cell.reader(m["name"]).read(ctx)
        if v is not None:
            result["metrics"][m["name"]] = {"value": float(v),
                                            "unit": m["unit"]}
    if trace:
        result["breakdown"] = ctx.trace.breakdown()

    short = [r for r in win.requests if r.input_len < thr]
    print(f"window: {win.wall_s:.3f} s wall, virtual {win.v_end:.3f} s "
          f"({'deadline' if win.closed_by_deadline else 'traffic drained'});"
          f" offered {rate} req/s, arrived {len(win.requests)} "
          f"({len(short)} short), finished {len(finished)}; "
          f"{win.n_events} events; {win.measured_s:.3f} s measured chip "
          f"work; compilations inside the window: {win.compiles}; "
          f"set-up {setup_s:.3f} s; peak {info['memory_peak_bytes']} bytes",
          file=sys.stderr)
    print(f"backend stats: {dict(mc.backend.stats)}", file=sys.stderr)
    print(f"latency summary: {stats.summary(ctx)}", file=sys.stderr)

    # the reference runs on a chip the served path has let go of
    served = win.served
    del mc, ctx
    gc.collect()
    limits = lay["limits"]
    rids = check.sample(finished, {r: by_rid[r].prompt_len for r in finished},
                        served, seed, lay["sample"]["min_tokens"],
                        lay["sample"]["max_requests"])
    cmp = check.compare(cell.reference(), conf, params,
                        {r: by_rid[r].tokens for r in rids}, served, rids,
                        control=control)
    # the control's tokens stand in the served tokens' place and meet the
    # same checks
    gap = cmp["control_gap_max"] if control else cmp["served_gap_max"]
    checks = {"served_gap_max": [gap, limits["served_gap_max"]],
              "failed": [failed, 0], "window_compiles": [win.compiles, 0],
              "compared_requests": [cmp["n_requests"], 1]}
    result["correct"] = check.verdict(checks)
    if control:
        result["program_gap_max"] = cmp["served_gap_max"]
    print(f"compared {cmp['n_requests']} finished requests, "
          f"{cmp['n_tokens']} served tokens, with the float32 reference"
          + (" (float8 control in the program's place)" if control else ""),
          file=sys.stderr)
    for k, (v, lim) in checks.items():
        print(f"check {k} {v} {check.RELATION[k]} {lim}", file=sys.stderr)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="offered rate override (req/s), for a rate sweep")
    ap.add_argument("--trace-dir", default="",
                    help="keep the profiler trace in this directory")
    args = ap.parse_args(argv)
    try:
        res = run(Cell(args.workload), args.seed, args.seconds,
                  bool(args.trace), rate=args.rate, trace_dir=args.trace_dir)
    except NoChip as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 3
    sys.stdout.flush()
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
