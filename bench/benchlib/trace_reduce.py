"""From a profiler trace (`.xplane.pb`) to the numbers the benchmark reports.

- busy: the union of the intervals in which a compiled program or an
  operation ran on a chip, inside the traced window (the host span
  `WINDOW`), averaged over chips;
- per-program device seconds: the chip's "XLA Modules" line, one event per
  execution of a compiled program, named after its jitted function; the
  breakdown lists the programs that took most time (an operation's own
  name is an HLO instruction that changes with every compile);
- idle gaps between busy intervals, each charged to the innermost host span
  (a `TraceAnnotation` the harness wrote) that was open at the gap's middle.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

#: the host span that marks the traced window
WINDOW = "bench_window"


@dataclass
class Trace:
    window_s: float
    busy_s: float                      # mean over chips
    n_chips: int
    module_s: Dict[str, float] = field(default_factory=dict)   # chip mean
    module_n: Dict[str, int] = field(default_factory=dict)
    idle_by_span: Dict[str, float] = field(default_factory=dict)

    def program_s(self, names) -> float:
        """Device seconds of the programs whose jitted function is in
        `names`."""
        return sum(s for m, s in self.module_s.items()
                   if program_name(m) in names)

    def breakdown(self, k: int = 10) -> dict:
        by_prog: Dict[str, float] = defaultdict(float)
        for m, s in self.module_s.items():
            by_prog[program_name(m)] += s
        top = sorted(by_prog.items(), key=lambda x: -x[1])[:k]
        gaps = sorted(self.idle_by_span.items(), key=lambda x: -x[1])[:k]
        return {"device_ops": [[n, s] for n, s in top],
                "idle_gaps": [[n, s] for n, s in gaps]}


_JIT = re.compile(r"^jit_(.+?)(\(\d*\))?$")


def program_name(module: str) -> str:
    """'jit__decode(12)' -> '_decode': the jitted function's name."""
    m = _JIT.match(module)
    return m.group(1) if m else module


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _spans_at(spans: List[Tuple[float, float, str]], starts: List[float],
              t: float) -> Optional[str]:
    """Innermost (latest-starting) host span covering time t."""
    i = bisect.bisect_right(starts, t)
    best = None
    for j in range(i - 1, max(i - 64, -1), -1):
        a, b, name = spans[j]
        if a <= t < b and name != WINDOW:
            best = name
            break
    return best


def reduce_events(device: Dict[str, Dict[str, List[Tuple[float, float, str]]]],
                  host: List[Tuple[float, float, str]]) -> Trace:
    """device: chip -> line name -> [(start_s, end_s, name)];
    host: [(start_s, end_s, span name)] from the harness's spans."""
    win = [(a, b) for a, b, n in host if n == WINDOW]
    if not win:
        raise ValueError(f"trace holds no {WINDOW!r} span")
    lo, hi = win[0]
    spans = sorted((s for s in host if s[2] != WINDOW), key=lambda s: s[0])
    starts = [s[0] for s in spans]
    busy_total = 0.0
    module_s: Dict[str, float] = defaultdict(float)
    module_n: Dict[str, int] = defaultdict(int)
    idle: Dict[str, float] = defaultdict(float)
    chips = sorted(device)
    for chip in chips:
        lines = device[chip]
        ops = lines.get("XLA Ops", []) + lines.get("XLA Modules", [])
        for a, b, n in _clip_named(lines.get("XLA Modules", []), lo, hi):
            module_s[n] += (b - a) / len(chips)
            module_n[n] += 1
        busy = _union(_clip([(a, b) for a, b, _ in ops], lo, hi))
        busy_total += sum(b - a for a, b in busy)
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                label = _spans_at(spans, starts, 0.5 * (g0 + g1)) or "no span"
                idle[label] += (g1 - g0) / len(chips)
    return Trace(window_s=hi - lo, busy_s=busy_total / max(len(chips), 1),
                 n_chips=len(chips), module_s=dict(module_s),
                 module_n=dict(module_n),
                 idle_by_span=dict(idle))


def _clip_named(ev, lo, hi):
    return [(max(a, lo), min(b, hi), n) for a, b, n in ev if b > lo and a < hi]


def load(path: str, n_chips: int) -> Tuple[dict, list]:
    """Read the xplane at `path` into reduce_events' inputs, keeping the
    first `n_chips` TPU planes (the chips the cell used)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device: Dict[str, Dict[str, list]] = {}
    host: List[Tuple[float, float, str]] = []
    for plane in data.planes:
        name = plane.name
        if re.fullmatch(r"/device:TPU:\d+", name):
            if int(name.rsplit(":", 1)[1]) >= n_chips:
                continue
            device[name] = {
                line.name: [(e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9, e.name)
                            for e in line.events]
                for line in plane.lines
                if line.name in ("XLA Ops", "XLA Modules")}
        elif name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    host.append((e.start_ns * 1e-9,
                                 (e.start_ns + e.duration_ns) * 1e-9, e.name))
    return device, host


def host_spans(host: list, names) -> list:
    """Only the harness's spans (its TraceAnnotations), by name prefix."""
    keep = tuple(names)
    return [s for s in host if s[2] == WINDOW or s[2].startswith(keep)]
