"""Spans and counters of the served path.

`span(name, **args)` times a block of host code.  It enters a
`jax.profiler.TraceAnnotation`, so under a profiler session the span lands
on the trace's host plane, on the profiler's clock beside the device lines,
with `args` (a request id, say) as stats of the event; without a session
the annotation does nothing.  It also adds to per-name aggregates: the
count, the total seconds and the self seconds (total less the time its
child spans cover).  `count(name, n)` adds to a counter.

Names nest with `/`: a child is named after the span it sits in
(`decode_iteration/program` inside `decode_iteration`), so a reader that
filters spans by prefix keeps the children with their parent.

No span is kept in memory one by one: the profiler's trace is the record
of each span, the aggregates are the operator's counters.  `reset()`
clears them (`EngineBackend.reset` does, before every run); `disable()`
turns spans into one shared null context and counting into nothing.
`count_device(name, x)` adds a number a program computed on the device
without waiting for it: the counters read it when they are read.
"""
from __future__ import annotations

import time
from collections import Counter
from contextlib import nullcontext
from typing import Dict, List

import jax
from jax.profiler import TraceAnnotation

_NULL = nullcontext()


class Agg:
    """What the spans of one name added up to."""
    __slots__ = ("n", "total_s", "self_s")

    def __init__(self):
        self.n = 0
        self.total_s = 0.0
        self.self_s = 0.0


class _Span:
    __slots__ = ("tracer", "name", "ann", "t0", "child_s")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self.tracer = tracer
        self.name = name
        self.ann = TraceAnnotation(name, **args)

    def __enter__(self):
        self.ann.__enter__()
        self.child_s = 0.0
        self.tracer._open.append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        open_ = self.tracer._open
        open_.pop()
        if open_:
            open_[-1].child_s += dt
        agg = self.tracer.spans.get(self.name)
        if agg is None:
            agg = self.tracer.spans[self.name] = Agg()
        agg.n += 1
        agg.total_s += dt
        agg.self_s += dt - self.child_s
        self.ann.__exit__(*exc)
        return False


class Tracer:
    """Per-name span aggregates and counters of one process."""

    def __init__(self):
        self.enabled = True
        self.spans: Dict[str, Agg] = {}
        self._counters: Counter = Counter()
        self._pending: List[tuple] = []
        self._open: List[_Span] = []

    @property
    def counters(self) -> Counter:
        """The counters, with every device number added so far in."""
        if self._pending:
            names = [n for n, _ in self._pending]
            values = jax.device_get([x for _, x in self._pending])
            self._pending = []
            for n, x in zip(names, values):
                self._counters[n] += x.item()
        return self._counters

    def span(self, name: str, **args):
        if not self.enabled:
            return _NULL
        return _Span(self, name, args)

    def count(self, name: str, n: float = 1) -> None:
        if self.enabled:
            self._counters[name] += n

    def count_device(self, name: str, x: jax.Array) -> None:
        """Add the device scalar `x` to counter `name` when the counters
        are next read, with no wait now."""
        if self.enabled:
            self._pending.append((name, x))

    def reset(self) -> None:
        self.spans = {}
        self._counters = Counter()
        self._pending = []

    def disable(self) -> None:
        self.enabled = False

    def mean_ms(self, name: str) -> float:
        """Mean milliseconds per span of `name` (0 if none ran)."""
        agg = self.spans.get(name)
        return 1e3 * agg.total_s / agg.n if agg and agg.n else 0.0


#: the process's tracer, as the profiler's trace is the process's; the
#: module functions below act on it
TRACER = Tracer()
span = TRACER.span
count = TRACER.count
count_device = TRACER.count_device
reset = TRACER.reset
disable = TRACER.disable
