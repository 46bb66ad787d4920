"""The program's own tracer: spans and counters at each layer's boundary.

A span records its wall time on ``time.perf_counter_ns``, its self time
(the duration less what the spans nested in it covered) and its calls.
Accumulation is always on: the spans are the program's decision clocks,
which ``ClusterResult.decision_phases`` and the ``ScheduleResult``
``*_time_s`` fields report.  After ``annotate(True)`` each span is also
written as a ``jax.profiler.TraceAnnotation`` of its name, so a profiler
trace holds it on the device ops' clock; while annotation is off no
``jax.profiler`` call runs.

``snapshot()`` may be read at any moment; the difference of two measures
the stretch between them.  A span still open enters a snapshot when it
ends.  The tracer is single-threaded, like the event loop.

Spans and counters, by layer:

    simulator  loop.<KIND>     ``EventLoop.step``, one per head event
                               (``loop.ARRIVAL``, ``loop.COMPLETE``, ...)
    dispatch   sched.route     ``ClusterRun.route``
    staging    sched.stage     ``ClusterRun._prepare_batch`` and
                               ``_prepare_complete_batch``
    decision   sched.decide    ``NodeSim.invoke_policy``
               sched.resize    the resize phase of ``EventLoop._post_complete``
               sched.migrate   its migration phase
    kernel     kernel.pack     each ``score_reduce*`` entry point: padding
                               and packing the operands on the host
               kernel.call     the jitted call: argument transfer, dispatch
               kernel.fetch    the blocking read of the answer, slicing
               counters ``kernel.launches.{solo,batch,multi}``,
               ``kernel.h2d_arrays`` and ``kernel.h2d_bytes`` (the host
               arrays each call hands the device: one packed table),
               ``kernel.d2h_arrays`` (the device arrays ``kernel.fetch``
               reads back: one answer)
"""
from __future__ import annotations

import time
from typing import Dict, List

_now = time.perf_counter_ns


class _Span:
    __slots__ = ("_tracer", "_name")

    def __init__(self, tracer: "Tracer", name: str):
        self._tracer = tracer
        self._name = name

    def __enter__(self) -> None:
        self._tracer.start(self._name)

    def __exit__(self, *exc) -> None:
        self._tracer.stop()


class Tracer:
    def __init__(self):
        self._stats: Dict[str, List[int]] = {}  # name -> [self, total ns, calls]
        self._counts: Dict[str, int] = {}
        self._stack: List[list] = []  # [stats, child ns, annotation, start ns]
        self._spans: Dict[str, _Span] = {}
        self._annotation = None  # jax.profiler.TraceAnnotation while on

    def annotate(self, on: bool) -> None:
        """Write every span that starts from now on into the profiler's
        trace as well (``on``), or stop doing so."""
        if on:
            from jax.profiler import TraceAnnotation

            self._annotation = TraceAnnotation
        else:
            self._annotation = None

    def start(self, name: str) -> None:
        """Open span ``name``; every ``start`` is closed by one ``stop``."""
        stats = self._stats.get(name)
        if stats is None:
            stats = self._stats[name] = [0, 0, 0]
        ann = None
        if self._annotation is not None:
            ann = self._annotation(name)
            ann.__enter__()
        self._stack.append([stats, 0, ann, _now()])

    def stop(self) -> int:
        """Close the innermost open span; returns its duration in ns."""
        t1 = _now()
        stack = self._stack
        stats, child, ann, t0 = stack.pop()
        d = t1 - t0
        stats[0] += d - child
        stats[1] += d
        stats[2] += 1
        if stack:
            stack[-1][1] += d
        if ann is not None:
            ann.__exit__(None, None, None)
        return d

    def span(self, name: str) -> _Span:
        """``with tracer.span(name):`` -- ``start``/``stop`` around a block."""
        s = self._spans.get(name)
        if s is None:
            s = self._spans[name] = _Span(self, name)
        return s

    def count(self, name: str, k: int = 1) -> None:
        self._counts[name] = self._counts.get(name, 0) + k

    def snapshot(self) -> dict:
        """``{"spans": {name: {"self_s", "total_s", "calls"}}, "counts":
        {name: n}}`` of every span ended and every count made so far."""
        return {
            "spans": {
                name: {"self_s": s / 1e9, "total_s": t / 1e9, "calls": c}
                for name, (s, t, c) in self._stats.items()
            },
            "counts": dict(self._counts),
        }


# the process's tracer, which the program's spans and counters feed
TRACER = Tracer()
annotate = TRACER.annotate
start = TRACER.start
stop = TRACER.stop
span = TRACER.span
count = TRACER.count
snapshot = TRACER.snapshot
