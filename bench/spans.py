"""The benchmark's own spans around calls into the program's layers.

A span records its wall time on the host clock, and its self time: the
duration less what nested spans covered.  In a traced run each span is
also written as a ``jax.profiler.TraceAnnotation``, so the device trace can
say what the host was doing while the device sat idle.

Span names and where the harness puts them:

    instant  one simulated instant of the event loop (top level)
    route    ``ClusterRun.route`` (the loop's arrival hook): dispatch
    stage    the loop's ``prepare_batch``/``prepare_complete`` hooks:
             cross-node staging
    decide   ``EcoSched.on_event`` and ``EcoSched.propose_resizes``
    kernel   each call into a ``score_reduce*`` entry point
"""
from __future__ import annotations

import time
from collections import defaultdict
from typing import Callable, Dict, Optional, Set, Tuple


class Spans:
    def __init__(self, annotate: bool = False):
        self.self_s: Dict[str, float] = defaultdict(float)
        self.total_s: Dict[str, float] = defaultdict(float)
        self.calls: Dict[str, int] = defaultdict(int)
        self.edges: Set[Tuple[Optional[str], str]] = set()
        self._stack: list = []  # [name, child seconds]
        self._annotation = None
        if annotate:
            from jax.profiler import TraceAnnotation

            self._annotation = TraceAnnotation

    def wrap(self, name: str, fn: Callable) -> Callable:
        stack, clock = self._stack, time.perf_counter

        def call(*args, **kw):
            ann = self._annotation(name) if self._annotation else None
            if ann is not None:
                ann.__enter__()
            parent = stack[-1][0] if stack else None
            stack.append([name, 0.0])
            t0 = clock()
            try:
                return fn(*args, **kw)
            finally:
                dur = clock() - t0
                _, child = stack.pop()
                self.self_s[name] += dur - child
                self.total_s[name] += dur
                self.calls[name] += 1
                self.edges.add((parent, name))
                if stack:
                    stack[-1][1] += dur
                if ann is not None:
                    ann.__exit__(None, None, None)

        return call
