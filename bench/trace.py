"""Reduction of one profiler trace to the benchmark's device numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` wrote with nothing but JAX's
own ``ProfileData``.  The traced window is the host span named ``window``
that the harness writes around it.  On each TPU device plane
(``/device:TPU:<n>``):

* busy time is the union of the intervals of the ``XLA Ops`` and
  ``XLA Modules`` lines' events inside the window (a program that runs
  holds the device between its ops too), averaged over the device planes;
* kernel time is the summed duration of the ``XLA Modules`` events of the
  three jitted score reductions (module names ``jit__reduce_jit``,
  ``jit__reduce_batch_jit``, ``jit__reduce_multi_jit``), inside the window;
* idle time (window time no op covers) is put down, piece by piece, to
  the innermost benchmark span the host was in.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_NAME = re.compile(r"^%?([A-Za-z_][\w-]*?)(\.\d+)?( =|$)")
REDUCTION_MODULE = re.compile(r"^jit__reduce(_batch|_multi)?_jit\b")
HOST_SPANS = frozenset(("window", "instant", "route", "stage", "decide", "kernel"))

Interval = Tuple[int, int]


def load(trace_dir: str):
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(files[-1])


def op_kind(name: str) -> str:
    """``%fusion.2 = (f32[1,1]...) fusion(...)`` -> ``fusion``: an XLA op's
    HLO text without its instance number and shapes."""
    m = OP_NAME.match(name)
    return m.group(1) if m else name[:40]


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def reduce(trace_dir: str) -> dict:
    """busy_s, window_s, kernel_device_s, idle seconds by host span, and the
    ``breakdown`` of the result line."""
    data = load(trace_dir)
    host: List[Tuple[int, int, str]] = []
    devices = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in HOST_SPANS:
                    s = int(ev.start_ns)
                    host.append((s, s + int(ev.duration_ns), ev.name))
    windows = [(s, e) for s, e, n in host if n == "window"]
    if not windows or not devices:
        raise ValueError(f"trace has windows={len(windows)} devices={len(devices)}")
    lo, hi = windows[0]
    busy_total = 0
    kernel_ns = 0
    ops_ns: Dict[str, int] = defaultdict(int)
    busy_0: List[Interval] = []
    for k, plane in enumerate(devices):
        ops: List[Interval] = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                for ev in line.events:
                    s = int(ev.start_ns)
                    e = s + int(ev.duration_ns)
                    if e > lo and s < hi:
                        ops.append((s, e))
                        ops_ns[op_kind(ev.name)] += min(e, hi) - max(s, lo)
            elif line.name == "XLA Modules":
                for ev in line.events:
                    s = int(ev.start_ns)
                    e = s + int(ev.duration_ns)
                    if e > lo and s < hi:
                        ops.append((s, e))
                        if REDUCTION_MODULE.match(ev.name):
                            kernel_ns += min(e, hi) - max(s, lo)
        busy = clip(union(ops), lo, hi)
        busy_total += sum(e - s for s, e in busy)
        if k == 0:
            busy_0 = busy
    n = len(devices)
    idle = idle_by_span(busy_0, lo, hi, host)
    by_label: Dict[str, int] = defaultdict(int)
    for label, ns in idle:
        by_label[label] += ns
    ranked = sorted(by_label.items(), key=lambda kv: -kv[1])
    top_ops = sorted(ops_ns.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy_total / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "kernel_device_s": kernel_ns / n / 1e9,
        "devices": n,
        "idle_by_host_span": [[k, v / 1e9] for k, v in ranked],
        "breakdown": {
            "device_ops": [[k, v / n / 1e9] for k, v in top_ops],
            "idle_gaps": [[k, v / 1e9] for k, v in ranked[:10]],
        },
    }


def innermost(host: List[Tuple[int, int, str]]) -> List[Tuple[int, int, str]]:
    """Split nested host spans into disjoint pieces, each labelled with the
    innermost span that covers it."""
    out: List[Tuple[int, int, str]] = []
    stack: List[Tuple[int, str]] = []
    t = 0
    for s, e, name in sorted(host, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, label = stack.pop()
            if end > t:
                out.append((t, end, label))
            t = max(t, end)
        if stack and s > t:
            out.append((t, s, stack[-1][1]))
        stack.append((e, name))
        t = s
    while stack:
        end, label = stack.pop()
        if end > t:
            out.append((t, end, label))
        t = max(t, end)
    return out


def idle_by_span(busy: List[Interval], lo: int, hi: int,
                 host: List[Tuple[int, int, str]]) -> List[Tuple[str, int]]:
    """The idle time of ``[lo, hi)`` (what ``busy`` leaves), piece by piece,
    each with the innermost host span the host was in."""
    gaps: List[Interval] = []
    t = lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    pieces = innermost(host)
    out: List[Tuple[str, int]] = []
    j = 0
    for a, b in gaps:
        covered = 0
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            s, e, label = pieces[k]
            n = min(e, b) - max(s, a)
            if n > 0:
                out.append((label, n))
                covered += n
            k += 1
        if b - a > covered:
            out.append(("outside any span", b - a - covered))
    return out
