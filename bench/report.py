"""Turns one run's window into the result line and the lines before it."""
from __future__ import annotations

from typing import List, Optional, Tuple

from bench import harness as H
from bench import program_spans as P
from bench import roofline as R


def program_window(before: dict, after: dict) -> Tuple[dict, dict]:
    """What the program's tracer made between two ``obs.snapshot()``
    readings: spans (name -> ``self_s``, ``total_s``, ``calls``) that ended
    in between, and counters (name -> count) that moved."""
    spans, counts = P.since(before, after)
    return ({n: s for n, s in spans.items() if s["calls"]},
            {n: k for n, k in counts.items() if k})


def context(cell: dict, res: dict, device: dict, trace: Optional[dict]) -> dict:
    """What the metric readers read: the window's counts, the benchmark's
    spans, the program's spans and counters over the window, the profiler
    trace's reduction and the kernels' least work."""
    ctx = H.summarize(cell, res)
    ctx["setup_s"] = res["setup_s"]
    spans = res["spans"]
    ctx["span_self_s"] = dict(spans.self_s) if spans is not None else {}
    ctx["span_calls"] = dict(spans.calls) if spans is not None else {}
    ctx["program_spans"], ctx["program_counts"] = program_window(*res["program"])
    ctx["trace"] = trace
    ctx["kernel_least_s"] = None
    if trace is not None:
        peak = R.peaks(device["kind"])
        ctx["kernel_least_s"] = sum(
            R.least_seconds(*R.request_work(req), peak)
            for _, req, _, _ in res["recorder"].requests)
    return ctx


def result(cell: dict, res: dict, device: dict,
           trace: Optional[dict]) -> Tuple[dict, List[str]]:
    ctx = context(cell, res, device, trace)
    lines = [
        f"bench: window_s={ctx['seconds']} replays={ctx['replays']} "
        f"complete={ctx['replays_complete']} events={ctx['events']} "
        f"instants={ctx['instants']}",
        "bench: launches per reduction: "
        + " ".join(f"{k}={v}" for k, v in ctx["launches"].items()),
        f"bench: set-up compiles={res['warm_compiles']['compiles']} "
        f"cache_hits={res['warm_compiles']['cache_hits']}; "
        f"compiles inside the window={res['window_compiles']['compiles']} "
        f"(cache_hits={res['window_compiles']['cache_hits']})",
        f"bench: python_fallbacks={ctx['python_fallbacks']}",
        "bench: set-up phases: " + " ".join(
            f"{k}={v}" for k, v in res["setup_phases"].items()),
    ]
    if res["window"]["error"]:
        lines.append(f"bench: program error: {res['window']['error']}")
    if res["spans"] is not None:
        lines.append(f"bench: traced events_per_s={ctx['events_per_s']} "
                     "(compare with an untraced run for the tracing overhead)")
        lines.append("bench: span self seconds: " + " ".join(
            f"{k}={v}" for k, v in sorted(ctx["span_self_s"].items())))
    metrics_of = (cell["per_layer"] if res["spans"] is not None
                  else cell["end_to_end"])
    metrics = {}
    for m in metrics_of:
        v = H.reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    checks = H.check(cell, res["window"], res["recorder"])
    compared = checks.pop("_compared")
    lines.append(f"bench: records and launches compared={compared}; "
                 f"decisions scored against the reference={checks.pop('_decisions')}")
    correct = H.passed(checks)
    if trace is not None:
        device = dict(device, busy_s=trace["busy_s"], window_s=trace["window_s"])
        lines.append(f"bench: trace busy_s={trace['busy_s']} "
                     f"window_s={trace['window_s']} "
                     f"kernel_device_s={trace['kernel_device_s']} "
                     f"kernel_least_s={ctx['kernel_least_s']}")
        for label, secs in trace["idle_by_host_span"]:
            lines.append(f"bench: device idle while host in {label}: {secs} s")
    line = {"correct": correct, "attempted": compared,
            "failed": checks["record_mismatches"]["value"],
            "metrics": metrics, "device": device}
    if trace is not None:
        line["breakdown"] = trace["breakdown"]
    line["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                      for k, c in checks.items()}
    lines.append(f"bench: correct={correct}")
    for k, c in checks.items():
        rel = ">=" if c.get("at_least") else "<="
        lines.append(f"check {k}: {c['value']} (limit {rel} {c['limit']})")
    return line, lines
