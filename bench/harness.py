"""One benchmark run of one cell: set-up, the measured window, the check.

The window is a loop of replays.  A replay is the cell's whole job stream,
its submissions in an order drawn from the seed and the replay's index,
from an empty fleet to its drain, on a fresh ``Cluster`` and a fresh
``DecisionCache``, driven instant by instant through
``Cluster.open_run`` -> ``ClusterRun`` with ``EventLoop.start`` /
``peek_time`` / ``step``.  Replays run back to back until the window's
seconds have passed; the last one is cut between two instants.  Finite
replays keep the cost of an event steady: an endless stream at these rates
backs up without bound.

A traffic file with a fault section (``bench/traffic.py``) hands its
``FaultConfig`` to ``open_run``; one without hands ``faults=None``.  Under
faults the node failure and repair timeline regenerates forever, so a
replay drains where the program's own ``EventLoop.run`` stops: when
``loop.idle()`` holds (no work event pending, no job waiting) before the
next event, which may leave timeline events in the heap.  The test is
chosen once per replay; a replay without faults steps as it always did,
until the heap is empty.

An event is one arrival routed, one launch, one segment completion, one
segment killed by a fault, or one killed job handed back to a queue by a
retry, as ``ClusterRun`` reports them through its transition hook
(``queued``, ``launch``, ``done`` or ``ckpt``, ``fail``, ``retry``).
Requeues, migrations and lost jobs are not counted.  An instant is every
event at one simulated time, timed from taking its first event to
finishing its last.

Once the window has closed, every kernel request of the window is checked
against a float64 argmin, and the schedule records and total energy of
every replay against the plain reference (``bench/reference.py``) on the
same stream, faults included; a cut replay's launches must all be
launches of the reference.  Every node decision that launched the kernel
itself (not one served from a staged batch or a cache) has its answered
best score held to the reference's float64 score of the same decision.
Set-up warms up on a stream drawn from another seed.

``run_cell`` reads the program's own tracer (``repro.obs``) just before
and just after the window, outside its timed region; ``report.context``
hands metric readers what the program's spans and counters made in
between (``program_spans``, ``program_counts``).
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import time
from typing import Callable, Dict, List, Optional

from bench import audit as A
from bench import fleet as F
from bench import traffic as T
from bench.reference import Reference
from bench.spans import Spans

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
COUNTED = frozenset(("queued", "launch", "done", "ckpt", "fail", "retry"))
WARM_SEED_SALT = 0x9E3779B97F4A7C15  # the warm-up stream's seed is never the window's
# widest |kernel score - float64 score| over every feasible row of every
# request in the window; set from the readings in PERF.md ("The check")
SCORE_GAP_LIMIT = 1e-5
# widest |answered best score - the reference's float64 score| over the
# node decisions that launched the kernel; set from the same readings
DECISION_GAP_LIMIT = 1e-5


class ProgramError(Exception):
    """The program raised inside a replay; the run is not correct."""


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def load_cell(name: str, root: str = ROOT) -> dict:
    """Everything one cell needs, found by the names in ``BENCHMARK.json``:
    its configuration file, its traffic file, its cell file of shape
    buckets, and the metrics that it reports: those whose ``workloads``
    list it, or that have no such list."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, configs[w["config"]]["file"])) as fh:
        config = json.load(fh)
    traffic = T.load(w["traffic"])
    cell_file = os.path.join(BENCH, "cells", f"{name}.json")
    shapes = {}
    if os.path.exists(cell_file):
        with open(cell_file) as fh:
            shapes = json.load(fh)
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name]) and m["moves"] in reported]
    return dict(name=name, workload=w, config=config, traffic=traffic,
                shapes=shapes, end_to_end=e2e, per_layer=per_layer,
                profiles=T.profiles(config, traffic),
                apps=T.app_names(config, traffic))


def reader(metric: str) -> Callable:
    """The per-layer metric's own reader, ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def warm_seed(seed: int) -> int:
    return (seed ^ WARM_SEED_SALT) % (1 << 63)


# -- replays -------------------------------------------------------------------


def audited(node: str, on_event: Callable, recorder: A.Recorder, now: list,
            decisions: list) -> Callable:
    """``on_event`` of one node, noting each decision that launched the
    kernel: (node, time, launches, index of its last request)."""
    requests = recorder.requests

    def call(view, waiting):
        n0 = len(requests)
        launches = on_event(view, waiting)
        if launches and len(requests) > n0:
            decisions.append((node, now[0], launches, len(requests) - 1))
        return launches

    return call


def replay(cell: dict, arrivals, deadline: float = math.inf,
           spans: Optional[Spans] = None,
           recorder: Optional[A.Recorder] = None) -> dict:
    """One replay of ``arrivals`` on a fresh fleet, cut at the first instant
    boundary past ``deadline`` (host clock).  With ``recorder``, the
    decisions that launched the kernel are kept for the check."""
    from repro.core.arrivals import Arrival
    from repro.core.events import EVT_ARRIVAL

    out = {"events": 0, "launches": [], "instants": [], "complete": False,
           "decisions": []}
    now = [0.0]  # the simulated time of the instant being stepped

    def on_transition(event, t, job, node, g, end, f):
        if event in COUNTED:
            out["events"] += 1
        if event == "launch":
            out["launches"].append((job, node, g, f, t))

    run = F.cluster(cell["config"], cell["profiles"]).open_run(
        apps=cell["apps"], jobs=[(n, a) for _, n, a in arrivals],
        elastic=F.elastic_config(cell["traffic"]),
        faults=F.fault_config(cell["traffic"]), on_transition=on_transition,
    )
    loop = run.loop
    if recorder is not None:
        for name, sim in run.sims.items():
            sim.policy.on_event = audited(name, sim.policy.on_event, recorder,
                                          now, out["decisions"])
    if spans is not None:
        loop.arrive = spans.wrap("route", loop.arrive)
        loop.prepare_batch = spans.wrap("stage", loop.prepare_batch)
        loop.prepare_complete = spans.wrap("stage", loop.prepare_complete)
        for sim in run.sims.values():
            pol = sim.policy
            pol.on_event = spans.wrap("decide", pol.on_event)
            pol.propose_resizes = spans.wrap("decide", pol.propose_resizes)
    for t, n, a in arrivals:
        loop.queue.push(t, EVT_ARRIVAL, Arrival(t=t, name=n, app=a))

    clock, queue = time.perf_counter, loop.queue
    peek = queue.peek_time
    if loop.faults is not None:
        idle = loop.idle

        def peek():  # the program's stop: only the node timeline is left
            return None if idle() else queue.peek_time()

    def instant(t):
        while peek() == t:
            loop.step()

    def start():
        loop.start()

    if spans is not None:
        instant = spans.wrap("instant", instant)
        start = spans.wrap("instant", start)
    durations = out["instants"]
    try:
        t0 = clock()
        start()
        durations.append(clock() - t0)
        while True:
            t = peek()
            if t is None:
                break
            if clock() >= deadline:
                return out
            now[0] = t
            t0 = clock()
            instant(t)
            durations.append(clock() - t0)
        res = run.finalize()
    except Exception as e:  # the program failed; reported as not correct
        raise ProgramError(f"{type(e).__name__}: {e}") from e
    out["complete"] = True
    out["records"] = sorted(
        (r.job, r.node, r.g, r.f, r.start, r.end, r.kind, r.segment)
        for r in res.records)
    out["energy"] = res.total_energy
    out["python_fallbacks"] = sum(
        getattr(s.policy, "python_fallbacks", 0) for s in run.sims.values())
    return out


def window(cell: dict, seed: int, seconds: float,
           spans: Optional[Spans] = None,
           recorder: Optional[A.Recorder] = None) -> dict:
    """Replays back to back for ``seconds`` of wall time; replay ``k``
    offers the cell's submissions in the order of (``seed``, ``k``)."""
    replays: List[dict] = []
    t0 = time.perf_counter()
    deadline = t0 + seconds
    error = None
    while time.perf_counter() < deadline:
        arrivals = T.stream(cell["traffic"], cell["apps"], seed, len(replays))
        try:
            replays.append(replay(cell, arrivals, deadline, spans, recorder))
        except ProgramError as e:
            error = str(e)
            break
        replays[-1]["arrivals"] = arrivals
    elapsed = time.perf_counter() - t0
    return {"replays": replays, "seconds": elapsed, "error": error}


def shape_requests(shapes: dict):
    """Synthetic requests that land exactly on each padded shape bucket the
    cell lists: ``solo`` [b_pad, s_pad], ``batch`` [d_pad, b_pad, s_pad],
    ``multi`` [b_pad, s_pad, n_windows]."""
    import numpy as np

    def req(B, S):
        return dict(dev=np.zeros((B, S)), g=np.ones((B, S)), n=np.ones(B),
                    lam=0.35, g_free=8, M=8)

    for b, s in shapes.get("solo", []):
        yield "score_reduce", req(b, s)
    for d, b, s in shapes.get("batch", []):
        yield "score_reduce_batch", [req(b, s) for _ in range(d)]
    for b, s, w in shapes.get("multi", []):
        k = w // 2  # n_windows is the power of two above the window count
        yield "score_reduce_multi", [req(b - (k - 1), s)] + [req(1, s)] * (k - 1)


def warmup(cell: dict, seed: int, phases: Optional[dict] = None) -> None:
    """Compile every padded shape bucket the cell lists, then replay a
    prefix of the cell's traffic drawn from a seed the window never uses.
    ``phases`` receives the seconds of each part."""
    from repro.kernels import score_reduce as sr

    phases = {} if phases is None else phases
    t0 = time.perf_counter()
    for name, req in shape_requests(cell["shapes"]):
        fn = getattr(sr, name)
        if name == "score_reduce":
            fn(req["dev"], req["g"], req["n"], lam=req["lam"],
               g_free=req["g_free"], M=req["M"])
        else:
            fn(req)
    t1 = time.perf_counter()
    arrivals = T.stream(cell["traffic"], cell["apps"], warm_seed(seed))
    keep = cell["shapes"].get("warm_jobs", len(arrivals))
    replay(cell, arrivals[:keep])
    phases["buckets_s"] = t1 - t0
    phases["warm_replay_s"] = time.perf_counter() - t1


# -- the check ---------------------------------------------------------------


def check(cell: dict, win: dict, recorder: A.Recorder) -> Dict[str, dict]:
    """Every number compared, with its limit.  Runs after the window: the
    reference replays each stream the window offered."""
    record_mism = 0
    energy_gap = 0.0
    decision_gap = 0.0
    compared = decided = 0
    for r in win["replays"]:
        ref = Reference(T.nodes(cell["config"]), cell["profiles"],
                        cell["config"]["scheduler"], cell["traffic"]["elastic"],
                        T.faults(cell["traffic"]))
        ref_records, ref_energy, ref_launches = ref.run(r["arrivals"])
        ref_records = sorted(ref_records)
        ref_launch_set = set(ref_launches)
        for node, t, launches, idx in r["decisions"]:
            key = tuple(sorted((l.job, l.g, l.f) for l in launches))
            ref_score = ref.decision_scores.get((node, t, key))
            if ref_score is None:  # a decision the reference did not take
                record_mism += 1
                continue
            _, _, scores, best = recorder.requests[idx]
            decision_gap = max(decision_gap, abs(float(scores[best]) - ref_score))
            decided += 1
        if r["complete"]:
            recs = r["records"]
            record_mism += sum(1 for a, b in zip(recs, ref_records) if a != b)
            record_mism += abs(len(recs) - len(ref_records))
            compared += len(recs)
            energy_gap = max(energy_gap, abs(r["energy"] - ref_energy) / ref_energy)
        else:
            record_mism += sum(1 for l in r["launches"] if l not in ref_launch_set)
            compared += len(r["launches"])
    complete = sum(1 for r in win["replays"] if r["complete"])
    wrong_rows, score_gap = recorder.audit()
    return {
        "kernel_argmin_mismatches": {"value": wrong_rows, "limit": 0},
        "kernel_score_gap": {"value": score_gap, "limit": SCORE_GAP_LIMIT},
        "decision_score_gap": {"value": decision_gap, "limit": DECISION_GAP_LIMIT},
        "record_mismatches": {"value": record_mism, "limit": 0},
        "energy_rel_gap": {"value": energy_gap, "limit": 0.0},
        "complete_replays": {"value": complete, "limit": 1, "at_least": True},
        "program_errors": {"value": int(win["error"] is not None), "limit": 0},
        "_compared": compared,
        "_decisions": decided,
    }


def passed(checks: Dict[str, dict]) -> bool:
    for k, c in checks.items():
        if k.startswith("_"):
            continue
        v = c["value"]
        if c.get("at_least") and v < c["limit"]:
            return False
        if not c.get("at_least") and v > c["limit"]:
            return False
    return True


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             trace_dir: Optional[str] = None, replace=None,
             t_start: Optional[float] = None) -> dict:
    """Set-up and window of one run; returns the pieces of the result.
    ``t_start`` is the process's start on the host clock, from which
    ``setup_s`` counts.  ``trace`` records the benchmark's spans, and
    ``trace_dir`` also the profiler's trace.  ``replace`` swaps what runs
    under the three reduction entry points (the control, or a planted
    fault).  The result's ``program`` holds ``obs.snapshot()`` read just
    before and just after the window."""
    from bench.compile_events import CompileCounter
    from repro import obs

    t_start = time.perf_counter() if t_start is None else t_start
    compiles = CompileCounter()
    warm = A.Recorder()
    warm_error = None
    phases = {"to_warmup_s": time.perf_counter() - t_start}
    with A.patched_reductions(warm, replace=replace):
        try:
            warmup(cell, seed, phases)
        except ProgramError as e:
            warm_error = f"in warm-up: {e}"
    warm_compiles = compiles.since((0, 0))
    snap = compiles.snapshot()
    # what set-up left behind is never garbage the window has to sweep
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start

    recorder = A.Recorder()
    spans = Spans(annotate=trace_dir is not None) if trace else None
    before = obs.snapshot()
    with A.patched_reductions(recorder, spans, replace=replace):
        if warm_error is not None:
            win = {"replays": [], "seconds": 0.0, "error": warm_error}
        elif trace_dir is not None:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # the benchmark's spans, not every frame
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            try:
                from jax.profiler import TraceAnnotation

                with TraceAnnotation("window"):
                    win = window(cell, seed, seconds, spans, recorder)
            finally:
                jax.profiler.stop_trace()
        else:
            win = window(cell, seed, seconds, spans, recorder)
    program = (before, obs.snapshot())
    window_compiles = compiles.since(snap)
    result = {"setup_s": setup_s, "window": win, "recorder": recorder,
              "spans": spans, "window_compiles": window_compiles,
              "warm_compiles": warm_compiles, "setup_phases": phases,
              "program": program}
    return result


def summarize(cell: dict, res: dict) -> dict:
    """Counts the window produced, for the metrics and the earlier lines."""
    win = res["window"]
    events = sum(r["events"] for r in win["replays"])
    instants = [d for r in win["replays"] for d in r["instants"]]
    return {
        "events": events,
        "instants": len(instants),
        "instant_seconds": instants,
        "seconds": win["seconds"],
        "replays_complete": sum(1 for r in win["replays"] if r["complete"]),
        "replays": len(win["replays"]),
        "launches": dict(res["recorder"].launches),
        "python_fallbacks": sum(r.get("python_fallbacks", 0)
                                for r in win["replays"]),
        "events_per_s": events / win["seconds"] if win["seconds"] > 0 else 0.0,
    }
