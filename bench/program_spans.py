#!/usr/bin/env python3
"""One run of one cell as ``bench/run.py`` makes it, that also reads the
program's own tracer (``repro.obs``) over the window.

    python3 bench/program_spans.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It takes ``obs.snapshot()`` at the window's start and end.  With
``--trace 1`` the program's spans are also written into the profiler's
trace (``obs.annotate``), and the device's idle time is put down to the
innermost span, the program's included (``kernel.fetch``, ``loop.ARRIVAL``,
...).  After ``bench/run.py``'s own lines and result line it prints on
standard error, each line starting ``prog:``:

* the kernel call's split, per launch: ``kernel_pack_us``,
  ``kernel_call_us``, ``kernel_fetch_us`` (self times of the three kernel
  spans), ``h2d_arrays_per_launch``, and ``loop_us_per_event`` (self time
  of the ``loop.*`` spans per event);
* ``sched.route`` self time per event, ``sched.stage`` per instant and
  ``sched.decide`` + ``sched.resize`` per event, beside the benchmark's own
  ``route``, ``stage`` and ``decide`` spans in a traced run;
* the decision cache's hits by layer: the fleet's shared
  ``DecisionCache.stats()`` once per replay, and the ``EcoSched``
  counters summed over the nodes;
* ``prog_json:`` with all of it.
"""
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
KERNEL_SPANS = ("kernel.pack", "kernel.call", "kernel.fetch")
POLICY_COUNTERS = ("launch_hits", "frontier_hits", "stage_served",
                   "resize_stage_served", "python_fallbacks")
CACHE_LAYERS = ("table", "oracle", "decision")


def since(before: dict, after: dict):
    """(spans, counts) between two ``obs.snapshot()`` readings."""
    spans = {}
    for name, s in after["spans"].items():
        b = before["spans"].get(name)
        spans[name] = {k: v - (b[k] if b else 0) for k, v in s.items()}
    counts = {k: v - before["counts"].get(k, 0)
              for k, v in after["counts"].items()}
    return spans, counts


def readings(spans: dict, counts: dict, events: int) -> dict:
    """The kernel call's split per launch and the loop's own time per
    event; a reading with nothing to read is None."""
    launches = sum(v for k, v in counts.items()
                   if k.startswith("kernel.launches."))
    out = {}
    for name in KERNEL_SPANS:
        s = spans.get(name)
        key = name.replace(".", "_") + "_us"
        out[key] = 1e6 * s["self_s"] / launches if s and launches else None
    out["h2d_arrays_per_launch"] = (
        counts.get("kernel.h2d_arrays", 0) / launches if launches else None)
    loop = [s["self_s"] for n, s in spans.items() if n.startswith("loop.")]
    out["loop_us_per_event"] = 1e6 * sum(loop) / events if loop and events else None
    return out


def beside(spans: dict, harness_self: dict, events: int, instants: int) -> dict:
    """The program's dispatch, staging and decision self times beside the
    benchmark's own spans around the same calls (per event, per instant)."""
    def prog(*names):
        return sum(spans.get(n, {"self_s": 0.0})["self_s"] for n in names)

    out = {}
    for key, mine, theirs, per in (
            ("dispatch_us_per_event", prog("sched.route"), "route", events),
            ("stage_us_per_instant", prog("sched.stage"), "stage", instants),
            ("decision_us_per_event", prog("sched.decide", "sched.resize"),
             "decide", events)):
        if per:
            out[key] = {"program": 1e6 * mine / per,
                        "benchmark": (1e6 * harness_self[theirs] / per
                                      if theirs in harness_self else None)}
    return out


def cache_hits(run, total: dict) -> None:
    """Adds one replay's decision-cache hits by layer into ``total``."""
    policies = [sim.policy for sim in run.sims.values()]
    stats = policies[0].cache_stats() if policies else {}
    for layer in CACHE_LAYERS:
        for side in ("hits", "misses"):
            k = f"{layer}_{side}"
            total[k] = total.get(k, 0) + stats.get(k, 0)
    for k in POLICY_COUNTERS:
        total[k] = total.get(k, 0) + sum(getattr(p, k, 0) for p in policies)
    total["decisions"] = total.get("decisions", 0) + sum(
        sim.decision_events for sim in run.sims.values())


class Probe:
    """Wraps the harness's window and replays while installed: snapshots
    the tracer around the window, turns annotation on inside it when
    asked, and reads the cache counters of each replay of the window."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.result = None
        self._in_window = False
        self._run = None
        self._hits: dict = {}

    def __enter__(self):
        from bench import fleet as F
        from bench import harness as H

        self._saved = [(H, "window", H.window), (H, "replay", H.replay),
                       (F, "cluster", F.cluster)]
        H.window = self._window(H.window)
        H.replay = self._replay(H.replay)
        F.cluster = self._cluster(F.cluster)
        return self

    def __exit__(self, *exc):
        for mod, name, fn in self._saved:
            setattr(mod, name, fn)

    def _cluster(self, build):
        def cluster(config, profiles):
            c = build(config, profiles)
            open_run = c.open_run

            def opened(**kw):
                self._run = open_run(**kw)
                return self._run

            c.open_run = opened
            return c

        return cluster

    def _replay(self, replay):
        def call(*args, **kw):
            try:
                return replay(*args, **kw)
            finally:
                if self._in_window and self._run is not None:
                    cache_hits(self._run, self._hits)
                self._run = None

        return call

    def _window(self, window):
        from repro import obs

        def call(cell, seed, seconds, spans=None, recorder=None):
            self._in_window = True
            obs.annotate(self.annotate)
            before = obs.snapshot()
            try:
                win = window(cell, seed, seconds, spans, recorder)
            finally:
                after = obs.snapshot()
                obs.annotate(False)
                self._in_window = False
            prog, counts = since(before, after)
            events = sum(r["events"] for r in win["replays"])
            instants = sum(len(r["instants"]) for r in win["replays"])
            harness_self = dict(spans.self_s) if spans is not None else {}
            self.result = {
                "events": events, "instants": instants,
                "readings": readings(prog, counts, events),
                "beside": beside(prog, harness_self, events, instants),
                "cache_hits": dict(self._hits),
                "program_self_s": {n: s["self_s"] for n, s in prog.items()},
                "program_calls": {n: s["calls"] for n, s in prog.items()},
                "program_counts": counts,
            }
            return win

        return call

    def lines(self):
        r = self.result
        if r is None:
            return ["prog: no window ran"]
        out = ["prog: " + " ".join(f"{k}={v}" for k, v in r["readings"].items())]
        for k, v in r["beside"].items():
            out.append(f"prog: {k} program={v['program']} benchmark={v['benchmark']}")
        out.append("prog: cache hits: " + " ".join(
            f"{k}={v}" for k, v in r["cache_hits"].items()))
        out.append("prog_json: " + json.dumps(r))
        return out


def program_span_names() -> frozenset:
    from repro.core.events import EVENT_NAMES

    return frozenset(["sched.route", "sched.stage", "sched.decide",
                      "sched.resize", "sched.migrate", *KERNEL_SPANS,
                      *(f"loop.{k}" for k in EVENT_NAMES.values())])


def main(argv=None) -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import run
    from bench import trace as TR

    args = run.parse(argv)
    TR.HOST_SPANS = TR.HOST_SPANS | program_span_names()
    with Probe(annotate=bool(args.trace)) as probe:
        rc = run.main(argv)
    if rc == 0:
        for line in probe.lines():
            print(line, file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
