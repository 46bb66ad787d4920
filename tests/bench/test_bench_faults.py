"""The fault plane through the benchmark, on the CPU at 12 nodes.

* The harness's ``replay`` against the reference (``bench/reference.py``)
  on the same stream, with a traffic file's fault section: bit-identical
  records and total energy, and every kernel decision's score within the
  check's limit.  Each case names the fault events it must show, and the
  cases together show every event the reference counts.
* A faulted replay ends where the program's own ``EventLoop.run`` stops.
* Planted faults in a copy of the reference (no rollback on a kill, Eq.
  (1)'s ``M`` over every unit, a retry delay without its cap) make a run
  not correct; the same runs with the reference as it is are correct.
* Without a fault section nothing changes: ``open_run`` gets
  ``faults=None``, and the two transitions added to the events counted
  never come.
* Metric readers see the program's tracer over the window alone.
"""
import ast
import dataclasses
import importlib.util
import json
import os

import pytest

from bench import audit as A
from bench import fleet as F
from bench import harness as H
from bench import report
from bench import traffic as T
from bench.reference import Reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
SEED = 3_000_000_019
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1, "memory_peak_bytes": 0}
CELLS = ["fleet256.elastic_burst", "dvfs512.burst", "fleet256.poisson"]
HEAVY = {"seed": 11, "node_mtbf_s": 1500, "node_mttr_s": 400, "degrade_frac": 0.5,
         "degrade_units": 4, "job_mtbf_s": 600, "straggler_prob": 0.3}
with open(os.path.join(BENCH, "traffic", "elastic_burst.json")) as fh:
    RESIZE = json.load(fh)["elastic"]

# name -> (base cell, elastic settings, fault section, jobs, rate, the
# reference's fault counts that must read above 0)
CASES = {
    "node_full": ("fleet256.poisson", None,
                  {"seed": 11, "node_mtbf_s": 2000, "node_mttr_s": 500},
                  150, 0.5, ("node_failures", "kills", "retries", "retry_on_dead")),
    "node_partial": ("fleet256.poisson", None,
                     {"seed": 11, "node_mtbf_s": 2000, "node_mttr_s": 500,
                      "degrade_frac": 1.0, "degrade_units": 4},
                     150, 0.5, ("partial_failures", "kills")),
    "crash": ("fleet256.poisson", None, {"seed": 11, "job_mtbf_s": 300},
              150, 0.5, ("crashes", "retries")),
    "straggler": ("fleet256.poisson", None, {"seed": 11, "straggler_prob": 0.5},
                  150, 0.5, ("stragglers",)),
    "lost": ("fleet256.poisson", None,
             {"seed": 5, "job_mtbf_s": 200, "max_retries": 1},
             150, 0.5, ("crashes", "lost")),
    "held_at_the_edge": ("fleet256.poisson", None,
                         {"seed": 7, "node_mtbf_s": 100, "node_mttr_s": 3000,
                          "degrade_frac": 1.0, "degrade_units": 4},
                         120, 0.1, ("held", "partial_failures")),
    "resize_on": ("fleet256.elastic_burst", RESIZE, HEAVY, 150, 0.5,
                  ("crashes", "node_failures", "stragglers", "lost")),
    "ckpt_write_killed": ("fleet256.elastic_burst", {**RESIZE, "ckpt_time": 300.0},
                          {"seed": 19, "node_mtbf_s": 1500, "node_mttr_s": 300,
                           "degrade_frac": 0.5, "degrade_units": 4,
                           "straggler_prob": 0.3},
                          150, 0.5, ("ckpt_kills", "partial_failures")),
    "elastic_restart": ("fleet256.poisson",
                        {**RESIZE, "resize": False, "restart_time": 40.0},
                        {"seed": 11, "job_mtbf_s": 300}, 150, 0.5, ("crashes",)),
    "dvfs": ("dvfs512.burst", None, HEAVY, 150, 0.3,
             ("node_failures", "crashes", "stragglers", "retry_on_dead")),
    "mixed": ("fleet256.poisson", None, {**HEAVY, "seed": 13}, 200, 1.0,
              ("node_failures", "partial_failures", "crashes", "stragglers",
               "retry_on_dead")),
    "capped_retries": ("fleet256.poisson", None,
                       {"seed": 5, "job_mtbf_s": 200, "max_retries": 6,
                        "retry_cap_s": 60.0}, 150, 0.5, ("crashes", "retries")),
}


def fault_cell(name):
    """A 12-node fleet of the base cell's configuration, 8 (or 4) GPUs a
    node in pods of 4, under the case's traffic."""
    base, elastic, faults, jobs, rate, _ = CASES[name]
    c = H.load_cell(base, ROOT)
    c["config"].update(nodes=12, pod_size=4, pods_per_region=2)
    c["traffic"]["arrivals"].update(jobs=jobs, rate=rate)
    c["traffic"]["elastic"] = elastic
    c["traffic"]["faults"] = faults
    c["profiles"] = T.profiles(c["config"], c["traffic"])
    c["apps"] = T.app_names(c["config"], c["traffic"])
    return c


def reference(c):
    return Reference(T.nodes(c["config"]), c["profiles"], c["config"]["scheduler"],
                     c["traffic"]["elastic"], T.faults(c["traffic"]))


def spy_runs(monkeypatch):
    """Every ``ClusterRun`` the harness opens, with the keywords it was
    opened with."""
    opened = []
    build = F.cluster

    def cluster(config, profiles):
        c = build(config, profiles)
        open_run = c.open_run

        def spied(**kw):
            run = open_run(**kw)
            opened.append((kw, run))
            return run

        c.open_run = spied
        return c

    monkeypatch.setattr(F, "cluster", cluster)
    return opened


def test_cases_cover_every_fault_event():
    c = fault_cell("crash")
    ref = reference(c)
    ref.run(T.stream(c["traffic"], c["apps"], SEED)[:5])
    shown = {need for *_, needs in CASES.values() for need in needs}
    assert shown == set(ref.counts)
    resized = {name for name, case in CASES.items()
               if case[1] is not None and case[1]["resize"]}
    assert resized and resized != set(CASES)  # resizing on and off


@pytest.mark.parametrize("case", sorted(CASES))
def test_replay_matches_reference_under_faults(case, monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    c = fault_cell(case)
    arrivals = T.stream(c["traffic"], c["apps"], SEED)
    rec = A.Recorder()
    with A.patched_reductions(rec):
        out = H.replay(c, arrivals, recorder=rec)
    assert out["complete"]
    ref = reference(c)
    records, energy, launches = ref.run(arrivals)
    for need in CASES[case][-1]:
        assert ref.counts[need] > 0, (need, ref.counts)
    assert out["records"] == sorted(records)
    assert out["energy"] == energy
    assert len(out["launches"]) == len(launches)
    assert len(out["instants"]) == len(ref.instants)
    # arrivals routed, launches, segment ends (done, ckpt or fail), retries
    assert out["events"] == len(arrivals) + 2 * len(records) + ref.counts["retries"]
    assert out["decisions"]
    for node, t, made, idx in out["decisions"]:
        key = tuple(sorted((l.job, l.g, l.f) for l in made))
        _, _, scores, best = rec.requests[idx]
        gap = abs(float(scores[best]) - ref.decision_scores[(node, t, key)])
        assert gap <= H.DECISION_GAP_LIMIT
    wrong, gap = rec.audit()
    assert wrong == 0 and gap < H.SCORE_GAP_LIMIT


def test_faulted_replay_ends_under_the_idle_rule(monkeypatch):
    from repro.core.arrivals import Arrival
    from repro.core.events import EVT_ARRIVAL, EVT_NODE_FAIL, EVT_NODE_RECOVER

    monkeypatch.setenv("REPRO_KERNELS", "ref")
    c = fault_cell("node_full")
    arrivals = T.stream(c["traffic"], c["apps"], SEED)
    opened = spy_runs(monkeypatch)
    out = H.replay(c, arrivals)
    assert out["complete"]
    (kw, run), = opened
    loop = run.loop
    assert loop.idle() and loop.queue.work == 0 and len(loop.queue) > 0
    assert {kind for _, kind, _, _ in loop.queue._heap} <= {EVT_NODE_FAIL,
                                                           EVT_NODE_RECOVER}
    # the program's own batch loop stops at the same place
    run = F.cluster(c["config"], c["profiles"]).open_run(
        apps=c["apps"], jobs=[(n, a) for _, n, a in arrivals],
        faults=F.fault_config(c["traffic"]))
    for t, n, a in arrivals:
        run.loop.queue.push(t, EVT_ARRIVAL, Arrival(t=t, name=n, app=a))
    run.loop.run()
    res = run.finalize()
    assert sorted((r.job, r.node, r.g, r.f, r.start, r.end, r.kind, r.segment)
                  for r in res.records) == out["records"]
    assert res.total_energy == out["energy"]


# -- controls: a fault planted in a copy of the reference ---------------------

# name -> (case it shows in, [(the reference's text, the planted text)])
PLANTED = {
    "no_rollback": ("crash", [(
        "node.progress[seg.job] = seg.frac0",
        "node.progress[seg.job] = seg.frac_at(t)")]),
    "m_all_units": ("node_partial", [(
        "free_units, node.alive())", "free_units, node.units)")]),
    "retry_uncapped": ("capped_retries", [(
        'return min(cfg["retry_base_s"] * cfg["retry_mult"] ** k,\n'
        '                   cfg["retry_cap_s"])',
        'return cfg["retry_base_s"] * cfg["retry_mult"] ** k')]),
}


def planted(name, tmp_path):
    """A copy of ``bench/reference.py`` with the fault ``name`` planted."""
    with open(os.path.join(BENCH, "reference.py")) as fh:
        src = fh.read()
    for old, new in PLANTED[name][1]:
        assert old in src, old
        src = src.replace(old, new)
    path = tmp_path / f"reference_{name}.py"
    path.write_text(src)
    spec = importlib.util.spec_from_file_location(f"reference_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_line(c):
    res = H.run_cell(c, SEED, 1.0, trace=False)
    line, _ = report.result(c, res, DEVICE, None)
    return line


@pytest.mark.parametrize("name", sorted(PLANTED))
def test_planted_reference_fault_is_not_correct(name, tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    c = fault_cell(PLANTED[name][0])
    c["shapes"] = {"warm_jobs": 20}
    line = run_line(c)
    assert line["correct"] is True, line["checks"]
    assert line["checks"]["complete_replays"]["value"] >= 1
    monkeypatch.setattr(H, "Reference", planted(name, tmp_path).Reference)
    line = run_line(c)
    assert line["correct"] is False
    checks = line["checks"]
    assert (checks["record_mismatches"]["value"] > 0
            or checks["energy_rel_gap"]["value"] > 0
            or checks["decision_score_gap"]["value"] > H.DECISION_GAP_LIMIT)


# -- without a fault section --------------------------------------------------


def small(cell, nodes=24, jobs=60):
    c = H.load_cell(cell, ROOT)
    c["config"]["nodes"] = nodes
    c["traffic"]["arrivals"]["jobs"] = jobs
    return c


@pytest.mark.parametrize("cell", CELLS)
def test_cell_without_faults_opens_with_none(cell, monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    c = small(cell, jobs=20)
    assert "faults" not in c["traffic"] and T.faults(c["traffic"]) is None
    opened = spy_runs(monkeypatch)
    out = H.replay(c, T.stream(c["traffic"], c["apps"], SEED))
    assert out["complete"]
    (kw, run), = opened
    assert "faults" in kw and kw["faults"] is None
    assert run.loop.faults is None


@pytest.mark.parametrize("cell", CELLS)
def test_events_counted_without_faults_are_unchanged(cell, monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    c = small(cell)
    arrivals = T.stream(c["traffic"], c["apps"], SEED)
    now = H.replay(c, arrivals)
    monkeypatch.setattr(H, "COUNTED", frozenset(("queued", "launch", "done", "ckpt")))
    before = H.replay(c, arrivals)
    assert now["complete"] and before["complete"]
    assert now["events"] == before["events"] > 0
    assert len(now["instants"]) == len(before["instants"])
    assert now["records"] == before["records"]
    assert now["energy"] == before["energy"]


def test_fault_section_is_completed_from_the_programs_defaults():
    from repro.core import FaultConfig

    assert T.FAULT_DEFAULTS == dataclasses.asdict(FaultConfig())
    got = T.faults({"faults": {"seed": 4, "job_mtbf_s": 10.0}})
    assert got == {**T.FAULT_DEFAULTS, "seed": 4, "job_mtbf_s": 10.0}
    assert F.fault_config({"faults": {"seed": 4}}) == FaultConfig(seed=4)
    assert F.fault_config({"faults": None}) is None
    with pytest.raises(ValueError):
        T.faults({"faults": {"mtbf": 1.0}})


def test_reference_refuses_migration():
    c = fault_cell("resize_on")
    with pytest.raises(ValueError):
        Reference(T.nodes(c["config"]), c["profiles"], c["config"]["scheduler"],
                  {**RESIZE, "migrate": True}, T.faults(c["traffic"]))


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference.py")) as fh:
        tree = ast.parse(fh.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add(node.module or "")
    assert names <= {"__future__", "hashlib", "heapq", "itertools", "math",
                     "random", "typing"}, names


# -- the program's tracer, as metric readers see it ------------------------------


def test_readers_see_the_program_tracer_over_the_window_alone(monkeypatch):
    from repro import obs

    monkeypatch.setenv("REPRO_KERNELS", "ref")
    c = small("fleet256.poisson")
    warmup, window = H.warmup, H.window

    def counted_warmup(*a, **kw):
        obs.count("bench_test.warm_up")
        return warmup(*a, **kw)

    def counted_window(*a, **kw):
        obs.count("bench_test.window")
        return window(*a, **kw)

    monkeypatch.setattr(H, "warmup", counted_warmup)
    monkeypatch.setattr(H, "window", counted_window)
    res = H.run_cell(c, SEED, 0.3, trace=False)
    ctx = report.context(c, res, DEVICE, None)
    counts, spans = ctx["program_counts"], ctx["program_spans"]
    assert "bench_test.warm_up" not in counts
    assert counts["bench_test.window"] == 1
    launches = sum(v for k, v in counts.items() if k.startswith("kernel.launches."))
    assert launches == sum(res["recorder"].launches.values()) > 0
    assert spans["kernel.fetch"]["calls"] == launches
    assert spans["sched.route"]["calls"] > 0
    for s in spans.values():  # differences of seconds: a nanosecond of rounding
        assert -1e-9 <= s["self_s"] <= s["total_s"] + 1e-9 and s["calls"] > 0
