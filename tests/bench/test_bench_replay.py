"""Each cell's replay loop in-process on the CPU, at a few dozen jobs: the
events and instants the harness counts are those of the schedule, the
spans nest as the self-time definitions assume, and the check passes."""
import os

import pytest

from bench import audit as A
from bench import harness as H
from bench import report
from bench import traffic as T
from bench.reference import Reference
from bench.spans import Spans

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SEED = 3_000_000_019
# parent -> child span pairs that the self-time readers assume
NESTING = {(None, "instant"), ("instant", "route"), ("instant", "stage"),
           ("instant", "decide"), ("stage", "kernel"), ("decide", "kernel")}


def small(cell, nodes=24, jobs=60):
    c = H.load_cell(cell, ROOT)
    c["config"]["nodes"] = nodes
    c["traffic"]["arrivals"]["jobs"] = jobs
    return c


@pytest.mark.parametrize("cell", ["fleet256.elastic_burst", "dvfs512.burst",
                                  "fleet256.poisson"])
def test_counts_match_schedule_and_spans_nest(cell, monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    c = small(cell)
    arrivals = T.stream(c["traffic"], c["apps"], SEED)
    spans = Spans()
    rec = A.Recorder()
    with A.patched_reductions(rec, spans):
        out = H.replay(c, arrivals, spans=spans, recorder=rec)
    assert out["complete"]
    ref = Reference(T.nodes(c["config"]), c["profiles"],
                    c["config"]["scheduler"], c["traffic"]["elastic"])
    records, energy, _ = ref.run(arrivals)
    # one event per arrival routed, per launch and per segment completion
    assert out["events"] == len(arrivals) + 2 * len(records)
    assert len(out["launches"]) == len(records)
    assert len(out["instants"]) == len(ref.instants)
    assert out["records"] == sorted(records)
    assert out["energy"] == energy
    assert spans.edges <= NESTING
    assert ("decide", "kernel") in spans.edges or ("stage", "kernel") in spans.edges
    assert spans.calls["instant"] == len(out["instants"])
    assert sum(rec.launches.values()) > 0
    wrong, gap = rec.audit()
    assert wrong == 0 and gap < H.SCORE_GAP_LIMIT
    # every decision that launched the kernel is one the reference took
    assert out["decisions"]
    for node, t, launches, idx in out["decisions"]:
        key = tuple(sorted((l.job, l.g, l.f) for l in launches))
        _, _, scores, best = rec.requests[idx]
        assert abs(float(scores[best]) - ref.decision_scores[(node, t, key)]) \
            < H.DECISION_GAP_LIMIT
    for name, self_s in spans.self_s.items():
        assert 0.0 <= self_s <= spans.total_s[name]


def test_window_cuts_the_last_replay_and_reports(monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    c = small("fleet256.elastic_burst", jobs=120)
    res = H.run_cell(c, SEED, 1.5, trace=True)
    win = res["window"]
    assert len(win["replays"]) >= 2 and not win["replays"][-1]["complete"]
    assert all(r["complete"] for r in win["replays"][:-1])
    assert res["window_compiles"]["compiles"] == 0
    device = {"platform": "cpu", "kind": "cpu", "count": 1, "memory_peak_bytes": 0}
    line, lines = report.result(c, res, device, None)
    assert line["correct"] is True
    assert list(line)[-1] == "checks"
    assert lines[-1].startswith("check ")
    assert set(line["metrics"]) >= {"dispatch_us_per_event", "stage_us_per_instant",
                                    "decision_us_per_event", "launches_per_event",
                                    "launch_us", "instant_p95_ms"}
    res0 = H.run_cell(c, SEED, 0.5, trace=False)
    line0, _ = report.result(c, res0, device, None)
    assert set(line0["metrics"]) == {"events_per_s", "setup_s"}
