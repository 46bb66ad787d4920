"""CPU checks of ``bench/program_spans.py``: its readings on hand-made
snapshots, idle time put down to the program's innermost spans, and one
small window read in-process with the probe installed."""
import os

import pytest

from bench import audit as A
from bench import fleet as F
from bench import harness as H
from bench import program_spans as P
from bench import trace as TR
from bench.spans import Spans

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def snap(spans, counts):
    return {"spans": {n: {"self_s": s, "total_s": t, "calls": c}
                      for n, (s, t, c) in spans.items()},
            "counts": counts}


def test_readings_on_hand_made_snapshots():
    before = snap({"kernel.pack": (1.0, 1.0, 10), "loop.ARRIVAL": (5.0, 9.0, 100)},
                  {"kernel.launches.solo": 10, "kernel.h2d_arrays": 70})
    after = snap({"kernel.pack": (1.002, 1.002, 14), "kernel.call": (0.004, 0.004, 4),
                  "kernel.fetch": (0.006, 0.006, 4), "loop.ARRIVAL": (5.5, 10.0, 150),
                  "loop.COMPLETE": (0.3, 0.4, 50)},
                 {"kernel.launches.solo": 12, "kernel.launches.multi": 2,
                  "kernel.h2d_arrays": 70 + 2 * 7 + 2 * 12})
    spans, counts = P.since(before, after)
    assert spans["kernel.pack"]["calls"] == 4
    got = P.readings(spans, counts, events=400)
    assert got["kernel_pack_us"] == pytest.approx(500.0)
    assert got["kernel_call_us"] == pytest.approx(1000.0)
    assert got["kernel_fetch_us"] == pytest.approx(1500.0)
    assert got["h2d_arrays_per_launch"] == pytest.approx(9.5)
    assert got["loop_us_per_event"] == pytest.approx(2000.0)
    # nothing launched: the kernel readings have nothing to read
    empty = P.readings({"loop.ARRIVAL": spans["loop.ARRIVAL"]}, {}, events=10)
    assert empty["kernel_call_us"] is None and empty["h2d_arrays_per_launch"] is None
    assert P.readings({}, {}, events=0)["loop_us_per_event"] is None


def test_program_spans_beside_the_benchmarks():
    spans = {"sched.route": {"self_s": 0.02}, "sched.decide": {"self_s": 0.01},
             "sched.resize": {"self_s": 0.005}}
    got = P.beside(spans, {"route": 0.021, "decide": 0.016}, events=1000,
                   instants=100)
    assert got["dispatch_us_per_event"] == pytest.approx(
        {"program": 20.0, "benchmark": 21.0})
    assert got["decision_us_per_event"] == pytest.approx(
        {"program": 15.0, "benchmark": 16.0})
    assert got["stage_us_per_instant"] == {"program": 0.0, "benchmark": None}


def test_idle_goes_to_the_innermost_program_span():
    host = [(0, 100, "window"), (10, 90, "instant"), (12, 88, "loop.ARRIVAL"),
            (20, 80, "kernel"), (21, 30, "kernel.pack"), (30, 40, "kernel.call"),
            (40, 79, "kernel.fetch")]
    assert all(n in TR.HOST_SPANS | P.program_span_names() for _, _, n in host)
    busy = [(41, 45)]  # the device works while the host waits in the fetch
    gaps = {}
    for label, ns in TR.idle_by_span(busy, 0, 100, host):
        gaps[label] = gaps.get(label, 0) + ns
    assert gaps["kernel.pack"] == 9 and gaps["kernel.call"] == 10
    assert gaps["kernel.fetch"] == 39 - 4
    assert gaps["kernel"] == 2 and gaps["loop.ARRIVAL"] == 16
    assert gaps["instant"] == 4 and gaps["window"] == 20
    assert sum(gaps.values()) == 100 - 4


def test_probe_reads_a_small_window(monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    c = H.load_cell("fleet256.elastic_burst", ROOT)
    c["config"]["nodes"] = 24
    c["traffic"]["arrivals"]["jobs"] = 60
    saved = (H.window, H.replay, F.cluster)
    spans, rec = Spans(), A.Recorder()
    with P.Probe(annotate=False) as probe, A.patched_reductions(rec, spans):
        win = H.window(c, 3_000_000_019, 0.5, spans, rec)
    assert (H.window, H.replay, F.cluster) == saved
    r = probe.result
    assert r["events"] == sum(x["events"] for x in win["replays"]) > 0
    launches = sum(v for k, v in r["program_counts"].items()
                   if k.startswith("kernel.launches."))
    assert launches == sum(rec.launches.values()) > 0
    assert r["program_calls"]["kernel.fetch"] == launches
    assert all(v is not None for v in r["readings"].values())
    assert r["beside"]["dispatch_us_per_event"]["benchmark"] > 0
    hits = r["cache_hits"]
    assert hits["decisions"] > 0
    assert set(hits) >= {"table_hits", "launch_hits", "stage_served"}
    assert probe.lines()[-1].startswith("prog_json: ")
