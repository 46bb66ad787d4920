"""The program's tracer (repro.obs): self time of nested spans, snapshots
read in the middle of a run, counters, profiler annotations only when
asked for, the kernel entry points' pack/call/fetch spans and counters,
and ``decision_phases`` read from the tracer's spans."""
import numpy as np
import pytest

from repro import obs
from repro.core import (
    Cluster,
    EcoSched,
    ElasticConfig,
    NodeSpec,
    ProfiledPerfModel,
    RoundRobinDispatcher,
    bursty_stream,
)
from repro.core import calibration as C
from repro.core.events import EVT_ARRIVAL
from repro.kernels import score_reduce as sr
from repro.roofline.hw import H100


def spans_since(before: dict, after: dict) -> dict:
    """Calls and seconds of each span between two snapshots."""
    out = {}
    for name, s in after["spans"].items():
        b = before["spans"].get(name, {"self_s": 0.0, "total_s": 0.0, "calls": 0})
        out[name] = {k: s[k] - b[k] for k in s}
    return out


def counts_since(before: dict, after: dict) -> dict:
    return {k: v - before["counts"].get(k, 0) for k, v in after["counts"].items()}


@pytest.fixture
def clock(monkeypatch):
    """The tracer's clock reads the times the test sets, in ns."""
    now = [0]
    monkeypatch.setattr(obs, "_now", lambda: now[0])
    return now


def test_self_time_excludes_nested_spans(clock):
    tr = obs.Tracer()
    tr.start("a")           # t=0
    clock[0] = 10
    tr.start("b")
    clock[0] = 40
    assert tr.stop() == 30  # b
    clock[0] = 50
    with tr.span("c"):
        clock[0] = 60
    clock[0] = 70
    tr.start("b")
    clock[0] = 75
    tr.stop()
    clock[0] = 100
    assert tr.stop() == 100  # a
    spans = tr.snapshot()["spans"]
    assert spans["a"] == {"self_s": 55e-9, "total_s": 100e-9, "calls": 1}
    assert spans["b"] == {"self_s": 35e-9, "total_s": 35e-9, "calls": 2}
    assert spans["c"] == {"self_s": 10e-9, "total_s": 10e-9, "calls": 1}


def test_snapshot_reads_the_middle_of_a_span_tree(clock):
    tr = obs.Tracer()
    tr.start("outer")
    tr.start("inner")
    clock[0] = 20
    tr.stop()
    mid = tr.snapshot()  # "outer" is still open: it counts once it ends
    assert mid["spans"]["outer"] == {"self_s": 0.0, "total_s": 0.0, "calls": 0}
    assert mid["spans"]["inner"]["calls"] == 1
    clock[0] = 50
    tr.stop()
    after = tr.snapshot()
    d = spans_since(mid, after)
    assert d["inner"]["calls"] == 0
    assert d["outer"] == {"self_s": 30e-9, "total_s": 50e-9, "calls": 1}


def test_counters_and_snapshot_copies():
    tr = obs.Tracer()
    tr.count("x")
    tr.count("x", 4)
    tr.count("y", 2)
    snap = tr.snapshot()
    assert snap["counts"] == {"x": 5, "y": 2}
    snap["counts"]["x"] = 0
    tr.count("x")
    assert tr.snapshot()["counts"]["x"] == 6


class FakeAnnotation:
    log = []

    def __init__(self, name):
        self.name = name
        FakeAnnotation.log.append(("new", name))

    def __enter__(self):
        FakeAnnotation.log.append(("enter", self.name))

    def __exit__(self, *exc):
        FakeAnnotation.log.append(("exit", self.name))


@pytest.fixture
def fake_annotation(monkeypatch):
    import jax.profiler

    FakeAnnotation.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    return FakeAnnotation.log


def test_no_annotation_unless_turned_on(fake_annotation):
    tr = obs.Tracer()
    with tr.span("a"):
        tr.start("b")
        tr.stop()
    assert fake_annotation == []


def test_each_span_enters_one_annotation(fake_annotation):
    tr = obs.Tracer()
    tr.annotate(True)
    with tr.span("a"):
        tr.start("b")
        tr.stop()
    assert fake_annotation == [("new", "a"), ("enter", "a"), ("new", "b"),
                               ("enter", "b"), ("exit", "b"), ("exit", "a")]
    tr.annotate(False)
    with tr.span("c"):
        pass
    assert len(fake_annotation) == 6
    assert tr.snapshot()["spans"]["c"]["calls"] == 1


def window(B, S, seed):
    rng = np.random.default_rng(seed)
    return dict(dev=rng.random((B, S)), g=rng.integers(1, 4, (B, S)).astype(float),
                n=np.full(B, S), lam=0.35, g_free=8, M=8)


@pytest.mark.parametrize("kind,rows", [("solo", 256), ("batch", 2 * 256), ("multi", 256)])
def test_kernel_entry_point_spans_and_counters(kind, rows):
    reqs = [window(3, 2, 1), window(5, 3, 2)]
    before = obs.snapshot()
    if kind == "solo":
        r = reqs[0]
        sr.score_reduce(r["dev"], r["g"], r["n"], lam=r["lam"],
                        g_free=r["g_free"], M=r["M"], mode="ref")
    else:
        getattr(sr, f"score_reduce_{kind}")(reqs, mode="ref")
    after = obs.snapshot()
    spans = spans_since(before, after)
    for name in ("kernel.pack", "kernel.call", "kernel.fetch"):
        assert spans[name]["calls"] == 1
        assert spans[name]["self_s"] > 0
    counts = counts_since(before, after)
    assert counts[f"kernel.launches.{kind}"] == 1
    # one packed table in: three 8-slot planes and eight per-row columns
    assert counts["kernel.h2d_arrays"] == 1
    assert counts["kernel.h2d_bytes"] == 4 * rows * (3 * 8 + 8)
    # one answer out
    assert counts["kernel.d2h_arrays"] == 1


def test_empty_request_list_launches_nothing():
    before = obs.snapshot()
    assert sr.score_reduce_multi([]) == []
    assert counts_since(before, obs.snapshot()).get("kernel.h2d_arrays", 0) == 0


def elastic_fleet():
    apps = C.build_system("h100")

    def policy_for(spec, truth):
        return EcoSched(ProfiledPerfModel(truth, noise=0.0, seed=1),
                        lam=0.35, tau=0.45, engine="jax")

    return Cluster(
        [NodeSpec(f"n{i:03d}", H100, units=8, domains=2) for i in range(4)],
        truth_for=lambda s: apps,
        policy_for=policy_for,
        dispatcher=RoundRobinDispatcher(),
    )


def test_cluster_run_reads_its_phases_from_the_tracer():
    stream = bursty_stream(list(C.APP_ORDER), rate=0.05, n=40, burst=4, seed=5)
    run = elastic_fleet().open_run(
        apps=sorted({a.app for a in stream}),
        jobs=[(a.name, a.app) for a in stream],
        elastic=ElasticConfig(resize=True, migrate=True),
    )
    for a in stream:
        run.loop.queue.push(a.t, EVT_ARRIVAL, a)
    before = obs.snapshot()
    run.loop.start()
    for _ in range(10):
        run.loop.step()
    mid = spans_since(before, obs.snapshot())  # readable mid-run
    assert sum(s["calls"] for n, s in mid.items() if n.startswith("loop.")) == 10
    assert mid["sched.route"]["calls"] > 0
    run.loop.run()
    res = run.finalize()
    spans = spans_since(before, obs.snapshot())

    phases = res.decision_phases
    assert set(phases) == {"dispatch", "launch", "resize", "migrate", "stage"}
    assert phases["dispatch"] > 0 and phases["launch"] > 0
    loop_calls = sum(s["calls"] for n, s in spans.items() if n.startswith("loop."))
    assert loop_calls == run.loop.events
    for key, name in (("dispatch", "sched.route"), ("launch", "sched.decide"),
                      ("resize", "sched.resize"), ("migrate", "sched.migrate"),
                      ("stage", "sched.stage")):
        got = spans.get(name, {"total_s": 0.0})["total_s"]
        assert phases[key] == pytest.approx(got, rel=1e-9, abs=1e-12), key
    assert spans["sched.decide"]["calls"] == sum(
        r.decision_events for r in res.per_node.values())
    assert spans["kernel.call"]["calls"] > 0
    assert spans["sched.resize"]["calls"] > 0
