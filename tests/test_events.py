"""Event-queue substrate (ISSUE 4): pre-refactor bit-identity locks,
preemption/checkpoint-restart mechanics, elastic resizing, migration,
legacy dispatcher parity + deprecation."""
import hashlib
import warnings

import numpy as np
import pytest

from repro.core import (
    Arrival,
    Cluster,
    EcoSched,
    ElasticConfig,
    EnergyAwareDispatcher,
    EventQueue,
    JobProfile,
    LeastLoadedDispatcher,
    Marble,
    Node,
    NodeSim,
    NodeSpec,
    ProfiledPerfModel,
    RoundRobinDispatcher,
    SequentialMax,
    bursty_stream,
    elastic_summary,
    poisson_stream,
    simulate,
)
from repro.core import calibration as C
from repro.core.events import (
    EVT_ARRIVAL,
    EVT_COMPLETE,
    EVT_MIGRATE,
    EVT_PREEMPT,
    EVT_RESUME,
)
from repro.core.types import RunningJob
from repro.roofline.hw import A100, H100, V100


def fp_records(records):
    s = ";".join(
        f"{r.job}|{r.g}|{r.start!r}|{r.end!r}|{r.node}|{r.domain}"
        for r in records
    )
    return hashlib.md5(s.encode()).hexdigest()


def prof(name, times, pows):
    util = {g: 1.0 / (times[g] * g) for g in times}
    return JobProfile(name=name, runtime=times, busy_power=pows, dram_util=util)


# ---------------------------------------------------------------------------
# Regression lock: the substrate reproduces the PRE-refactor loops bit-exactly
# (fingerprints captured from the original simulate()/Cluster.simulate()
# heaps at commit 07ec742, immediately before the events.py refactor)
# ---------------------------------------------------------------------------

GOLDEN = {
    "single_eco": ("4e5acdeeb3914722311e6f77658684e6",
                   28776.922695292677, 37833975.82206808),
    "single_marble": ("ae237255c84080ef71dd1656b25dd6fc",
                      37049.71767090324, 42220817.23598296),
    # rr/ll fingerprints re-captured for ISSUE 9: dispatcher ordering and
    # score ties now follow *name rank* instead of spec construction order
    # (the hetero fixture constructs h100-0 before a100-0, so the rr cycle
    # and the empty-cluster ll ties shifted; eco scores have no ties here
    # and its rows are the original pre-refactor captures)
    "cluster_rr_poisson": ("6d4e0947e2cc1abf9fbbca4344388686",
                           29071.552330516854, 52281764.54420596),
    "cluster_rr_bursty": ("026e027ccb63f638f098a003d07e20d6",
                          30795.74235233504, 56501997.61546908),
    "cluster_ll_poisson": ("89870d98998f9d73dc8e9029ada743a2",
                           23660.99784615058, 50152980.42951542),
    "cluster_ll_bursty": ("5d0ba4e4314ceb89afd624e415a405e8",
                          23587.94143314568, 51811670.13997635),
    "cluster_eco_poisson": ("121a072270dd10043f630b6817baa3a8",
                            22616.542502162163, 48650401.147005975),
    "cluster_eco_bursty": ("221212a44202a789b7345968ae61b2f4",
                           24528.02720558229, 52370378.05932653),
    "cluster_fifo_bursty": ("e66e494286395166d4d76d421082bd10",
                            53076.10181267525, 67945350.48415726),
}


def _hetero(dispatcher):
    return Cluster(
        [NodeSpec("h100-0", H100), NodeSpec("a100-0", A100),
         NodeSpec("v100-0", V100)],
        truth_for=lambda s: C.build_system(s.chip.name),
        policy_for=lambda s, t: EcoSched(
            ProfiledPerfModel(t, noise=0.02, seed=1), lam=0.35, tau=0.45
        ),
        dispatcher=dispatcher,
        slowdown_for=lambda s: C.cross_numa_slowdown,
    )


def _golden_streams():
    return {
        "poisson": poisson_stream(C.APP_ORDER, rate=1 / 700, n=20, seed=11),
        "bursty": bursty_stream(C.APP_ORDER, rate=1 / 500, n=22, burst=4, seed=5),
    }


def test_single_node_matches_pre_refactor_golden():
    truth = C.build_system("h100")
    node = Node(4, 2, C.idle_power("h100"))
    pol = EcoSched(ProfiledPerfModel(truth, noise=0.02, seed=1),
                   lam=0.35, tau=0.45)
    r = simulate(
        pol, node, truth,
        arrivals=[(120.0 * i, a) for i, a in enumerate(C.APP_ORDER)],
        slowdown_model=C.cross_numa_slowdown,
    )
    fp, makespan, energy = GOLDEN["single_eco"]
    assert fp_records(r.records) == fp
    assert r.makespan == makespan and r.total_energy == energy

    r2 = simulate(Marble(truth), node, truth, queue=list(C.APP_ORDER))
    fp, makespan, energy = GOLDEN["single_marble"]
    assert fp_records(r2.records) == fp
    assert r2.makespan == makespan and r2.total_energy == energy


@pytest.mark.parametrize("dn,disp", [
    ("rr", RoundRobinDispatcher), ("ll", LeastLoadedDispatcher),
    ("eco", EnergyAwareDispatcher),
])
def test_cluster_matches_pre_refactor_golden(dn, disp):
    for sn, stream in _golden_streams().items():
        res = _hetero(disp()).simulate(stream)
        fp, makespan, energy = GOLDEN[f"cluster_{dn}_{sn}"]
        assert fp_records(res.records) == fp
        assert res.makespan == makespan and res.total_energy == energy


def test_baseline_cluster_matches_pre_refactor_golden():
    res = Cluster(
        [NodeSpec("h100-0", H100), NodeSpec("v100-0", V100)],
        truth_for=lambda s: C.build_system(s.chip.name),
        policy_for=lambda s, t: SequentialMax(t),
        dispatcher=RoundRobinDispatcher(),
    ).simulate(_golden_streams()["bursty"])
    fp, makespan, energy = GOLDEN["cluster_fifo_bursty"]
    assert fp_records(res.records) == fp
    assert res.makespan == makespan and res.total_energy == energy


def test_all_off_elastic_config_is_bit_identical():
    """``ElasticConfig()`` with every switch off must ride the exact static
    path — single-node and cluster."""
    truth = C.build_system("v100")
    node = Node(4, 2, C.idle_power("v100"))

    def pol():
        return EcoSched(ProfiledPerfModel(truth, noise=0.02, seed=1),
                        lam=0.35, tau=0.45)

    a = simulate(pol(), node, truth, queue=list(C.APP_ORDER))
    b = simulate(pol(), node, truth, queue=list(C.APP_ORDER),
                 elastic=ElasticConfig())
    assert fp_records(a.records) == fp_records(b.records)
    assert a.total_energy == b.total_energy and a.makespan == b.makespan

    stream = _golden_streams()["poisson"]
    ca = _hetero(EnergyAwareDispatcher()).simulate(stream)
    cb = _hetero(EnergyAwareDispatcher()).simulate(
        stream, elastic=ElasticConfig()
    )
    assert fp_records(ca.records) == fp_records(cb.records)
    assert ca.total_energy == cb.total_energy


# ---------------------------------------------------------------------------
# Event queue ordering
# ---------------------------------------------------------------------------


def test_event_kind_ordering_at_one_instant():
    q = EventQueue()
    q.push(5.0, EVT_MIGRATE, "m")
    q.push(5.0, EVT_COMPLETE, "c")
    q.push(5.0, EVT_ARRIVAL, "a")
    q.push(5.0, EVT_RESUME, "r")
    q.push(5.0, EVT_PREEMPT, "p")
    q.push(1.0, EVT_COMPLETE, "early")
    order = [q.pop()[2] for _ in range(len(q))]
    assert order == ["early", "a", "c", "p", "r", "m"]


def test_same_kind_ties_keep_push_order():
    q = EventQueue()
    for i in range(5):
        q.push(2.0, EVT_COMPLETE, i)
    assert [q.pop()[2] for _ in range(len(q))] == [0, 1, 2, 3, 4]
    assert q.next_is(1.0, EVT_ARRIVAL) is False


# ---------------------------------------------------------------------------
# Preemption / checkpoint-restart mechanics
# ---------------------------------------------------------------------------

AB_TRUTH = {
    # A: moderate scaler whose τ-kept modes span {2, 3, 4}, with g=4 cheap
    # enough that upsizing beats the switch cost once the node drains
    "A": prof("A", {1: 3500, 2: 2000, 3: 1600, 4: 1450},
              {1: 140, 2: 250, 3: 330, 4: 380}),
    "B": prof("B", {1: 1050, 2: 600, 3: 480, 4: 435},
              {1: 140, 2: 250, 3: 330, 4: 380}),
}


def _eco_ab():
    return EcoSched(ProfiledPerfModel(AB_TRUTH, noise=0.0, seed=0),
                    lam=0.35, tau=0.45)


def test_resize_preempts_and_relaunches_at_better_count():
    """Co-scheduled pair at g=2 each; when B completes, A is checkpointed
    and relaunched on all 4 units — time and EDP improve, every joule is
    accounted."""
    node = Node(4, 2, 10.0)
    cfg = ElasticConfig(resize=True, ckpt_time=30.0, restart_time=15.0,
                        min_gain_s=60.0)
    static = simulate(_eco_ab(), node, AB_TRUTH, queue=["A", "B"])
    el = simulate(_eco_ab(), node, AB_TRUTH, queue=["A", "B"], elastic=cfg)

    assert static.preemptions == 0 and static.resizes == 0
    assert el.preemptions == 1
    assert el.resize_history == {"A": [(630.0, 2, 4)]}
    assert el.makespan < static.makespan
    assert el.edp < static.edp

    segs = [(r.job, r.g, r.segment, r.kind, r.start, r.end) for r in el.records]
    assert segs == [
        ("A", 2, 0, "ckpt", 0.0, 630.0),  # 600 useful + 30 ckpt write
        ("B", 2, 0, "run", 0.0, 600.0),
        ("A", 4, 1, "run", 630.0, 1660.0),  # 15 restart + 70% of 1450
    ]
    # exact energy: A seg0 = 600s@250W + 30s ckpt@250W; relaunch 1030s@380W
    assert el.records[0].busy_energy == 250.0 * 600 + 250.0 * 30
    assert el.records[0].ckpt_energy == 250.0 * 30
    assert el.records[2].busy_energy == pytest.approx(380.0 * 1030, rel=1e-12)
    assert el.ckpt_energy == 250.0 * 30
    assert el.busy_energy == pytest.approx(
        sum(r.busy_energy for r in el.records), rel=1e-12
    )
    assert elastic_summary(el) == {
        "preemptions": 1, "migrations": 0, "resizes": 1,
        "ckpt_energy": 250.0 * 30,
    }


def test_preemption_conserves_gpu_seconds():
    node = Node(4, 2, 10.0)
    cfg = ElasticConfig(resize=True, ckpt_time=30.0, restart_time=15.0,
                        min_gain_s=60.0)
    r = simulate(_eco_ab(), node, AB_TRUTH, queue=["A", "B"], elastic=cfg)
    busy_us = sum((rec.end - rec.start) * rec.g for rec in r.records)
    idle_us = r.idle_energy / node.idle_power_per_unit
    assert busy_us + idle_us == pytest.approx(node.units * r.makespan, rel=1e-9)


def test_max_preempts_bounds_churn():
    node = Node(4, 2, 10.0)
    cfg = ElasticConfig(resize=True, ckpt_time=1.0, restart_time=1.0,
                        min_gain_s=0.0, max_preempts=0)
    r = simulate(_eco_ab(), node, AB_TRUTH, queue=["A", "B"], elastic=cfg)
    assert r.preemptions == 0  # budget 0: the proposal is always refused


def test_frac_at_tracks_useful_work():
    rj = RunningJob(job="x", g=2, units=(0, 1), domain=0, start=100.0,
                    end=100.0 + 15.0 + 700.0, power=200.0,
                    frac0=0.3, restart=15.0)
    assert rj.frac_at(100.0) == pytest.approx(0.3)
    assert rj.frac_at(115.0) == pytest.approx(0.3)  # restart = no progress
    assert rj.frac_at(115.0 + 350.0) == pytest.approx(0.3 + 0.7 / 2)
    assert rj.frac_at(815.0) == pytest.approx(1.0)
    assert rj.frac_at(9999.0) == 1.0


def test_resize_identical_across_scoring_backends(monkeypatch):
    """The switch-cost-biased resize scoring runs through whichever backend
    the policy uses — vector argmin, pure-Python reference, or the Pallas
    score-reduce kernel (interpret fallback on CPU) — with one decision."""
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    node = Node(4, 2, 10.0)
    cfg = ElasticConfig(resize=True, ckpt_time=30.0, restart_time=15.0,
                        min_gain_s=60.0)
    out = {}
    for eng in ("vector", "python", "jax"):
        pol = EcoSched(ProfiledPerfModel(AB_TRUTH, noise=0.0, seed=0),
                       lam=0.35, tau=0.45, engine=eng)
        r = simulate(pol, node, AB_TRUTH, queue=["A", "B"], elastic=cfg)
        out[eng] = (r.makespan, r.total_energy, r.preemptions,
                    dict(r.resize_history))
    assert out["vector"] == out["python"] == out["jax"]
    assert out["vector"][3] == {"A": [(630.0, 2, 4)]}


def test_nonelastic_baselines_never_resize():
    node = Node(4, 2, 10.0)
    cfg = ElasticConfig(resize=True, ckpt_time=1.0, restart_time=1.0,
                        min_gain_s=0.0)
    r = simulate(SequentialMax(AB_TRUTH), node, AB_TRUTH,
                 queue=["A", "B"], elastic=cfg)
    assert r.preemptions == 0 and r.resizes == 0


# ---------------------------------------------------------------------------
# Migration
# ---------------------------------------------------------------------------

MIG_TRUTH = {
    "L": JobProfile(name="L", runtime={4: 4000.0}, busy_power={4: 400.0}),
    "S": JobProfile(name="S", runtime={4: 400.0}, busy_power={4: 400.0}),
}


def _mig_cluster():
    return Cluster(
        [NodeSpec("n0", H100), NodeSpec("n1", H100)],
        truth_for=lambda s: MIG_TRUTH,
        policy_for=lambda s, t: SequentialMax(t),
        dispatcher=RoundRobinDispatcher(),
    )


MIG_STREAM = [
    Arrival(0.0, "L#0", "L"), Arrival(0.0, "S#1", "S"), Arrival(0.0, "L#2", "L"),
]


def test_migration_pulls_waiting_job_to_drained_node():
    cfg = ElasticConfig(migrate=True, migration_delay=10.0, min_gain_s=60.0)
    static = _mig_cluster().simulate(MIG_STREAM)
    el = _mig_cluster().simulate(MIG_STREAM, elastic=cfg)
    assert static.migrations == 0
    assert el.migrations == 1
    assert el.makespan < static.makespan
    moved = next(r for r in el.records if r.job == "L#2")
    assert moved.node == "n1"  # pulled onto the drained node
    assert moved.start == pytest.approx(400.0 + 10.0)  # after the delay
    assert moved.arrival == 0.0  # waiting time counts from submission
    # donor queueing + transit is all genuine waiting for a job that
    # never ran: wait spans submission -> launch on the receiving node
    assert moved.wait == pytest.approx(410.0)
    assert el.per_node["n0"].migrations_out == 1
    assert el.per_node["n1"].migrations_in == 1
    # conservation per node still holds with the cross-node move
    for nm, nr in el.per_node.items():
        busy_us = sum((rec.end - rec.start) * rec.g for rec in nr.records)
        idle_us = nr.idle_energy / H100.power_idle
        assert busy_us + idle_us == pytest.approx(4 * nr.makespan, rel=1e-9)


def test_migration_declines_when_gain_too_small():
    cfg = ElasticConfig(migrate=True, migration_delay=10.0, min_gain_s=1e9)
    el = _mig_cluster().simulate(MIG_STREAM, elastic=cfg)
    assert el.migrations == 0


def test_preempted_job_state_travels_on_migration():
    """evict/absorb carry progress + the restart obligation across nodes;
    the relaunch runs only the remaining work plus the restart overhead."""
    cfg = ElasticConfig(resize=True, ckpt_time=30.0, restart_time=15.0,
                        min_gain_s=60.0)
    node = Node(4, 2, 10.0)
    donor = NodeSim(node, AB_TRUTH, _eco_ab(), name="donor", elastic=cfg)
    target = NodeSim(node, AB_TRUTH, SequentialMax(AB_TRUTH), name="target",
                     elastic=cfg)
    donor.arrive("A", 0.0)
    (rj,) = donor.invoke_policy()
    frac = 1000.0 / AB_TRUTH["A"].runtime[rj.g]
    ck_end = donor.begin_preempt(rj, 1000.0, cfg)
    assert ck_end == 1030.0
    donor.finish_preempt(rj, ck_end)
    donor.requeue("A", ck_end)  # the RESUME event the substrate would fire
    assert donor.progress["A"] == pytest.approx(frac)
    st = donor.evict("A")
    assert st.arrival == 0.0 and st.progress == pytest.approx(frac)
    assert st.restart is True and st.segment == 1
    assert st.preempts == 1 and st.last_g == rj.g  # budget + history travel
    assert st.queued_at == ck_end  # donor's requeue instant travels too
    assert donor.migrations_out == 1 and "A" not in donor.progress
    assert "A" not in donor.preempt_count

    target.absorb("A", 1040.0, st)
    assert target.migrations_in == 1
    assert target.preempt_count["A"] == 1  # max_preempts stays global
    (rj2,) = target.invoke_policy()
    assert rj2.frac0 == pytest.approx(frac) and rj2.restart == 15.0
    # SequentialMax launches at g=4: restart + the remaining fraction
    assert rj2.end - rj2.start == pytest.approx(15.0 + (1 - frac) * 1450.0)
    rec = target.records[-1]
    assert rec.arrival == 0.0 and rec.segment == 1
    # wait counts from the donor's requeue (1030) through the transit to
    # the launch at 1040 — queueing + transit, but not the running time
    assert rec.queued == ck_end and rec.wait == pytest.approx(10.0)
    if rj2.g != rj.g:  # cross-node resize lands in the history
        assert target.resize_history["A"] == [(1040.0, rj.g, rj2.g)]


def test_resumed_segment_wait_counts_requeue_time_only():
    """A preempted job's resume record must not count its own running time
    as waiting (mean_wait would otherwise penalize elastic runs)."""
    node = Node(4, 2, 10.0)
    cfg = ElasticConfig(resize=True, ckpt_time=30.0, restart_time=15.0,
                        min_gain_s=60.0)
    el = simulate(_eco_ab(), node, AB_TRUTH, queue=["A", "B"], elastic=cfg)
    resumed = next(r for r in el.records if r.segment == 1)
    # requeued at the checkpoint end (630) and relaunched immediately
    assert resumed.queued == 630.0
    assert resumed.wait == pytest.approx(0.0)
    assert resumed.arrival == 0.0  # submission time still preserved


# ---------------------------------------------------------------------------
# Legacy route(arr, statuses) protocol: graduated to a hard error (satellite)
# ---------------------------------------------------------------------------


class LegacyLeastLoaded:
    """route()-only dispatcher — the pre-PR-4 protocol, now rejected."""

    def name(self):
        return "legacy-ll"

    def route(self, arr, statuses):
        raise AssertionError("the legacy protocol must never be invoked")


def test_legacy_route_only_dispatcher_is_rejected():
    """A dispatcher without route_indexed fails fast at run construction
    (the DeprecationWarning period ended; the list protocol is gone)."""
    stream = [Arrival(0.0, "L#0", "L")]
    cl = Cluster(
        [NodeSpec("n0", H100)],
        truth_for=lambda s: MIG_TRUTH,
        policy_for=lambda s, t: SequentialMax(t),
        dispatcher=LegacyLeastLoaded(),
    )
    with pytest.raises(TypeError, match="route_indexed"):
        cl.simulate(stream)


def test_route_indexed_dispatcher_does_not_warn():
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        _mig_cluster().simulate(MIG_STREAM)  # must not raise


# ---------------------------------------------------------------------------
# Cancel races against in-flight elastic transitions (ISSUE 8)
# ---------------------------------------------------------------------------


def test_cancel_during_migration_transit_is_refused():
    """Between the donor's evict and the receiver's absorb the job exists
    only as an in-flight MIGRATE event; a cancel there must be refused
    and the migration must land untouched."""
    cfg = ElasticConfig(migrate=True, migration_delay=10.0, min_gain_s=60.0)

    def drive(cancel_at=None):
        run = _mig_cluster().open_run(apps=["L", "S"], elastic=cfg)
        for a in MIG_STREAM:
            run.submit(a.name, a.app, a.t)
        if cancel_at is not None:
            run.run_until(cancel_at)
            assert run.cancel("L#2") is False  # mid-transit: refused
        run.run_to_completion()
        return run.finalize()

    res = drive(cancel_at=405.0)  # n1 drains at 400, L#2 lands at 410
    ctrl = drive()
    assert res.migrations == 1
    moved = next(r for r in res.records if r.job == "L#2")
    assert moved.node == "n1" and moved.start == pytest.approx(410.0)
    assert [(r.job, r.node, r.start, r.end) for r in res.records] == [
        (r.job, r.node, r.start, r.end) for r in ctrl.records
    ]


def test_cancel_during_checkpoint_write_is_refused():
    """While a resize checkpoint is being written the job is neither
    waiting nor done; cancel must refuse, and the relaunch must proceed
    exactly as if nobody had asked."""
    cfg = ElasticConfig(resize=True, ckpt_time=30.0, restart_time=15.0,
                        min_gain_s=60.0)

    def cluster():
        return Cluster(
            [NodeSpec("n0", H100)],
            truth_for=lambda s: AB_TRUTH,
            policy_for=lambda s, t: _eco_ab(),
            dispatcher=RoundRobinDispatcher(),
        )

    def drive(cancel_at=None):
        run = cluster().open_run(apps=["A", "B"], elastic=cfg)
        run.submit("A", "A", 0.0)
        run.submit("B", "B", 0.0)
        if cancel_at is not None:
            run.run_until(cancel_at)
            assert run.cancel("A") is False  # mid-ckpt-write: refused
        run.run_to_completion()
        return run.finalize()

    res = drive(cancel_at=615.0)  # ckpt write spans 600 -> 630
    ctrl = drive()
    segs = [(r.job, r.g, r.kind, r.start, r.end) for r in res.records]
    assert ("A", 2, "ckpt", 0.0, 630.0) in segs
    assert segs == [(r.job, r.g, r.kind, r.start, r.end) for r in ctrl.records]
