"""Fault plane (ISSUE 8): seeded deterministic injection, crash/retry/
lost mechanics, energy accounting under kills, degraded-capacity
scheduling, journal snapshot compaction, daemon hardening, and crash
recovery with faults enabled."""
import json
import math
import os
import socket
import threading
import time

import numpy as np
import pytest

from repro.core import (
    Cluster,
    ClusterBackend,
    EcoSched,
    ElasticConfig,
    EnergyAwareDispatcher,
    FaultConfig,
    FaultInjector,
    ForecastConfig,
    JobProfile,
    Node,
    NodeSim,
    NodeSpec,
    ProfiledPerfModel,
    RoundRobinDispatcher,
    SchedulerService,
    SequentialMax,
    simulate,
)
from repro.core import calibration as C
from repro.core.journal import JOURNAL_VERSION, Journal, chain_hash
from repro.core.service import (
    FAILED,
    FAILED_RETRYING,
    MAX_LINE,
    QUEUED,
    RUNNING,
    TRANSITIONS,
    request,
    request_retry,
    serve,
)
from repro.roofline.hw import A100, H100

LAM, TAU, NOISE, SEED = 0.35, 0.45, 0.02, 1


def prof(name, times, pows):
    util = {g: 1.0 / (times[g] * g) for g in times}
    return JobProfile(name=name, runtime=times, busy_power=pows, dram_util=util)


TRUTH = {
    "A": prof("A", {1: 3500, 2: 2000, 4: 1450}, {1: 140, 2: 250, 4: 380}),
    "B": prof("B", {1: 1050, 2: 600, 4: 435}, {1: 140, 2: 250, 4: 380}),
}


def _eco(engine="vector"):
    return EcoSched(
        ProfiledPerfModel(TRUTH, noise=0.0, seed=0),
        lam=0.35, tau=0.45, engine=engine,
    )


def fp(records):
    return ";".join(
        f"{r.job}|{r.g}|{r.start!r}|{r.end!r}|{r.node}|{r.domain}|{r.kind}"
        for r in records
    )


# ---------------------------------------------------------------------------
# Injector determinism
# ---------------------------------------------------------------------------


def test_injector_streams_are_seeded_and_deterministic():
    cfg = FaultConfig(
        seed=7, node_mtbf_s=1000.0, node_mttr_s=100.0, degrade_frac=0.5,
        job_mtbf_s=5000.0, straggler_prob=0.3,
    )
    a, b = FaultInjector(cfg), FaultInjector(cfg)
    seq_a = [a.next_cycle("n0", 4) for _ in range(5)]
    seq_b = [b.next_cycle("n0", 4) for _ in range(5)]
    assert seq_a == seq_b
    assert all(up > 0 and down > 0 and 1 <= k <= 4 for up, down, k in seq_a)
    # distinct nodes draw from distinct streams
    assert FaultInjector(cfg).next_cycle("n1", 4) != seq_a[0]
    # crash offsets are pure functions of (job, segment)
    assert a.crash_offset("j", 0) == b.crash_offset("j", 0)
    assert a.crash_offset("j", 0) != a.crash_offset("j", 1)
    assert a.straggler("j", 0) in (1.0, cfg.straggler_factor)
    # a different seed moves every stream
    other = FaultInjector(
        FaultConfig(seed=8, node_mtbf_s=1000.0, job_mtbf_s=5000.0)
    )
    assert other.crash_offset("j", 0) != a.crash_offset("j", 0)


def test_disabled_hazards_are_inert():
    inj = FaultInjector(FaultConfig())
    assert not FaultConfig().enabled
    assert inj.crash_offset("j", 0) == math.inf
    assert inj.straggler("j", 0) == 1.0


def test_retry_backoff_caps():
    cfg = FaultConfig(
        job_mtbf_s=1.0, retry_base_s=10.0, retry_mult=3.0, retry_cap_s=50.0
    )
    inj = FaultInjector(cfg)
    assert [inj.retry_delay(i) for i in range(4)] == [10.0, 30.0, 50.0, 50.0]


def test_signature_identifies_the_fault_process():
    a = FaultConfig(seed=3, node_mtbf_s=4000.0)
    b = FaultConfig(seed=4, node_mtbf_s=4000.0)
    assert a.signature() != b.signature()
    assert a.signature() == FaultConfig(seed=3, node_mtbf_s=4000.0).signature()


# ---------------------------------------------------------------------------
# Faults-off parity (the golden lock in test_events.py covers faults=None;
# this locks the disabled-config path onto the same bytes)
# ---------------------------------------------------------------------------


def test_disabled_faults_bit_identical_to_none():
    node = Node(4, 2, 10.0)
    r0 = simulate(_eco(), node, TRUTH, queue=["A", "B"])
    r1 = simulate(_eco(), node, TRUTH, queue=["A", "B"], faults=FaultConfig())
    assert fp(r0.records) == fp(r1.records)
    assert (r0.makespan, r0.total_energy) == (r1.makespan, r1.total_energy)
    assert r1.job_crashes == 0 and r1.node_failures == 0
    assert r1.fault_kills == 0 and not r1.lost_jobs


# ---------------------------------------------------------------------------
# Job crashes: determinism, engine identity, energy accounting
# ---------------------------------------------------------------------------

CRASHY = FaultConfig(seed=5, job_mtbf_s=1500.0, retry_base_s=30.0)


def test_seeded_job_crash_trace_is_deterministic():
    node = Node(4, 2, 10.0)
    r1 = simulate(_eco(), node, TRUTH, queue=["A", "B"], faults=CRASHY)
    r2 = simulate(_eco(), node, TRUTH, queue=["A", "B"], faults=CRASHY)
    assert r1.job_crashes > 0  # the hazard actually fired
    assert fp(r1.records) == fp(r2.records)
    assert (r1.makespan, r1.total_energy) == (r2.makespan, r2.total_energy)


def test_fault_trace_identical_across_engines(monkeypatch):
    """The crash hazard is a pure function of (job, segment), never of
    the engine backend — seeded fault traces are bit-identical across
    the vector, pure-Python, and Pallas (interpret) scorers."""
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    node = Node(4, 2, 10.0)
    out = {}
    for eng in ("vector", "python", "jax"):
        r = simulate(_eco(eng), node, TRUTH, queue=["A", "B"], faults=CRASHY)
        out[eng] = (
            fp(r.records), r.makespan, r.total_energy,
            r.job_crashes, r.fault_retries,
        )
    assert out["vector"] == out["python"] == out["jax"]
    assert out["vector"][3] > 0


def test_job_crash_conserves_unit_seconds():
    """A kill refunds the unrun busy tail and releases the units: busy +
    idle unit-seconds still tile the node exactly (no node downtime in a
    job-crash-only run)."""
    node = Node(4, 2, 10.0)
    r = simulate(
        SequentialMax(TRUTH), node, TRUTH, queue=["A", "B"], faults=CRASHY
    )
    assert r.job_crashes > 0 and not r.lost_jobs
    busy_us = sum((rec.end - rec.start) * rec.g for rec in r.records)
    idle_us = r.idle_energy / node.idle_power_per_unit
    assert busy_us + idle_us == pytest.approx(4 * r.makespan, rel=1e-9)
    # failed segments are marked and charged only to the kill instant
    fails = [rec for rec in r.records if rec.kind == "fail"]
    assert len(fails) == r.fault_kills
    assert all(rec.end <= r.makespan for rec in fails)


def test_retries_exhaust_to_lost():
    node = Node(4, 2, 10.0)
    fc = FaultConfig(
        seed=1, job_mtbf_s=1e-2, max_retries=2, retry_base_s=5.0
    )
    r = simulate(SequentialMax(TRUTH), node, TRUTH, queue=["A"], faults=fc)
    assert r.lost_jobs == ["A"]
    assert r.job_crashes == 3  # the launch + both retries all crashed
    assert r.fault_retries == 2
    assert all(rec.kind == "fail" for rec in r.records)
    # the node drains back to idle — the loop terminated on its own
    assert r.makespan > 0


def test_crash_rolls_progress_back_to_segment_start():
    """Work since the last checkpoint is lost AND re-done: the relaunch
    after a crash restarts from the killed segment's starting fraction,
    so total busy time exceeds the clean run's."""
    node = Node(4, 2, 10.0)
    clean = simulate(SequentialMax(TRUTH), node, TRUTH, queue=["A", "B"])
    r = simulate(
        SequentialMax(TRUTH), node, TRUTH, queue=["A", "B"], faults=CRASHY
    )
    assert r.job_crashes > 0 and not r.lost_jobs
    busy = sum((rec.end - rec.start) * rec.g for rec in r.records)
    busy_clean = sum(
        (rec.end - rec.start) * rec.g for rec in clean.records
    )
    assert busy > busy_clean  # lost work was re-done (plus restart heads)
    assert r.makespan > clean.makespan


# ---------------------------------------------------------------------------
# Node failures: eviction, downtime, degraded capacity
# ---------------------------------------------------------------------------


def test_node_failure_evicts_and_recovers():
    node = Node(4, 2, 10.0)
    fc = FaultConfig(seed=4, node_mtbf_s=2500.0, node_mttr_s=200.0)
    r = simulate(
        SequentialMax(TRUTH), node, TRUTH, queue=["A", "B"], faults=fc
    )
    assert r.node_failures > 0
    assert not r.lost_jobs
    # every job's chronologically-final segment completed (not a kill)
    for job in ("A", "B"):
        last = max(
            (rec for rec in r.records if rec.job == job),
            key=lambda rec: rec.end,
        )
        assert last.kind != "fail"
    # downtime is unpowered: busy + idle no longer tile units × makespan
    busy_us = sum((rec.end - rec.start) * rec.g for rec in r.records)
    idle_us = r.idle_energy / node.idle_power_per_unit
    assert busy_us + idle_us < 4 * r.makespan


def test_partial_degradation_masks_units():
    sim = NodeSim(Node(4, 2, 10.0), TRUTH, SequentialMax(TRUTH))
    sim.placement.mark_dead([3])
    v = sim.node_view()
    assert v.dead_units == 1 and v.alive_units == 3 and v.free_units == 3
    with pytest.raises(ValueError):
        sim.placement.allocate(4)  # the full node no longer exists
    sim.placement.revive([3])
    v2 = sim.node_view()
    assert v2.dead_units == 0 and v2.free_units == 4
    sim.placement.allocate(4)  # back to full capacity


def test_degraded_refit_shrinks_and_restores_feasible_space():
    # W scales superlinearly (wide modes are the unit-seconds minimum);
    # X only has a g=4 mode and becomes infeasible on a degraded node
    truth = {
        "W": prof("W", {1: 4000, 2: 1500, 4: 700}, {1: 140, 2: 250, 4: 380}),
        "X": prof("X", {4: 1000}, {4: 380}),
    }
    cl = Cluster(
        [NodeSpec("n0", H100)],
        truth_for=lambda s: truth,
        policy_for=lambda s, t: SequentialMax(t),
        dispatcher=RoundRobinDispatcher(),
    )
    run = cl.open_run(apps=["W", "X"])
    st = run.state
    fits0 = st.fits.copy()
    mins0 = st.min_unit_s.copy()
    assert fits0.all()
    assert st.min_unit_s[0, st.app_index["W"]] == 700.0 * 4
    st.set_alive_units(0, 1)
    # W falls back to its narrow mode at a worse unit-seconds cost;
    # X cannot run at all on the degraded node
    assert st.units[0] == 1.0
    assert st.fits[0, st.app_index["W"]]
    assert not st.fits[0, st.app_index["X"]]
    assert st.min_unit_s[0, st.app_index["W"]] == 4000.0
    st.set_alive_units(0, 4)
    assert np.array_equal(st.fits, fits0)
    assert np.allclose(st.min_unit_s, mins0)
    assert st.units[0] == 4.0


MIG_TRUTH = {
    "L": prof("L", {4: 4000.0}, {4: 400.0}),
}


def _two_nodes():
    return Cluster(
        [NodeSpec("n0", H100), NodeSpec("n1", H100)],
        truth_for=lambda s: MIG_TRUTH,
        policy_for=lambda s, t: SequentialMax(t),
        dispatcher=RoundRobinDispatcher(),
    )


def test_full_node_failure_reroutes_waiting_jobs():
    """When a node dies outright and migration is on, its waiting jobs
    move to live nodes instead of waiting out the repair."""
    fc = FaultConfig(
        seed=0, node_mtbf_s=6000.0, node_mttr_s=2000.0, max_retries=10
    )
    up, _, k = FaultInjector(fc).next_cycle("n0", 4)
    assert up < 4000.0 and k == 4  # the seed puts n0's death mid-run
    cfg = ElasticConfig(migrate=True, migration_delay=10.0, min_gain_s=60.0)
    run = _two_nodes().open_run(apps=["L"], elastic=cfg, faults=fc)
    for i in range(3):  # RR: L#0 -> n0, L#1 -> n1, L#2 waits on n0
        run.submit(f"L#{i}", "L", 0.0)
    run.run_to_completion()
    res = run.finalize()
    assert res.node_failures >= 1
    assert not res.lost_jobs
    # the waiting job escaped the dead node through the migration path
    l2 = [r for r in res.records if r.job == "L#2" and r.kind != "fail"]
    assert l2 and all(r.node == "n1" for r in l2)
    assert res.migrations >= 1


def test_without_migration_jobs_wait_out_the_repair():
    fc = FaultConfig(
        seed=0, node_mtbf_s=6000.0, node_mttr_s=2000.0, max_retries=10
    )
    run = _two_nodes().open_run(apps=["L"], faults=fc)
    for i in range(3):
        run.submit(f"L#{i}", "L", 0.0)
    run.run_to_completion()
    res = run.finalize()
    assert res.node_failures >= 1 and not res.lost_jobs
    assert res.migrations == 0
    # the stranded job stayed on the dead node and ran after the repair
    l2 = [r for r in res.records if r.job == "L#2" and r.kind != "fail"]
    assert l2 and all(r.node == "n0" for r in l2)


# ---------------------------------------------------------------------------
# Forecast plane under faults
# ---------------------------------------------------------------------------


def test_forecast_posterior_ignores_crashed_segments():
    """Crashed segment durations say nothing about an app's runtime:
    the refined posterior must not observe them."""
    cl = Cluster(
        [NodeSpec("n0", H100)],
        truth_for=lambda s: TRUTH,
        policy_for=lambda s, t: EcoSched(
            ProfiledPerfModel(t, noise=NOISE, seed=SEED), lam=LAM, tau=TAU
        ),
        dispatcher=RoundRobinDispatcher(),
    )
    fc = FaultConfig(seed=1, job_mtbf_s=1e-2, max_retries=1, retry_base_s=5.0)
    run = cl.open_run(apps=["A"], forecast=ForecastConfig(), faults=fc)
    run.submit("A#0", "A", 0.0)
    run.run_to_completion()
    res = run.finalize()
    assert res.lost_jobs == ["A#0"]  # every attempt crashed
    assert all(m.version == 0 for m in run.plane._models.values())

    # control: a clean completion does feed the posterior
    run2 = cl.open_run(apps=["A"], forecast=ForecastConfig())
    run2.submit("A#0", "A", 0.0)
    run2.run_to_completion()
    assert any(m.version > 0 for m in run2.plane._models.values())


# ---------------------------------------------------------------------------
# Control plane: states, journal v3, snapshot compaction, recovery
# ---------------------------------------------------------------------------


def _svc_cluster():
    return Cluster(
        [NodeSpec("h100-0", H100), NodeSpec("a100-0", A100)],
        truth_for=lambda s: C.build_system(s.chip.name),
        policy_for=lambda s, t: EcoSched(
            ProfiledPerfModel(t, noise=NOISE, seed=SEED), lam=LAM, tau=TAU
        ),
        dispatcher=EnergyAwareDispatcher(),
        slowdown_for=lambda s: C.cross_numa_slowdown,
        label="faults-svc",
    )


SVC_FAULTS = FaultConfig(seed=9, node_mtbf_s=20000.0, node_mttr_s=600.0,
                         job_mtbf_s=9000.0)


def _factory(faults=SVC_FAULTS, **kw):
    return lambda: ClusterBackend(_svc_cluster(), faults=faults, **kw)


OPS = [
    ("submit", "j0", "bert", 10.0),
    ("submit", "j1", "lbm", 10.0),
    ("submit", "j2", "resnet50", 40.0),
    ("advance", 900.0),
    ("submit", "j3", "gpt2", 1000.0),
    ("submit", "j4", "MonteCarlo", 1000.0),
    ("cancel", "j4"),
    ("submit", "j5", "vgg16", 1800.0),
    ("drain",),
]


def _apply(service, ops=OPS):
    for op in ops:
        if op[0] == "submit":
            service.submit(op[1], op[2], op[3])
        elif op[0] == "cancel":
            service.cancel(op[1])
        elif op[0] == "advance":
            service.advance(op[1])
        else:
            service.advance(None)


def _fingerprint(service):
    res = service.result()
    assert res["ok"], res
    return (
        tuple(tuple(r) for r in sorted(res["records"])),
        res["makespan"],
        res["total_energy"],
    )


def test_failed_retrying_state_machine_legs():
    assert FAILED_RETRYING in TRANSITIONS[RUNNING]
    assert TRANSITIONS[FAILED_RETRYING] == frozenset({QUEUED, FAILED})


def test_service_journals_fault_transitions(tmp_path):
    path = str(tmp_path / "f.jnl")
    svc = SchedulerService(_factory(), journal_path=path)
    _apply(svc)
    golden = _fingerprint(svc)
    kinds = {r["e"] for r in Journal.read(path) if r["k"] == "evt"}
    assert "fail" in kinds and "retry" in kinds  # the trace had crashes
    hist = [s for j in svc.jobs.values() for _, s in j.history]
    assert FAILED_RETRYING in hist
    assert Journal.read(path)[0]["v"] == JOURNAL_VERSION
    assert "/faults:" in svc.backend.describe()
    svc.close()

    # cold recovery reproduces the faulty schedule bit-identically
    back = SchedulerService(_factory(), journal_path=path)
    assert back.replay_divergences == 0
    assert _fingerprint(back) == golden
    back.close()


def test_crash_recovery_under_faults_at_random_offsets(tmp_path):
    """SIGKILL-anywhere with failures injected: truncate the journal at
    random byte offsets, restart, re-drive — bit-identical."""
    golden_path = str(tmp_path / "golden.jnl")
    svc = SchedulerService(_factory(), journal_path=golden_path)
    _apply(svc)
    golden = _fingerprint(svc)
    svc.close()
    blob = open(golden_path, "rb").read()
    header_end = blob.index(b"\n") + 1
    rng = np.random.default_rng(77)
    offsets = sorted(
        {int(o) for o in rng.integers(1, len(blob), size=8)}
        | {header_end, len(blob) - 1}
    )
    for off in offsets:
        path = str(tmp_path / f"crash{off}.jnl")
        with open(path, "wb") as f:
            f.write(blob[:off])
        back = SchedulerService(_factory(), journal_path=path)
        _apply(back)  # idempotent re-drive
        assert _fingerprint(back) == golden, f"diverged at offset {off}"
        assert back.replay_divergences == 0
        back.close()


def test_snapshot_plus_tail_recovery_equals_full_replay(tmp_path):
    """Satellite: compaction folds the event log into a chained-hash
    snapshot; recovery from snapshot + tail is bit-identical to full
    replay, across repeated compactions at every split point."""
    golden_path = str(tmp_path / "golden.jnl")
    svc = SchedulerService(_factory(), journal_path=golden_path)
    _apply(svc)
    golden = _fingerprint(svc)
    golden_jobs = {n: j.to_dict() for n, j in svc.jobs.items()}
    svc.close()

    for split in range(1, len(OPS)):
        path = str(tmp_path / f"split{split}.jnl")
        s = SchedulerService(_factory(), journal_path=path)
        _apply(s, OPS[:split])
        folded = s.compact()
        assert folded["ok"]
        _apply(s, OPS[split:])
        # a second compaction continues the chain (associativity)
        assert s.compact()["ok"]
        assert _fingerprint(s) == golden
        s.close()

        recs = Journal.read(path)
        assert recs[1]["k"] == "snap"
        assert not any(r["k"] == "evt" for r in recs[:2])
        back = SchedulerService(_factory(), journal_path=path)
        assert back.replay_divergences == 0
        assert _fingerprint(back) == golden, f"diverged at split {split}"
        assert {n: j.to_dict() for n, j in back.jobs.items()} == golden_jobs
        back.close()


def test_compacted_journal_survives_torn_tail(tmp_path):
    """A crash after compaction can tear only appended records; any
    state the compacted file passed through recovers bit-identically."""
    path = str(tmp_path / "c.jnl")
    svc = SchedulerService(_factory(), journal_path=path)
    _apply(svc, OPS[:4])
    svc.compact()
    base_len = os.path.getsize(path)
    _apply(svc, OPS[4:])
    golden = _fingerprint(svc)
    svc.close()
    blob = open(path, "rb").read()
    rng = np.random.default_rng(13)
    for off in sorted(
        {int(o) for o in rng.integers(base_len, len(blob), size=6)}
    ):
        p = str(tmp_path / f"t{off}.jnl")
        with open(p, "wb") as f:
            f.write(blob[:off])
        back = SchedulerService(_factory(), journal_path=p)
        _apply(back)
        assert _fingerprint(back) == golden, f"diverged at offset {off}"
        back.close()


def test_snapshot_chain_detects_tampered_history(tmp_path):
    """Cutting inputs out from under a snapshot (events can no longer be
    regenerated to match the chain) must fail loudly, not diverge
    silently."""
    from repro.core.service import RecoveryError

    path = str(tmp_path / "c.jnl")
    svc = SchedulerService(_factory(), journal_path=path)
    _apply(svc)
    svc.compact()
    svc.close()
    recs = Journal.read(path)
    assert recs[1]["k"] == "snap" and recs[1]["n"] > 0
    keep = [r for r in recs if r["k"] != "sub"]  # drop every submit
    with open(path, "w", encoding="utf-8") as f:
        for r in keep:
            f.write(json.dumps(r, separators=(",", ":"), sort_keys=True))
            f.write("\n")
    with pytest.raises(RecoveryError):
        SchedulerService(_factory(), journal_path=path)


def test_chain_hash_is_associative():
    recs = [{"k": "evt", "e": "queued", "i": i} for i in range(7)]
    whole = chain_hash(recs)
    assert chain_hash(recs[3:], chain_hash(recs[:3])) == whole
    assert chain_hash([]) == ""


# ---------------------------------------------------------------------------
# Daemon hardening + client retry (satellites)
# ---------------------------------------------------------------------------


def _boot(tmp_path, read_timeout=30.0):
    sock = str(tmp_path / "d.sock")
    svc = SchedulerService(
        lambda: ClusterBackend(_svc_cluster(), faults=None)
    )
    th = threading.Thread(
        target=serve, args=(svc, sock),
        kwargs={"read_timeout": read_timeout}, daemon=True,
    )
    th.start()
    for _ in range(200):
        if os.path.exists(sock):
            break
        time.sleep(0.01)
    return sock


def _raw_lines(sock_path, payloads, timeout=10.0):
    with socket.socket(socket.AF_UNIX, socket.SOCK_STREAM) as c:
        c.settimeout(timeout)
        c.connect(sock_path)
        out = []
        f = c.makefile("rb")
        for p in payloads:
            c.sendall(p)
            out.append(json.loads(f.readline().decode()))
        return out


def test_daemon_survives_malformed_and_oversized_requests(tmp_path):
    sock = _boot(tmp_path)
    try:
        r1, r2, r3 = _raw_lines(sock, [
            b"this is not json\n",
            b'{"op":"x","pad":"' + b"A" * (MAX_LINE + 10) + b'"}\n',
            b'{"op":"ping"}\n',
        ])
        assert r1 == {"ok": False, "error": "malformed JSON request"}
        assert r2 == {"ok": False, "error": "request too large"}
        assert r3.get("pong") is True  # same connection still framed
        # and a fresh connection still works
        assert request(sock, {"op": "ping"})["pong"] is True
    finally:
        request(sock, {"op": "shutdown"})


def test_daemon_drops_stuck_client_and_keeps_serving(tmp_path):
    sock = _boot(tmp_path, read_timeout=0.2)
    try:
        stuck = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        stuck.connect(sock)  # connect, never send a line
        time.sleep(0.5)
        # the daemon timed the stuck client out and accepts new work
        assert request_retry(sock, {"op": "ping"}, retries=6)["pong"] is True
        stuck.close()
    finally:
        request_retry(sock, {"op": "shutdown"}, retries=6)


def test_request_retry_waits_out_a_booting_daemon(tmp_path):
    sock = str(tmp_path / "late.sock")
    svc = SchedulerService(
        lambda: ClusterBackend(_svc_cluster(), faults=None)
    )

    def late():
        time.sleep(0.4)
        serve(svc, sock)

    th = threading.Thread(target=late, daemon=True)
    th.start()
    # fail-fast path: nothing is listening yet
    with pytest.raises((FileNotFoundError, ConnectionRefusedError)):
        request(sock, {"op": "ping"})
    # the retrying client rides out the boot
    assert request_retry(sock, {"op": "ping"}, retries=8)["pong"] is True
    request_retry(sock, {"op": "shutdown"}, retries=8)
    th.join(timeout=5.0)
