"""Fleet scale-out sweep — hierarchical dispatch at 64/256/1024 nodes.

ISSUE 9's regime: one region-scale fleet of rack-homogeneous pods (16
nodes per pod, chips cycling H100/A100/V100 across pods) under bursty
arrivals heavy enough to keep per-node queues nonempty.  Each case runs
the same stream through

  * ``flat`` — ``EnergyAwareDispatcher`` scanning every node per arrival
    (the PR 3 reference path, kept as the parity oracle), and
  * ``hier`` — ``HierarchicalDispatcher(EnergyAwareDispatcher())``
    pruning region -> pod -> node via the ``FleetIndex`` pod summary
    tables (admissible bounds, so pruning is exact).

and hard-asserts the two schedules are bit-identical before reporting
events/s (events = routing decisions + per-job launch/complete pairs).
The workload mixes elastic apps with rigid {8}- and {1,2}-mode apps so
the fragmentation gauge (``ClusterResult.fragmentation``, Lettich-style
unusable-GPU fraction over the pending mix) has signal; its rollup is
reported per case.

Full mode also runs a cross-node batched-kernel parity case: a jax-engine
fleet where same-instant bursts are scored in one ``score_reduce_batch``
launch (``stage_served > 0`` asserted) against the staging-disabled solo
path — schedules must match bitwise.

ISSUE 10 adds the COMPLETE-path sweep: an anchor+grow elastic workload
(short rigid 4-unit anchors whose completions free half a node next to a
long strong-scaling {4,8} job — every anchor completion is a resize
opportunity, and burst arrivals align those completions into same-instant
COMPLETE bursts across nodes) run on a jax-engine fleet twice:

  * ``batched`` — the full fast COMPLETE path: one
    ``score_reduce_multi`` launch per resize table, cross-node
    COMPLETE-burst staging, and a fleet-shared ``DecisionCache``,
  * ``solo``    — the pre-batching reference exactly as it shipped:
    ``resize_batch=False`` (one kernel launch per running job per
    completion), the ``prepare_complete`` hook detached, and private
    per-node caches.

Schedules must match bit for bit (records, energy); the batched leg
must beat the solo leg by ``MIN_ELASTIC_SPEEDUP`` in events/s at the
gate scale.  Per-phase decision-time breakdowns
(dispatch/launch/resize/migrate/stage) are reported for both legs.

    PYTHONPATH=src python -m benchmarks.bench_fleet [--smoke]

Acceptance gates (full mode): >= 10k events/s at 256 nodes on the best
dispatcher with flat/hier schedule parity at every scale, and >= 2x
batched-vs-solo events/s on the elastic-on case at 256 nodes with
batched/solo schedule parity at every elastic scale.
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmarks.common import Csv
from repro.core import (
    Cluster,
    DecisionCache,
    EcoSched,
    ElasticConfig,
    EnergyAwareDispatcher,
    HierarchicalDispatcher,
    JobProfile,
    NodeSpec,
    ProfiledPerfModel,
    bursty_stream,
)
from repro.roofline.hw import A100, H100, V100

M, K = 8, 2  # per-node geometry: 8 units, 2 NUMA domains
N_APPS = 8
APP_SEED = 3
STREAM_SEED = 7
POD_SIZE = 16
PODS_PER_REGION = 8
LAM, TAU = 0.35, 0.45
CHIP_CYCLE = [H100, A100, V100]  # rack-homogeneous: one chip per pod
# relative service speed per chip — older racks run the same app slower
# (and at worse unit-energy), so pod lower bounds actually discriminate
CHIP_SLOW = {"h100": 1.0, "a100": 1.6, "v100": 2.6}

# (nodes, rate jobs/s, jobs): load scales with fleet size so queues stay
# bursty-nonempty — the regime where dispatch cost dominates
FULL_SWEEP = [
    (64, 1.2, 512),
    (256, 4.8, 2048),
    (1024, 19.2, 4096),
]
SMOKE_SWEEP = [(40, 1.2, 160)]  # 2.5 pods: exercises ragged geometry
GATE_NODES = 256
MIN_EVENTS_PER_S = 10_000.0  # full-mode gate at GATE_NODES

# COMPLETE-path sweep (ISSUE 10): rate scales with fleet size like the
# arrival sweep, but slower apps (hours, not minutes) so mid-flight
# resizes clear the min-gain guard
ELASTIC_APP_SEED = 5
ELASTIC_SWEEP = [(64, 0.6, 512), (256, 2.4, 2048)]
ELASTIC_SMOKE = [(40, 0.6, 160)]
MIN_ELASTIC_SPEEDUP = 2.0  # batched vs pre-PR per-job events/s at gate


def synth_apps(chip, n_apps: int = N_APPS, seed: int = APP_SEED) -> Dict[str, JobProfile]:
    """Seeded app mix with three mode families: elastic {2,4,8}, rigid
    {8}, and small {1,2}.  Rigid apps strand sub-8 free levels behind
    small-app launches — that is what the fragmentation gauge measures."""
    s = CHIP_SLOW[chip.name]
    rng = np.random.default_rng(seed)
    out = {}
    for i in range(n_apps):
        counts = (1, 2) if i % 3 == 0 else ((8,) if i % 3 == 1 else (2, 4, 8))
        t1 = float(rng.uniform(60.0, 240.0))
        alpha = float(rng.uniform(0.35, 0.95))
        beta = float(rng.uniform(0.6, 0.9))
        p0 = float(rng.uniform(250.0, 400.0))
        out[f"app{i}"] = JobProfile(
            name=f"app{i}",
            runtime={g: s * t1 / g ** alpha for g in counts},
            busy_power={g: (p0 / s ** 0.5) * g ** beta for g in counts},
        )
    return out


TRUTH = {chip.name: synth_apps(chip) for chip in CHIP_CYCLE}


def fleet(n_nodes: int, dispatcher) -> Cluster:
    def policy_for(spec, truth):
        return EcoSched(
            ProfiledPerfModel(truth, noise=0.0, seed=1),
            lam=LAM, tau=TAU, window=8, engine="vector", cache=True,
        )

    return Cluster(
        [
            NodeSpec(
                f"n{i:04d}",
                CHIP_CYCLE[(i // POD_SIZE) % len(CHIP_CYCLE)],
                units=M,
                domains=K,
            )
            for i in range(n_nodes)
        ],
        truth_for=lambda spec: TRUTH[spec.chip.name],
        policy_for=policy_for,
        dispatcher=dispatcher,
    )


def _stream(rate: float, n_jobs: int):
    return bursty_stream(
        [f"app{i}" for i in range(N_APPS)],
        rate=rate, n=n_jobs, seed=STREAM_SEED, burst=16,
    )


def _dispatchers() -> Dict[str, object]:
    return {
        "flat": EnergyAwareDispatcher(),
        "hier": HierarchicalDispatcher(
            EnergyAwareDispatcher(),
            pod_size=POD_SIZE,
            pods_per_region=PODS_PER_REGION,
        ),
    }


def _schedule_of(res) -> List[Tuple]:
    return [(r.job, r.node, r.g, r.start) for r in res.records]


def measure_case(
    n_nodes: int, rate: float, n_jobs: int, *, repeats: int = 2
) -> Dict[str, float]:
    out: Dict[str, float] = {"nodes": n_nodes, "rate": rate, "jobs": n_jobs}
    schedules = {}
    # interleave the repeats so a noisy-neighbor slowdown hits both
    # dispatchers equally instead of biasing whichever ran during it
    best: Dict[str, Tuple] = {
        name: (float("inf"), None) for name in _dispatchers()
    }
    for _ in range(repeats):
        for name, disp in _dispatchers().items():
            stream = _stream(rate, n_jobs)
            cl = fleet(n_nodes, disp)
            t0 = time.perf_counter()
            res = cl.simulate(stream)
            elapsed = time.perf_counter() - t0
            if elapsed < best[name][0]:
                best[name] = (elapsed, res)
    for name, (t_best, res) in best.items():
        schedules[name] = _schedule_of(res)
        # launches + completions are fleet work too: each job's lifecycle
        # transits the event loop twice beyond its routing decision
        events = res.decision_events + 2 * n_jobs
        out[f"{name}_s"] = t_best
        out[f"{name}_events_per_s"] = events / t_best
        out[f"{name}_energy_J"] = res.total_energy
    out["frag_time_avg"] = best["flat"][1].fragmentation["time_avg"]
    out["frag_peak"] = best["flat"][1].fragmentation["peak"]
    # parity gate: pod/region pruning uses admissible lower bounds, so the
    # hierarchical route must equal the flat scan, bit for bit (hard assert
    # — a fast-but-diverged dispatcher would be meaningless)
    assert schedules["hier"] == schedules["flat"], (
        f"hierarchical dispatch diverged from flat at {n_nodes} nodes"
    )
    out["speedup"] = out["flat_s"] / out["hier_s"]
    return out


def synth_elastic_apps(
    chip, n_apps: int = N_APPS, seed: int = ELASTIC_APP_SEED
) -> Dict[str, JobProfile]:
    """Anchor+grow mix for the COMPLETE-path sweep: even apps are long
    strong-scaling {4,8} jobs worth preempt-resizing to the full node
    mid-flight; odd apps are short rigid 4-unit anchors.  An anchor
    completion frees the other half of a node hosting a grow job — every
    such completion is a resize opportunity, and burst arrivals align
    anchor completions into same-instant COMPLETE bursts across nodes."""
    s = CHIP_SLOW[chip.name]
    rng = np.random.default_rng(seed)
    out = {}
    for i in range(n_apps):
        if i % 2 == 0:  # grow app: near-linear scaling, cheap extra units
            counts = (4, 8)
            t1 = float(rng.uniform(3600.0, 10800.0))
            alpha = float(rng.uniform(0.42, 0.52))
            beta = alpha - float(rng.uniform(0.10, 0.20))
            p0 = float(rng.uniform(250.0, 400.0))
            rt = {g: s * t1 / g ** alpha for g in counts}
            bp = {g: (p0 / s ** 0.5) * g ** beta for g in counts}
        else:  # anchor app: short, rigid half-node filler
            t4 = float(rng.uniform(600.0, 1800.0))
            p0 = float(rng.uniform(250.0, 400.0))
            rt = {4: s * t4}
            bp = {4: (p0 / s ** 0.5) * 4 ** 0.7}
        out[f"app{i}"] = JobProfile(name=f"app{i}", runtime=rt, busy_power=bp)
    return out


ELASTIC_TRUTH = {chip.name: synth_elastic_apps(chip) for chip in CHIP_CYCLE}


def elastic_fleet(
    n_nodes: int,
    dispatcher,
    *,
    resize_batch: bool = True,
    shared_cache: bool = True,
    launch_share: bool = True,
    engine: str = "jax",
) -> Cluster:
    """jax-engine fleet over the anchor+grow mix — the engine whose
    per-job resize loop pays one kernel launch per candidate, i.e. the
    path the batched plane exists to collapse.  By default all policies
    pool one ``DecisionCache``: keys are name-free, so
    identically-shaped nodes serve each other's first-sight
    enumerations (a private cache never warms when each node only hosts
    a handful of jobs).  ``shared_cache=False`` reverts to private
    per-node caches and ``launch_share=False`` disables the tie-frontier
    launch memo — together the pre-PR configuration the solo leg
    measures.  ``engine="vector"`` runs the same fleet on the numpy
    engine, the reference the chip smoke compares against."""
    cache = DecisionCache() if shared_cache else True

    def policy_for(spec, truth):
        return EcoSched(
            ProfiledPerfModel(truth, noise=0.0, seed=1),
            lam=LAM, tau=TAU, window=8, engine=engine, cache=cache,
            resize_batch=resize_batch, launch_share=launch_share,
        )

    return Cluster(
        [
            NodeSpec(
                f"n{i:04d}",
                CHIP_CYCLE[(i // POD_SIZE) % len(CHIP_CYCLE)],
                units=M,
                domains=K,
            )
            for i in range(n_nodes)
        ],
        truth_for=lambda spec: ELASTIC_TRUTH[spec.chip.name],
        policy_for=policy_for,
        dispatcher=dispatcher,
    )


def elastic_schedule_of(res) -> List[Tuple]:
    return [
        (r.job, r.node, r.g, r.f, r.start, r.end, r.kind, r.segment)
        for r in res.records
    ]


def run_elastic(
    n_nodes: int,
    rate: float,
    n_jobs: int,
    *,
    resize_batch: bool,
    staged: bool,
    shared_cache: bool,
    launch_share: bool = True,
    engine: str = "jax",
):
    """One elastic leg; returns (result, elapsed_s, run)."""
    from repro.core.events import EVT_ARRIVAL

    arrivals = sorted(_stream(rate, n_jobs), key=lambda a: a.t)
    cl = elastic_fleet(
        n_nodes,
        _dispatchers()["hier"],
        resize_batch=resize_batch,
        shared_cache=shared_cache,
        launch_share=launch_share,
        engine=engine,
    )
    run = cl.open_run(
        apps=[f"app{i}" for i in range(N_APPS)],
        jobs=[(a.name, a.app) for a in arrivals],
        elastic=ElasticConfig(resize=True, resize_before_backfill=True),
    )
    if not staged:
        run.loop.prepare_complete = None
    t0 = time.perf_counter()
    for a in arrivals:
        if a.t <= 0.0:
            run.route(a, 0.0)
        else:
            run.loop.queue.push(a.t, EVT_ARRIVAL, a)
    run.loop.run()
    res = run.finalize()
    elapsed = time.perf_counter() - t0
    return res, elapsed, run


def policy_sum(run, attr: str) -> int:
    """One EcoSched counter summed over a run's per-node policies."""
    return sum(getattr(s.policy, attr, 0) for s in run.sims.values())


def elastic_case(
    n_nodes: int, rate: float, n_jobs: int, *, repeats: int = 2
) -> Dict[str, float]:
    """Batched vs per-job COMPLETE path on the same workload: hard
    schedule parity, then the end-to-end events/s speedup.  The solo
    leg is the pre-PR configuration in full (per-job resize loop, no
    COMPLETE staging, private caches, no tie-frontier launch sharing);
    the batched leg is everything this PR's fast path adds.  None of
    those knobs can move a schedule (every key is name-free and each
    decision is a pure function of its key), and the parity asserts
    below re-prove that on this workload."""
    out: Dict[str, float] = {"nodes": n_nodes, "rate": rate, "jobs": n_jobs}
    legs = {
        "batched": dict(resize_batch=True, staged=True, shared_cache=True),
        "solo": dict(
            resize_batch=False, staged=False, shared_cache=False,
            launch_share=False,
        ),
    }
    best = {name: (float("inf"), None, 0) for name in legs}
    for _ in range(repeats):
        for name, kw in legs.items():
            res, elapsed, run = run_elastic(n_nodes, rate, n_jobs, **kw)
            if elapsed < best[name][0]:
                served = policy_sum(run, "resize_stage_served")
                best[name] = (elapsed, res, served)
    assert elastic_schedule_of(best["batched"][1]) == elastic_schedule_of(
        best["solo"][1]
    ), f"batched COMPLETE path diverged from per-job loop at {n_nodes} nodes"
    assert best["batched"][1].total_energy == best["solo"][1].total_energy
    events = best["batched"][1].decision_events + 2 * n_jobs
    for name, (t_best, res, served) in best.items():
        out[f"{name}_s"] = t_best
        out[f"{name}_events_per_s"] = events / t_best
        for k, v in res.decision_phases.items():
            out[f"{name}_phase_{k}_s"] = v
    out["resizes"] = best["batched"][1].resizes
    out["resize_stage_served"] = best["batched"][2]
    # the headline: the fast COMPLETE path (batched resize plane +
    # burst staging + shared cache) vs the pre-PR per-job loop, end to
    # end — phase columns above show where the time moved
    out["speedup"] = out["solo_s"] / out["batched_s"]
    return out


def jax_parity_case(n_jobs: int = 48) -> Dict[str, float]:
    """Cross-node batched scoring vs the solo per-node kernel path: same
    4-node jax-engine fleet, same bursty stream, staging on vs off."""
    from repro.core import calibration as C
    from repro.core.events import EVT_ARRIVAL

    apps = C.build_system("h100")

    def make(policies):
        def policy_for(spec, truth):
            pol = EcoSched(
                ProfiledPerfModel(truth, noise=0.0, seed=1),
                lam=LAM, tau=TAU, engine="jax",
            )
            policies.append(pol)
            return pol

        return Cluster(
            [NodeSpec(f"n{i:03d}", H100, units=8, domains=2) for i in range(4)],
            truth_for=lambda spec: apps,
            policy_for=policy_for,
            dispatcher=EnergyAwareDispatcher(),
        )

    stream = bursty_stream(list(C.APP_ORDER), rate=0.25, n=n_jobs, seed=11, burst=6)
    pols: List[EcoSched] = []
    t0 = time.perf_counter()
    batched = make(pols).simulate(stream)
    t_batched = time.perf_counter() - t0
    served = sum(p.stage_served for p in pols)
    assert served > 0, "no decision was served from the cross-node batch"
    # solo: same fleet with the staging hook detached before the run
    solo_cl = make([])
    arrivals = sorted(stream, key=lambda a: a.t)
    run = solo_cl.open_run(
        apps=sorted({a.app for a in arrivals}),
        jobs=[(a.name, a.app) for a in arrivals],
    )
    run.loop.prepare_batch = None
    t0 = time.perf_counter()
    for a in arrivals:
        if a.t <= 0.0:
            run.route(a, 0.0)
        else:
            run.loop.queue.push(a.t, EVT_ARRIVAL, a)
    run.loop.run()
    solo = run.finalize()
    t_solo = time.perf_counter() - t0
    assert _schedule_of(batched) == _schedule_of(solo), (
        "cross-node batched scoring changed the schedule"
    )
    assert batched.total_energy == solo.total_energy
    return {
        "jobs": n_jobs,
        "stage_served": served,
        "batched_s": t_batched,
        "solo_s": t_solo,
        "schedule_identical": True,
    }


def run(csv: Csv, verbose: bool = True, smoke: bool = False) -> Dict:
    sweep = SMOKE_SWEEP if smoke else FULL_SWEEP
    results: Dict = {"cases": {}}
    for n_nodes, rate, n_jobs in sweep:
        r = measure_case(n_nodes, rate, n_jobs, repeats=1 if smoke else 2)
        results["cases"][n_nodes] = r
        if verbose:
            print(
                f"fleet nodes={n_nodes:4d} rate={rate:5.2f}/s jobs={n_jobs}: "
                f"flat {r['flat_events_per_s']:7.0f} ev/s  "
                f"hier {r['hier_events_per_s']:7.0f} ev/s "
                f"({r['speedup']:4.2f}x)  frag avg {r['frag_time_avg']:.3f} "
                f"peak {r['frag_peak']:.2f}  parity OK"
            )
        csv.add(
            f"fleet_n{n_nodes}",
            1e6 / r["hier_events_per_s"],
            f"speedup={r['speedup']:.2f}x;frag={r['frag_time_avg']:.3f}",
        )
    esweep = ELASTIC_SMOKE if smoke else ELASTIC_SWEEP
    results["elastic"] = {}
    for n_nodes, rate, n_jobs in esweep:
        er = elastic_case(n_nodes, rate, n_jobs, repeats=1 if smoke else 2)
        results["elastic"][n_nodes] = er
        if verbose:
            print(
                f"fleet elastic nodes={n_nodes:4d} rate={rate:5.2f}/s "
                f"jobs={n_jobs}: batched {er['batched_events_per_s']:7.0f} "
                f"ev/s  solo {er['solo_events_per_s']:7.0f} ev/s "
                f"({er['speedup']:4.2f}x)  "
                f"(resizes={er['resizes']}, "
                f"staged={er['resize_stage_served']})  parity OK"
            )
        csv.add(
            f"fleet_elastic_n{n_nodes}",
            1e6 / er["batched_events_per_s"],
            f"speedup={er['speedup']:.2f}x;"
            f"resizes={er['resizes']}",
        )
    if not smoke:
        jp = jax_parity_case()
        results["jax_parity"] = jp
        if verbose:
            print(
                f"fleet jax batch: {jp['stage_served']} decisions served "
                f"from cross-node launches, schedule identical to solo"
            )
    return results


def write_json(path: str, results: Dict) -> None:
    """Fleet-scale perf snapshot (BENCH_fleet.json) — committed trajectory;
    future PRs diff against these numbers."""
    payload = {
        "schema": "bench_fleet/v1",
        "geometry": {
            "M": M,
            "K": K,
            "pod_size": POD_SIZE,
            "pods_per_region": PODS_PER_REGION,
            "chips": [c.name for c in CHIP_CYCLE],
        },
        "gate": {
            "nodes": GATE_NODES,
            "min_events_per_s": MIN_EVENTS_PER_S,
            "min_elastic_speedup": MIN_ELASTIC_SPEEDUP,
        },
        "cases": {str(k): v for k, v in results["cases"].items()},
        "elastic": {
            str(k): v for k, v in results.get("elastic", {}).items()
        },
    }
    if "jax_parity" in results:
        payload["jax_parity"] = results["jax_parity"]
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--smoke", action="store_true",
        help="one small ragged-pod case + parity gate only (CI tripwire)",
    )
    ap.add_argument(
        "--json", metavar="PATH",
        help="also write a BENCH_fleet.json baseline snapshot",
    )
    args = ap.parse_args()
    c = Csv()
    res = run(c, smoke=args.smoke)
    c.emit()
    if args.json:
        write_json(args.json, res)
        print(f"baseline JSON -> {args.json}")
    if not args.smoke:
        gate = res["cases"][GATE_NODES]
        ev = max(gate["flat_events_per_s"], gate["hier_events_per_s"])
        if ev < MIN_EVENTS_PER_S:
            raise SystemExit(
                f"fleet throughput target missed: {ev:.0f} ev/s < "
                f"{MIN_EVENTS_PER_S:.0f} at {GATE_NODES} nodes"
            )
        egate = res["elastic"][GATE_NODES]
        if egate["speedup"] < MIN_ELASTIC_SPEEDUP:
            raise SystemExit(
                f"fast COMPLETE path target missed: "
                f"{egate['speedup']:.2f}x < "
                f"{MIN_ELASTIC_SPEEDUP:.1f}x at {GATE_NODES} nodes"
            )
