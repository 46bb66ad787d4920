"""The benchmark's one traffic generator, driven by a traffic file.

A traffic file (``bench/traffic/<name>.json``) names an arrival process, an
application mix, the elastic settings and, optionally, a fault process:

    {"arrivals": {"kind": "bursty" | "poisson", "rate": jobs/s,
                  "jobs": n, "burst": max burst size (bursty only),
                  "shape_seed": s},
     "apps": {"family": "anchor_grow" | "three_family", "seed": s,
              "n_apps": k}  or  {"family": "config"},
     "elastic": null | {ElasticConfig fields},
     "faults": null | {FaultConfig fields}}

``faults`` is optional; absent or null, the run takes the program's
pre-fault path (``faults=None``).  A fault section names any of
``FaultConfig``'s fields; ``faults`` fills the rest from
``FAULT_DEFAULTS``, so the program and the reference see the same
complete numbers.  Its ``seed`` is fixed in the file, like
``shape_seed``: every run and every replay faces the same node, crash and
straggler streams, and ``--seed`` still only reorders the submissions.

Every replay offers the same set of submissions: the gaps, burst sizes and
apps are drawn once from ``shape_seed``, and the run's ``--seed`` with the
replay's index only shuffles their order.  So every seed offers the same
work (the same jobs of each app, the same span of arrival times), and a
run averages over as many orders as it has replays.

``family: config`` takes the application profiles frozen in the
configuration file; the two synthetic families build one profile per app
and hardware type from the configuration's per-chip slowdown.  Everything
here is plain numpy, copied from the program's own generators
(``repro.core.arrivals.poisson_stream``/``bursty_stream`` and the fleet
bench's ``synth_apps``/``synth_elastic_apps``) so that later changes to
the program cannot move the benchmark's inputs.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
# ``FaultConfig``'s documented defaults, copied so that a later change to
# the program's defaults cannot move a cell's fault process
FAULT_DEFAULTS = {
    "seed": 0, "node_mtbf_s": 0.0, "node_mttr_s": 600.0,
    "degrade_frac": 0.0, "degrade_units": 1, "job_mtbf_s": 0.0,
    "straggler_prob": 0.0, "straggler_factor": 1.5, "max_retries": 3,
    "retry_base_s": 30.0, "retry_mult": 2.0, "retry_cap_s": 1800.0,
    "restart_time": 15.0,
}

Arrival = Tuple[float, str, str]  # (t, instance name, app)


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as fh:
        return json.load(fh)


def faults(traffic: dict) -> Optional[dict]:
    """The traffic's fault section with every field stated, or None when
    it has none."""
    f = traffic.get("faults")
    if f is None:
        return None
    unknown = set(f) - set(FAULT_DEFAULTS)
    if unknown:
        raise ValueError(f"unknown fault fields {sorted(unknown)}")
    return {**FAULT_DEFAULTS, **f}


def poisson_stream(apps: Sequence[str], *, rate: float, n: int,
                   seed: int) -> List[Arrival]:
    """``n`` arrivals, exponential gaps with mean ``1/rate`` s, app drawn
    uniformly."""
    rng = np.random.default_rng(seed)
    t = 0.0
    out: List[Arrival] = []
    for i in range(n):
        t += float(rng.exponential(1.0 / rate))
        app = str(apps[int(rng.integers(len(apps)))])
        out.append((round(t, 6), f"{app}#{i}", app))
    return out


def bursty_stream(apps: Sequence[str], *, rate: float, n: int, burst: int,
                  seed: int) -> List[Arrival]:
    """~``n`` arrivals in bursts of 1..``burst`` same-app jobs submitted
    together; burst starts are Poisson at ``rate`` / mean burst size."""
    rng = np.random.default_rng(seed)
    mean_burst = (1 + burst) / 2.0
    t = 0.0
    out: List[Arrival] = []
    i = 0
    while i < n:
        t += float(rng.exponential(mean_burst / rate))
        size = min(int(rng.integers(1, burst + 1)), n - i)
        app = str(apps[int(rng.integers(len(apps)))])
        for _ in range(size):
            out.append((round(t, 6), f"{app}#{i}", app))
            i += 1
    return out


def three_family(slow: float, n_apps: int, seed: int) -> Dict[str, dict]:
    """Elastic {2,4,8}, rigid {8} and small {1,2} apps (the fleet bench's
    ``synth_apps``)."""
    rng = np.random.default_rng(seed)
    out = {}
    for i in range(n_apps):
        counts = (1, 2) if i % 3 == 0 else ((8,) if i % 3 == 1 else (2, 4, 8))
        t1 = float(rng.uniform(60.0, 240.0))
        alpha = float(rng.uniform(0.35, 0.95))
        beta = float(rng.uniform(0.6, 0.9))
        p0 = float(rng.uniform(250.0, 400.0))
        out[f"app{i}"] = {
            "runtime": {str(g): slow * t1 / g ** alpha for g in counts},
            "busy_power": {str(g): (p0 / slow ** 0.5) * g ** beta
                           for g in counts},
        }
    return out


def anchor_grow(slow: float, n_apps: int, seed: int) -> Dict[str, dict]:
    """Even apps: long strong-scaling {4,8} jobs worth growing mid-flight;
    odd apps: short rigid half-node anchors (the fleet bench's
    ``synth_elastic_apps``)."""
    rng = np.random.default_rng(seed)
    out = {}
    for i in range(n_apps):
        if i % 2 == 0:
            counts = (4, 8)
            t1 = float(rng.uniform(3600.0, 10800.0))
            alpha = float(rng.uniform(0.42, 0.52))
            beta = alpha - float(rng.uniform(0.10, 0.20))
            p0 = float(rng.uniform(250.0, 400.0))
            rt = {str(g): slow * t1 / g ** alpha for g in counts}
            bp = {str(g): (p0 / slow ** 0.5) * g ** beta for g in counts}
        else:
            t4 = float(rng.uniform(600.0, 1800.0))
            p0 = float(rng.uniform(250.0, 400.0))
            rt = {"4": slow * t4}
            bp = {"4": (p0 / slow ** 0.5) * 4 ** 0.7}
        out[f"app{i}"] = {"runtime": rt, "busy_power": bp}
    return out


FAMILIES = {"three_family": three_family, "anchor_grow": anchor_grow}


def profiles(config: dict, traffic: dict) -> Dict[str, Dict[str, dict]]:
    """chip name -> app -> profile dict (runtime, busy_power and, where the
    configuration has them, dram_util / freq_time / freq_power)."""
    mix = traffic["apps"]
    if mix["family"] == "config":
        return config["profiles"]
    make = FAMILIES[mix["family"]]
    return {
        chip: make(config["chip_slow"][chip], mix["n_apps"], mix["seed"])
        for chip in config["chip_cycle"]
    }


def submissions(traffic: dict, apps: Sequence[str]) -> List[Tuple[float, int, str]]:
    """The traffic's fixed set of submissions as (gap before it, jobs, app),
    in the order the generator drew them from ``shape_seed``."""
    arr = traffic["arrivals"]
    if arr["kind"] == "poisson":
        drawn = poisson_stream(apps, rate=arr["rate"], n=arr["jobs"],
                               seed=arr["shape_seed"])
    elif arr["kind"] == "bursty":
        drawn = bursty_stream(apps, rate=arr["rate"], n=arr["jobs"],
                              burst=arr["burst"], seed=arr["shape_seed"])
    else:
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    out: List[Tuple[float, int, str]] = []
    last = 0.0
    for t, _, app in drawn:
        if out and t == last and out[-1][2] == app and arr["kind"] == "bursty":
            out[-1] = (out[-1][0], out[-1][1] + 1, app)
        else:
            out.append((t - last, 1, app))
            last = t
    return out


def stream(traffic: dict, apps: Sequence[str], seed: int,
           replay: int = 0) -> List[Arrival]:
    """Replay ``replay`` of a run: the fixed submissions in an order drawn
    from (``seed``, ``replay``), each burst's jobs submitted together."""
    subs = submissions(traffic, apps)
    order = np.random.default_rng([seed, replay]).permutation(len(subs))
    out: List[Arrival] = []
    t = 0.0
    for k in order:
        gap, size, app = subs[int(k)]
        t += gap
        at = round(t, 6)
        for _ in range(size):
            out.append((at, f"{app}#{len(out)}", app))
    return out


def nodes(config: dict) -> List[dict]:
    """Node table: name, chip, units, domains, idle watts per unit.  Pods of
    ``pod_size`` consecutive nodes share one chip type, cycling through
    ``chip_cycle``."""
    cyc = config["chip_cycle"]
    out = []
    for i in range(config["nodes"]):
        chip = cyc[(i // config["pod_size"]) % len(cyc)]
        out.append({"name": f"n{i:04d}", "chip": chip,
                    "units": config["units"], "domains": config["domains"],
                    "idle_w": config["idle_w"][chip]})
    return out


def app_names(config: dict, traffic: dict) -> List[str]:
    """The app universe in the order the stream draws from it."""
    mix = traffic["apps"]
    if mix["family"] == "config":
        return list(config["apps"])
    return [f"app{i}" for i in range(mix["n_apps"])]
