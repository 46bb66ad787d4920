"""Builds the system under test from a configuration and a traffic file.

Every cell drives the program the same way: a ``Cluster`` of the
configuration's nodes behind the hierarchical region -> pod -> node
dispatcher, one ``EcoSched`` per node on the configuration's engine and
weights, all pooling one ``DecisionCache``, opened as a ``ClusterRun``.
"""
from __future__ import annotations

from typing import Dict

from bench import traffic as T


def job_profiles(apps: Dict[str, dict]):
    from repro.core import JobProfile

    def ints(d):
        return {int(k): float(v) for k, v in d.items()}

    return {
        a: JobProfile(
            name=a,
            runtime=ints(p["runtime"]),
            busy_power=ints(p["busy_power"]),
            dram_util=ints(p.get("dram_util", {})),
            freq_time=ints(p.get("freq_time", {})),
            freq_power=ints(p.get("freq_power", {})),
        )
        for a, p in apps.items()
    }


def cluster(config: dict, profiles: Dict[str, Dict[str, dict]]):
    """A fresh ``Cluster`` with a fresh shared ``DecisionCache``."""
    from repro.core import (Cluster, DecisionCache, EcoSched,
                            EnergyAwareDispatcher, HierarchicalDispatcher,
                            NodeSpec, ProfiledPerfModel)
    from repro.roofline.hw import CHIPS

    sched = config["scheduler"]
    truth = {chip: job_profiles(apps) for chip, apps in profiles.items()}
    cache = DecisionCache()

    def policy_for(spec, node_truth):
        return EcoSched(
            ProfiledPerfModel(node_truth, noise=0.0, seed=1),
            lam=sched["lam"], tau=sched["tau"], lam_f=sched["lam_f"],
            window=sched["window"], engine=sched["engine"], cache=cache,
        )

    specs = [
        NodeSpec(n["name"], CHIPS[n["chip"]], units=n["units"],
                 domains=n["domains"])
        for n in T.nodes(config)
    ]
    return Cluster(
        specs,
        truth_for=lambda spec: truth[spec.chip.name],
        policy_for=policy_for,
        dispatcher=HierarchicalDispatcher(
            EnergyAwareDispatcher(), pod_size=config["pod_size"],
            pods_per_region=config["pods_per_region"],
        ),
    )


def elastic_config(traffic: dict):
    from repro.core import ElasticConfig

    e = traffic.get("elastic")
    return None if e is None else ElasticConfig(**e)


def fault_config(traffic: dict):
    """The traffic's ``FaultConfig``, or None when it has no fault section."""
    from repro.core import FaultConfig

    f = T.faults(traffic)
    return None if f is None else FaultConfig(**f)
