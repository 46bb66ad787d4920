"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV at the end (scaffold contract).
Individual benchmarks are importable and runnable standalone:
    PYTHONPATH=src python -m benchmarks.bench_fig6_end2end
"""
from __future__ import annotations

import argparse
import os


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="skip the Oracle search")
    ap.add_argument("--quiet", action="store_true")
    args, _ = ap.parse_known_args()

    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    # Every jax-engine bench below runs in this process, which holds the
    # chip from its first kernel launch.  bench_service then spawns
    # ``python -m repro.cli daemon`` children: that is safe only while the
    # daemon stays on the numpy engine (cli.py builds its EcoSched with the
    # default ``vector`` engine and never imports JAX), since a second
    # process cannot open a chip this one holds.
    from benchmarks import (
        bench_cluster,
        bench_cluster_throughput,
        bench_decision_overhead,
        bench_dvfs,
        bench_elastic,
        bench_faults,
        bench_fleet,
        bench_forecast,
        bench_fig1_scaling,
        bench_fig2_tradeoff,
        bench_fig6_end2end,
        bench_fig9_perf_loss,
        bench_overhead,
        bench_roofline,
        bench_sensitivity,
        bench_service,
        bench_table2_choices,
        bench_tpu_pod,
    )
    from benchmarks.common import Csv

    csv = Csv()
    verbose = not args.quiet
    bench_fig1_scaling.run(csv, verbose=verbose)
    bench_fig2_tradeoff.run(csv, verbose=verbose)
    bench_fig6_end2end.run(
        csv, verbose=verbose, with_oracle=not args.quick, oracle_budget_s=20.0
    )
    bench_table2_choices.run(csv, verbose=verbose)
    bench_fig9_perf_loss.run(csv, verbose=verbose)
    bench_overhead.run(csv, verbose=verbose)
    decision = bench_decision_overhead.run(csv, verbose=verbose, smoke=args.quick)
    bench_roofline.run(csv, verbose=verbose)
    bench_tpu_pod.run(csv, verbose=verbose)
    bench_sensitivity.run(csv, verbose=verbose)
    bench_cluster.run(csv, verbose=verbose)
    bench_elastic.run(csv, verbose=verbose, smoke=args.quick)
    faults = bench_faults.run(csv, verbose=verbose, smoke=args.quick)
    forecast = bench_forecast.run(csv, verbose=verbose, smoke=args.quick)
    dvfs = bench_dvfs.run(csv, verbose=verbose, smoke=args.quick)
    throughput = bench_cluster_throughput.run(csv, verbose=verbose, smoke=args.quick)
    fleet = bench_fleet.run(csv, verbose=verbose, smoke=args.quick)
    bench_service.run(csv, verbose=verbose, smoke=args.quick)

    # perf-trajectory snapshots (ISSUE 3/5): decision overhead + throughput,
    # and the forecast-vs-eager EDP rows.  Only full runs refresh the
    # committed baselines (benchmarks/, not the gitignored results/) —
    # smoke numbers are a tripwire, not a trajectory.
    if not args.quick:
        json_path = os.path.join(
            os.path.dirname(__file__), "BENCH_decision.json"
        )
        bench_cluster_throughput.write_json(json_path, decision, throughput)
        forecast_path = os.path.join(
            os.path.dirname(__file__), "BENCH_forecast.json"
        )
        bench_forecast.write_json(forecast_path, forecast)
        dvfs_path = os.path.join(os.path.dirname(__file__), "BENCH_dvfs.json")
        bench_dvfs.write_json(dvfs_path, dvfs)
        faults_path = os.path.join(
            os.path.dirname(__file__), "BENCH_faults.json"
        )
        bench_faults.write_json(faults_path, faults)
        fleet_path = os.path.join(os.path.dirname(__file__), "BENCH_fleet.json")
        bench_fleet.write_json(fleet_path, fleet)
        if verbose:
            print(
                f"perf baselines -> {json_path}, {forecast_path}, "
                f"{dvfs_path}, {faults_path}, {fleet_path}"
            )

    print("\nname,us_per_call,derived")
    csv.emit()


if __name__ == "__main__":
    main()
