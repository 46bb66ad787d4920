#!/usr/bin/env python3
"""Benchmark entry point: one run of one cell on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Refuses to run, with no result line, unless JAX's first device is a TPU,
the cell's chips are there and the score-reduce kernels resolve to compiled
``pallas`` mode.  Set-up (JAX and TPU start-up, fleet build, compiles or
compile-cache loads of every shape bucket the cell lists, one warm-up
replay) counts as ``setup_s``; then the window replays the cell's stream
for ``--seconds``.  ``--trace 1`` records the benchmark's spans and a
profiler trace of the window and reports the per-layer metrics instead of
the end-to-end ones.

Earlier lines report launches per reduction, compiles inside the window
(expected 0), python fallbacks, replays, events and instants; the last
lines on standard error are each number the check compared, with its
limit.  The last line on standard output is the JSON result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def refuse(msg: str) -> int:
    print(f"bench: refused: {msg}", file=sys.stderr)
    return 2


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, replace=None) -> int:
    """``replace`` is for the control script alone: it swaps what runs
    under the three reduction entry points."""
    args = parse(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        return refuse(f"no program under {os.path.join(ROOT, 'src')}")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness as H
    from bench import report

    cell = H.load_cell(args.workload, ROOT)
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return refuse(f"JAX's first device is {dev.platform}, not a TPU")
    chips = cell["workload"]["chips"]
    if len(devices) < chips:
        return refuse(f"{len(devices)} chips, the cell needs {chips}")
    from bench.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    from repro.kernels import score_reduce as sr

    mode = sr.backend_mode()
    if mode != "pallas":
        return refuse(f"kernel mode resolves to {mode!r}, not 'pallas'")
    print(f"bench: device={dev.device_kind} count={len(devices)} mode={mode} "
          f"cache_dir={cache_dir}", file=sys.stderr)

    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(ROOT, ".bench_trace", args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
    res = H.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                     trace_dir=trace_dir, replace=replace, t_start=T_START)
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    trace = None
    if trace_dir is not None:
        from bench import trace as TR

        trace = TR.reduce(trace_dir)
        shutil.rmtree(os.path.join(ROOT, ".bench_trace"), ignore_errors=True)
    line, stderr_lines = report.result(cell, res, device, trace)
    for ln in stderr_lines:
        print(ln, file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
