#!/usr/bin/env python3
"""Drive the scheduler's device path once on one TPU chip and check it.

    python3 chip_smoke.py

In one process, spawning nothing:

1. Refuses to run unless JAX's first device is a TPU and the score-reduce
   kernels resolve to compiled ``pallas`` mode (``REPRO_KERNELS=ref`` or
   ``interpret`` is refused).
2. Compiles each of the three jitted Eq. (1) reductions at a shape the
   fleet run uses and checks that the compiled program holds the Pallas
   kernel (``tpu_custom_call``), so a jnp reference cannot stand in.
3. Replays ``bench_fleet``'s elastic fleet at 256 nodes x 8 GPUs (2,048
   GPUs, the size of the Philly cluster in Jeon et al., ATC 2019) under
   2,048 bursty jobs through ``Cluster.open_run`` on ``engine="jax"``,
   twice (cold, then with warm jit caches), and once on
   ``engine="vector"``, the float64 numpy reference.  Records and total
   energy must be bit-identical.  Every kernel launch is also checked
   against a float64 numpy argmin of the same request; when the schedules
   diverge, the first diverging record and the first kernel/reference
   disagreement (both rows, both scores) are printed.

Earlier lines report the kernel mode, the compiled kernels, launches per
reduction, staged decisions, python fallbacks, host wall time (cold and
warm), compiles and persistent-cache hits, and the cache directory.  The
last line is the JSON result; it is printed only when every check passed.
"""
from __future__ import annotations

import contextlib
import json
import os
import sys
from unittest import mock

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

NODES, RATE, JOBS = 256, 2.4, 2048  # bench_fleet.ELASTIC_SWEEP's gate case
REDUCTIONS = ("score_reduce", "score_reduce_batch", "score_reduce_multi")


class SmokeFailure(Exception):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


class CompileCounter:
    """Backend compile requests and persistent-cache hits, from JAX's
    monitoring events; a request the cache did not serve is a compile."""

    def __init__(self):
        import jax

        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.requests, self.hits

    def since(self, snap) -> str:
        req, hits = self.requests - snap[0], self.hits - snap[1]
        return f"compiles={req - hits} cache_hits={hits}"


def f64_best(req):
    """Float64 numpy Eq. (1) scores and tie-broken argmin of one kernel
    request (min score, then max total count, then first row)."""
    dev = np.asarray(req["dev"], dtype=np.float64)
    g = np.asarray(req["g"], dtype=np.float64)
    B = dev.shape[0]
    n_eff = np.maximum(np.asarray(req["n"], dtype=np.float64).reshape(B), 1.0)
    f = req.get("f")
    fsum = 0.0 if f is None else np.asarray(f, dtype=np.float64).sum(axis=1)
    bias = req.get("bias")
    tot = g.sum(axis=1)
    s = (
        dev.sum(axis=1) / n_eff
        + req["lam"] * (req["g_free"] - tot) / req["M"]
        + req.get("lam_f", 0.0) * fsum / n_eff
        + (0.0 if bias is None else np.asarray(bias, dtype=np.float64))
    )
    mask = req.get("mask")
    if mask is not None:
        s = np.where(np.asarray(mask, dtype=bool).reshape(B), s, np.inf)
    if B == 0 or not np.isfinite(s).any():
        return s, -1
    tie = s == s.min()
    best = np.flatnonzero(tie & (tot == tot[tie].max()))[0]
    return s, int(best)


@contextlib.contextmanager
def audited_launches():
    """Count each reduction's launches and compare every request's argmin
    with :func:`f64_best`.  Callers import the reductions from their
    module at call time, so patching the module attributes sees all."""
    from repro.kernels import score_reduce as sr

    audit = {"launches": dict.fromkeys(REDUCTIONS, 0), "mismatches": 0,
             "first": None}

    def wrap(name, fn):
        def call(*args, **kw):
            audit["launches"][name] += 1
            out = fn(*args, **kw)
            if name == "score_reduce":
                reqs, outs = [dict(dev=args[0], g=args[1], n=args[2], **kw)], [out]
            else:
                reqs, outs = args[0], out
            for req, (scores, best) in zip(reqs, outs):
                s64, b64 = f64_best(req)
                if best != b64:
                    audit["mismatches"] += 1
                    if audit["first"] is None:
                        rows = [r for r in (best, b64) if r >= 0]
                        audit["first"] = dict(
                            reduction=name, kernel_row=best, f64_row=b64,
                            f32_scores={r: float(scores[r]) for r in rows},
                            f64_scores={r: float(s64[r]) for r in rows},
                        )
            return out
        return call

    with contextlib.ExitStack() as stack:
        for name in REDUCTIONS:
            stack.enter_context(
                mock.patch.object(sr, name, wrap(name, getattr(sr, name)))
            )
        yield audit


def compile_kernels() -> None:
    """Lower and compile each jitted reduction at one shape of the fleet
    run and require the Pallas kernel in the compiled program."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import score_reduce as sr

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    b, s, d, w = 256, 8, 16, 8
    width = 3 * s + 8  # dev | g | f planes, then eight per-row columns
    lowered = {
        "score_reduce": sr._reduce_jit.lower(sds(b, width), mode="pallas"),
        "score_reduce_batch": sr._reduce_batch_jit.lower(
            sds(d, b, width), mode="pallas"
        ),
        "score_reduce_multi": sr._reduce_multi_jit.lower(
            sds(b, width), n_windows=w, mode="pallas"
        ),
    }
    for name, low in lowered.items():
        text = low.compile().as_text()
        check("tpu_custom_call" in text, f"{name}: no Pallas kernel compiled")
        print(f"chip_smoke: compiled {name}: tpu_custom_call present")


def run_fleet(engine: str):
    from benchmarks import bench_fleet as bf

    res, elapsed, run = bf.run_elastic(
        NODES, RATE, JOBS, resize_batch=True, staged=True, shared_cache=True,
        engine=engine,
    )
    counters = {
        k: bf.policy_sum(run, k)
        for k in ("stage_served", "resize_stage_served", "python_fallbacks")
    }
    return res, elapsed, counters, bf.elastic_schedule_of(res)


def main() -> int:
    import jax

    dev = jax.devices()[0]
    check(dev.platform == "tpu", f"no TPU: JAX's first device is {dev.platform}")

    from repro.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    from repro.kernels import score_reduce as sr

    mode = sr.backend_mode()
    check(mode == "pallas", f"kernel mode resolves to {mode!r}, not 'pallas'")
    print(f"chip_smoke: device={dev.device_kind} count={len(jax.devices())} "
          f"mode={mode} cache_dir={cache_dir}")

    compiles = CompileCounter()
    snap = compiles.snapshot()
    compile_kernels()
    print(f"chip_smoke: kernel compile check: {compiles.since(snap)}")

    legs = {}
    for leg in ("cold", "warm"):
        snap = compiles.snapshot()
        with audited_launches() as audit:
            res, elapsed, counters, sched = run_fleet("jax")
        legs[leg] = (sched, res.total_energy, audit["first"])
        print(
            f"chip_smoke: jax {leg}: wall_s={elapsed} records={len(sched)} "
            f"total_energy={res.total_energy!r} resizes={res.resizes} "
            f"launches={audit['launches']} "
            f"kernel_vs_f64_argmin_mismatches={audit['mismatches']} "
            + " ".join(f"{k}={v}" for k, v in counters.items())
            + f" {compiles.since(snap)}"
        )
        for name, n in audit["launches"].items():
            check(n > 0, f"{name} never launched ({leg})")
        check(counters["python_fallbacks"] == 0,
              f"{counters['python_fallbacks']} decisions fell back to python")
    snap = compiles.snapshot()
    vres, velapsed, vcounters, vsched = run_fleet("vector")
    print(
        f"chip_smoke: vector: wall_s={velapsed} records={len(vsched)} "
        f"total_energy={vres.total_energy!r} resizes={vres.resizes} "
        f"python_fallbacks={vcounters['python_fallbacks']} "
        f"{compiles.since(snap)}"
    )
    for leg, (sched, energy, first) in legs.items():
        if sched != vsched or energy != vres.total_energy:
            i = next(
                (k for k, (a, b) in enumerate(zip(sched, vsched)) if a != b),
                min(len(sched), len(vsched)),
            )
            print(f"chip_smoke: {leg} diverged at record {i}: "
                  f"jax={sched[i] if i < len(sched) else None} "
                  f"vector={vsched[i] if i < len(vsched) else None} "
                  f"energy jax={energy!r} vector={vres.total_energy!r}")
            print(f"chip_smoke: first kernel/f64 argmin disagreement: "
                  f"{first}")
        check(sched == vsched and energy == vres.total_energy,
              f"jax {leg} schedule or energy differs from engine='vector'")
    print("chip_smoke: schedule and total_energy bit-identical to engine='vector'")
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
    }))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
