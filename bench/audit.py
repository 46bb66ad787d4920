"""Float64 check of every score-reduce request, and the benchmark's control.

``compare`` recomputes the Eq. (1) reduction of one kernel request in
float64 numpy with the program's tie-break (least score, then most units,
then the first row).  ``Recorder`` sits in place of the three reduction entry points
for a whole run: it counts launches, keeps every request with the answer
the kernel gave, and lets the check compare them once the window has
closed.  ``bf16_reductions`` are the control: the same reduction computed in
bfloat16, put in the kernel's place.
"""
from __future__ import annotations

import contextlib
from typing import Callable, Dict, List, Optional

import numpy as np

KERNELS = ("score_reduce", "score_reduce_batch", "score_reduce_multi")


def _scores(req, dtype) -> tuple:
    c = lambda a: np.asarray(a, dtype=np.float64).astype(dtype)  # noqa: E731
    dev, g = c(req["dev"]), c(req["g"])
    B = dev.shape[0]
    n_eff = np.maximum(c(req["n"]).reshape(B), dtype(1.0))
    tot = g.sum(axis=1, dtype=dtype)
    s = dev.sum(axis=1, dtype=dtype) / n_eff
    s = s + dtype(req["lam"]) * (dtype(req["g_free"]) - tot) / dtype(req["M"])
    f = req.get("f")
    if f is not None:
        s = s + dtype(req.get("lam_f", 0.0)) * c(f).sum(axis=1, dtype=dtype) / n_eff
    bias = req.get("bias")
    if bias is not None:
        s = s + c(bias).reshape(B)
    mask = req.get("mask")
    if mask is not None:
        s = np.where(np.asarray(mask, dtype=bool).reshape(B), s, dtype(np.inf))
    return s, tot


def best_of(s: np.ndarray, tot: np.ndarray) -> int:
    if s.size == 0 or not np.isfinite(s.astype(np.float64)).any():
        return -1
    tie = s == s.min()
    return int(np.flatnonzero(tie & (tot == tot[tie].max()))[0])


def compare(req, scores, best) -> tuple:
    """(answered row differs from the float64 argmin, widest gap between
    the answered scores and the float64 scores over the feasible rows)."""
    s, tot = _scores(req, np.float64)
    live = np.isfinite(s)
    got = np.asarray(scores, dtype=np.float64)
    gap = float(np.max(np.abs(got[live] - s[live]))) if live.any() else 0.0
    return int(best) != best_of(s, tot), gap


def _bf16():
    import ml_dtypes

    return ml_dtypes.bfloat16


def bf16_reduce(req):
    s, tot = _scores(req, _bf16())
    return s.astype(np.float32), best_of(s, tot)


def bf16_reductions() -> Dict[str, Callable]:
    """The control: each entry point computed in bfloat16 on the host."""

    def solo(dev, g, n, **kw):
        return bf16_reduce(dict(dev=dev, g=g, n=n, **kw))

    def many(reqs, **kw):
        return [bf16_reduce(r) for r in reqs]

    return {"score_reduce": solo, "score_reduce_batch": many,
            "score_reduce_multi": many}


class Recorder:
    """Counts and keeps every reduction request of a run with its answer."""

    def __init__(self):
        self.launches = dict.fromkeys(KERNELS, 0)
        self.requests: List[tuple] = []  # (kernel, request, scores, row)

    def wrap(self, name: str, fn: Callable) -> Callable:
        def call(*args, **kw):
            self.launches[name] += 1
            out = fn(*args, **kw)
            if name == "score_reduce":
                req = dict(dev=args[0], g=args[1], n=args[2], **kw)
                self.requests.append((name, req, out[0], out[1]))
            else:
                for req, (scores, best) in zip(args[0], out):
                    self.requests.append((name, req, scores, best))
            return out
        return call

    def audit(self) -> tuple:
        """(requests whose answered row differs from the float64 argmin,
        widest score gap to float64 over every feasible row)."""
        wrong, widest = 0, 0.0
        for _, req, scores, best in self.requests:
            w, gap = compare(req, scores, best)
            wrong += w
            widest = max(widest, gap)
        return wrong, widest


@contextlib.contextmanager
def patched_reductions(recorder: Recorder, spans=None,
                       replace: Optional[Dict[str, Callable]] = None):
    """Route the program's three reduction entry points through
    ``recorder`` (and ``spans``, outermost) for the duration of the block.
    Callers import the reductions from their module at call time, so
    patching the module attributes sees every launch.  ``replace`` swaps
    what runs underneath (the control, or a planted fault)."""
    from repro.kernels import score_reduce as sr

    saved = {k: getattr(sr, k) for k in KERNELS}
    try:
        for k in KERNELS:
            fn = (replace or {}).get(k) or saved[k]
            fn = recorder.wrap(k, fn)
            if spans is not None:
                fn = spans.wrap("kernel", fn)
            setattr(sr, k, fn)
        yield recorder
    finally:
        for k, fn in saved.items():
            setattr(sr, k, fn)
