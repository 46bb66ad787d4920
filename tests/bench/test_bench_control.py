"""The check must fail the control and every fault the cells can have.

Each test skips the harness's look for a chip and drives the rest of a run
on the CPU at a few dozen jobs, with the timed path changed underneath:

* the control: every score reduction in bfloat16 (``bench/control.py``);
* an answer altered where it is produced: the reduction answers a
  different row than its argmin;
* half of a batch left out: batched reductions answer only the first half
  of their requests and report no feasible row for the rest;
* a step that leaves its state unchanged: every other node decision
  launches nothing.

Each cell runs on one chip, so there is no exchange between chips to
leave out.
"""
import os

import pytest

from bench import audit as A
from bench import harness as H
from bench import report

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELLS = ["fleet256.elastic_burst", "dvfs512.burst", "fleet256.poisson"]
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1, "memory_peak_bytes": 0}


def small(cell):
    c = H.load_cell(cell, ROOT)
    c["config"]["nodes"] = 24
    c["traffic"]["arrivals"]["jobs"] = 60
    return c


def run(c, seed, replace=None, seconds=0.4):
    res = H.run_cell(c, seed, seconds, trace=False, replace=replace)
    line, lines = report.result(c, res, DEVICE, None)
    return line


def altered():
    from repro.kernels import score_reduce as sr

    solo, batch, multi = sr.score_reduce, sr.score_reduce_batch, sr.score_reduce_multi

    def bump(out):
        scores, best = out
        return scores, (best + 1) % len(scores) if best >= 0 else best

    return {
        "score_reduce": lambda *a, **k: bump(solo(*a, **k)),
        "score_reduce_batch": lambda reqs, **k: [bump(o) for o in batch(reqs, **k)],
        "score_reduce_multi": lambda reqs, **k: [bump(o) for o in multi(reqs, **k)],
    }


def half_batch():
    import numpy as np
    from repro.kernels import score_reduce as sr

    batch, multi = sr.score_reduce_batch, sr.score_reduce_multi

    def halve(fn):
        def call(reqs, **k):
            keep = max(1, len(reqs) // 2)
            out = fn(reqs[:keep], **k)
            return out + [(np.full(r["dev"].shape[0], np.inf, np.float32), -1)
                          for r in reqs[keep:]]
        return call

    return {"score_reduce_batch": halve(batch), "score_reduce_multi": halve(multi)}


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    line = run(small(cell), 3_000_000_019)
    assert line["correct"] is True, line["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell, monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    line = run(small(cell), 3_000_000_019, A.bf16_reductions())
    assert line["correct"] is False
    for name in ("kernel_score_gap", "decision_score_gap"):
        gap = line["checks"][name]
        assert gap["value"] > gap["limit"], name


@pytest.mark.parametrize("cell", CELLS)
def test_altered_answer_is_not_correct(cell, monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    line = run(small(cell), 3_000_000_019, altered())
    assert line["correct"] is False
    checks = line["checks"]
    assert (checks["kernel_argmin_mismatches"]["value"]
            + checks["program_errors"]["value"]) > 0


@pytest.mark.parametrize("cell", ["fleet256.elastic_burst", "dvfs512.burst"])
def test_half_batch_left_out_is_not_correct(cell, monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    line = run(small(cell), 3_000_000_019, half_batch())
    assert line["correct"] is False


@pytest.mark.parametrize("cell", CELLS)
def test_unchanged_state_is_not_correct(cell, monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    from repro.core import EcoSched

    real = EcoSched.on_event
    calls = {"n": 0}

    def every_other(self, view, waiting):
        calls["n"] += 1
        return real(self, view, waiting) if calls["n"] % 2 else []

    monkeypatch.setattr(EcoSched, "on_event", every_other)
    line = run(small(cell), 3_000_000_019)
    assert line["correct"] is False
