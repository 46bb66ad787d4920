"""Batched Eq. (1) score reduction + masked argmin (JAX/Pallas).

The engine's candidate set for one scheduling event is a padded matrix of
per-slot energy deviations, unit counts (``ScoredBatch.padded_cols``) and
DVFS frequency levels (``ScoredBatch.padded_f``).  Scoring it is a row
reduction

    S[b] = Σ_s dev[b, s] / max(n[b], 1) + λ·(G_free − Σ_s g[b, s]) / M
           + λ_f·Σ_s f[b, s] / max(n[b], 1) + bias[b]

followed by a masked argmin under EcoSched's tie-break (lowest score, then
largest total unit count, then earliest row).  At pod scale the candidate
space exceeds 10^5 rows per event — and the joint (count × frequency) mode
set is 4–8× larger still; this module reduces it in one fused kernel
instead of a chain of numpy temporaries.

``backend_mode`` picks how it runs: on TPU the Pallas kernel runs
compiled (Mosaic, mode ``pallas``); off-TPU ``REPRO_KERNELS`` picks
``interpret`` (the kernel body op-by-op on CPU — the validation target)
or ``ref`` (the same elementwise ops in plain jnp, fast enough for CI;
the default off-TPU).  An unknown ``REPRO_KERNELS`` value raises.

One kernel serves all three entry points.  Its grid tiles a row-packed
table into blocks and writes each row's score and total count; the
Eq. (1) scalars λ, G_free, M and λ_f ride as per-row (traced) columns,
so sweeping node fill levels or frequency-conservatism weights does not
recompile.  Each entry point then takes its tie-broken argmin in jnp on
the device — over one window (``score_reduce``), per node of a stacked
batch (``score_reduce_batch``) or per packed window
(``score_reduce_multi``) — so the reduction never materializes on the
host.  Rows are padded to a power of two and slots to a multiple of 8,
so the jit cache stays small.  Scores are float32 — parity vs the
float64 numpy engine is ≤1e-6 over seeded random windows
(tests/test_score_reduce.py).

Each entry point runs in three spans of the program's tracer
(``repro.obs``): ``kernel.pack`` (padding and packing on the host),
``kernel.call`` (the jitted call) and ``kernel.fetch`` (the blocking reads
of the answer), and counts its launch and the arrays it hands the device.
The Pallas kernel is named ``eq1_row_scores`` in the device trace.
"""
from __future__ import annotations

import functools
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import obs

_BLOCK_B = 256  # candidate rows per grid step
_SLOT_PAD = 8  # slot (action-size) axis padded to a multiple of this
_MODES = ("pallas", "interpret", "ref")


def backend_mode(mode: Optional[str] = None) -> str:
    """``mode`` when given, else the one ``REPRO_KERNELS`` forces, else
    ``pallas`` on TPU and ``ref`` elsewhere.  An unknown value raises
    instead of silently running the compiled kernel."""
    mode = mode or os.environ.get("REPRO_KERNELS", "") or (
        "pallas" if jax.default_backend() == "tpu" else "ref"
    )
    if mode not in _MODES:
        raise ValueError(f"kernel mode {mode!r} is not one of {_MODES}")
    return mode


def _row_scores(dev, g, f, n, bias, mask, lam, g_free, M, lam_f):
    """(B, 1) masked Eq. (1) scores and total counts from (B, S)/(B, 1)
    blocks.  The frequency term is λ_f·mean(f); at λ_f = 0 (or an all-zero
    f plane — single-frequency windows) it contributes exactly +0.0,
    keeping scores bit-identical to the count-only reduction."""
    tot = jnp.sum(g, axis=1, keepdims=True)
    n_eff = jnp.maximum(n, 1.0)
    s = (
        jnp.sum(dev, axis=1, keepdims=True) / n_eff
        + lam * (g_free - tot) / M
        + lam_f * jnp.sum(f, axis=1, keepdims=True) / n_eff
        + bias
    )
    return jnp.where(mask > 0, s, jnp.inf), tot


def _kernel(dev_ref, g_ref, f_ref, n_ref, bias_ref, mask_ref,
            lam_ref, gfree_ref, m_ref, lamf_ref, scores_ref, tot_ref):
    """Grid step i: row-block i of a row-packed table.  Eq. (1) params are
    per-row columns, so one kernel serves a single window, a stack of
    nodes and many packed windows alike; every argmin is jnp outside."""
    scores, tot = _row_scores(
        dev_ref[:], g_ref[:], f_ref[:], n_ref[:], bias_ref[:], mask_ref[:],
        lam_ref[:], gfree_ref[:], m_ref[:], lamf_ref[:],
    )
    scores_ref[:] = scores
    tot_ref[:] = tot


def _score_rows(dev, g, f, n, bias, mask, lam, gfree, m, lamf, mode: str):
    """(B, 1) scores and total counts of a (B, S) table whose params are
    (B, 1) columns: the Pallas kernel (compiled or interpreted) or the
    same elementwise ops in plain jnp."""
    if mode == "ref":
        return _row_scores(dev, g, f, n, bias, mask, lam, gfree, m, lamf)
    b_pad, s_pad = dev.shape
    col = pl.BlockSpec((_BLOCK_B, 1), lambda i: (i, 0))
    plane = pl.BlockSpec((_BLOCK_B, s_pad), lambda i: (i, 0))
    return pl.pallas_call(
        _kernel,
        grid=(b_pad // _BLOCK_B,),
        in_specs=[plane, plane, plane, col, col, col, col, col, col, col],
        out_specs=[col, col],
        out_shape=[
            jax.ShapeDtypeStruct((b_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((b_pad, 1), jnp.float32),
        ],
        interpret=(mode == "interpret"),
        name="eq1_row_scores",
    )(dev, g, f, n, bias, mask, lam, gfree, m, lamf)


def _param_cols(params, rows: int):
    """(D, 4) [λ, G_free, M, λ_f] rows -> four (D·rows, 1) columns, each
    node's scalars repeated over its ``rows`` candidate rows."""
    return [jnp.repeat(params[:, k], rows)[:, None] for k in range(4)]


def _argmin(scores, tot):
    """Tie-broken argmin of (B, 1) scores: min score, then max total
    count, then min row.  Returns ((B,) scores, winning row or -1 when no
    row is feasible)."""
    b = scores.shape[0]
    idx = jax.lax.broadcasted_iota(jnp.int32, (b, 1), 0)
    m = jnp.min(scores)
    tie = scores == m
    t_best = jnp.max(jnp.where(tie, tot, -1.0))
    cand = tie & (tot == t_best)
    i = jnp.min(jnp.where(cand, idx, jnp.int32(b)))
    return scores[:, 0], jnp.where(jnp.isinf(m), jnp.int32(-1), i)


@functools.partial(jax.jit, static_argnames=("mode",))
def _reduce_jit(params, dev, g, f, n, bias, mask, *, mode: str):
    scores, tot = _score_rows(
        dev, g, f, n, bias, mask, *_param_cols(params, dev.shape[0]),
        mode=mode,
    )
    return _argmin(scores, tot)


def _pad_rows(a: np.ndarray, b_pad: int) -> np.ndarray:
    out = np.zeros((b_pad,) + a.shape[1:], dtype=a.dtype)
    out[: len(a)] = a
    return out


def _count_launch(kind: str, arrays: Sequence[np.ndarray]) -> None:
    """One launch of entry point ``kind`` that hands the device ``arrays``."""
    obs.count(f"kernel.launches.{kind}")
    obs.count("kernel.h2d_arrays", len(arrays))
    obs.count("kernel.h2d_bytes", sum(a.nbytes for a in arrays))


def score_reduce(
    dev: np.ndarray,
    g: np.ndarray,
    n: np.ndarray,
    *,
    lam: float,
    g_free: int,
    M: int,
    f: Optional[np.ndarray] = None,
    lam_f: float = 0.0,
    bias: Optional[np.ndarray] = None,
    mask: Optional[np.ndarray] = None,
    mode: Optional[str] = None,
) -> Tuple[np.ndarray, int]:
    """Scores + tie-broken argmin for a (B, S) candidate block.

    ``dev``/``g`` are per-slot deviation/count columns (zero-padded past
    each action's size ``n``); ``f`` is the optional per-slot DVFS
    frequency-level plane (``None`` ≡ all base clock) weighted by
    ``lam_f``; ``bias`` is an optional per-candidate additive term
    (EcoSched's lookahead spread penalty); ``mask`` marks feasible
    candidates (default: all).  Returns (float32 scores (B,), winning row
    index) — the index is -1 when no candidate is feasible.
    """
    with obs.span("kernel.pack"):
        B, S = dev.shape
        b_pad = max(_BLOCK_B, 1 << max(B - 1, 0).bit_length())
        s_pad = max(_SLOT_PAD, -(-S // _SLOT_PAD) * _SLOT_PAD)
        dev_p = np.zeros((b_pad, s_pad), dtype=np.float32)
        g_p = np.zeros((b_pad, s_pad), dtype=np.float32)
        f_p = np.zeros((b_pad, s_pad), dtype=np.float32)
        dev_p[:B, :S] = dev
        g_p[:B, :S] = g
        if f is not None:
            f_p[:B, :S] = f
        n_p = _pad_rows(np.asarray(n, dtype=np.float32).reshape(B, 1), b_pad)
        bias_p = (
            _pad_rows(np.asarray(bias, dtype=np.float32).reshape(B, 1), b_pad)
            if bias is not None
            else np.zeros((b_pad, 1), dtype=np.float32)
        )
        feasible = (
            np.asarray(mask, dtype=np.float32).reshape(B, 1)
            if mask is not None
            else np.ones((B, 1), dtype=np.float32)
        )
        mask_p = _pad_rows(feasible, b_pad)  # padding rows stay masked out
        params = np.array([[lam, g_free, M, lam_f]], dtype=np.float32)
        args = (params, dev_p, g_p, f_p, n_p, bias_p, mask_p)
        _count_launch("solo", args)
        mode = backend_mode(mode)
    with obs.span("kernel.call"):
        scores, best = _reduce_jit(*args, mode=mode)
    with obs.span("kernel.fetch"):
        return np.asarray(scores)[:B], int(best)


# ---------------------------------------------------------------------------
# Cross-node batched reduction: one launch serves a pod's worth of
# simultaneous per-node decisions (ISSUE 9 tentpole).
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("mode",))
def _reduce_batch_jit(params, dev, g, f, n, bias, mask, *, mode: str):
    d_pad, b_pad, s_pad = dev.shape
    rows = d_pad * b_pad
    scores, tot = _score_rows(
        dev.reshape(rows, s_pad), g.reshape(rows, s_pad),
        f.reshape(rows, s_pad), n.reshape(rows, 1), bias.reshape(rows, 1),
        mask.reshape(rows, 1), *_param_cols(params, b_pad), mode=mode,
    )
    return jax.vmap(_argmin)(
        scores.reshape(d_pad, b_pad, 1), tot.reshape(d_pad, b_pad, 1)
    )


def score_reduce_batch(
    reqs: Sequence[Dict[str, Any]],
    *,
    mode: Optional[str] = None,
) -> List[Tuple[np.ndarray, int]]:
    """Reduce many nodes' candidate blocks in one kernel launch.

    Each request is a dict with the per-node arguments of
    :func:`score_reduce`: required ``dev``/``g``/``n`` (the (B, S) padded
    columns and per-row action sizes) and ``lam``/``g_free``/``M``
    scalars; optional ``f``/``lam_f``/``bias``/``mask``.  Blocks are
    zero-padded to the common (b_pad, s_pad) and stacked on a leading
    node axis (itself padded to a power of two with fully-masked rows),
    so appended zeros contribute exactly +0.0 at every reduction combine
    and per-node results match the solo path.  Returns one
    (scores (B_k,), best index) pair per request, in order; ``best`` is
    -1 when that node has no feasible candidate (including B_k == 0).
    """
    if not reqs:
        return []
    with obs.span("kernel.pack"):
        sizes = [r["dev"].shape for r in reqs]
        b_max = max(b for b, _ in sizes)
        s_max = max(s for _, s in sizes)
        b_pad = max(_BLOCK_B, 1 << max(b_max - 1, 0).bit_length())
        s_pad = max(_SLOT_PAD, -(-s_max // _SLOT_PAD) * _SLOT_PAD)
        D = len(reqs)
        d_pad = 1 << max(D - 1, 0).bit_length()
        dev = np.zeros((d_pad, b_pad, s_pad), dtype=np.float32)
        g = np.zeros((d_pad, b_pad, s_pad), dtype=np.float32)
        f = np.zeros((d_pad, b_pad, s_pad), dtype=np.float32)
        n = np.zeros((d_pad, b_pad, 1), dtype=np.float32)
        bias = np.zeros((d_pad, b_pad, 1), dtype=np.float32)
        mask = np.zeros((d_pad, b_pad, 1), dtype=np.float32)
        params = np.zeros((d_pad, 4), dtype=np.float32)
        params[:, 2] = 1.0  # benign M for the masked pad nodes (no 0/0)
        for k, r in enumerate(reqs):
            B, S = sizes[k]
            dev[k, :B, :S] = r["dev"]
            g[k, :B, :S] = r["g"]
            rf = r.get("f")
            if rf is not None:
                f[k, :B, :S] = rf
            n[k, :B, 0] = np.asarray(r["n"], dtype=np.float32).reshape(B)
            rb = r.get("bias")
            if rb is not None:
                bias[k, :B, 0] = np.asarray(rb, dtype=np.float32).reshape(B)
            rm = r.get("mask")
            if rm is None:
                mask[k, :B, 0] = 1.0
            else:
                mask[k, :B, 0] = np.asarray(rm, dtype=np.float32).reshape(B)
            params[k] = [r["lam"], r["g_free"], r["M"], r.get("lam_f", 0.0)]
        args = (params, dev, g, f, n, bias, mask)
        _count_launch("batch", args)
        mode = backend_mode(mode)
    with obs.span("kernel.call"):
        scores, best = _reduce_batch_jit(*args, mode=mode)
    with obs.span("kernel.fetch"):
        scores = np.asarray(scores)
        best = np.asarray(best)
        return [(scores[k, : sizes[k][0]], int(best[k])) for k in range(D)]


# ---------------------------------------------------------------------------
# Multi-window reduction: many variable-size windows share one launch by
# packing rows, not by padding every window to the widest (ISSUE 10
# tentpole).  The COMPLETE path's windows are tiny-but-many (one per
# eligible resize candidate, one per backfilling node); stacking them on a
# node axis like ``score_reduce_batch`` would pad each to _BLOCK_B rows,
# so instead the rows concatenate into one block and the per-window
# [λ, G_free, M, λ_f] scalars ride as per-row columns.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n_windows", "mode"))
def _reduce_multi_jit(lam, gfree, m, lamf, dev, g, f, n, bias, mask,
                      wid, starts, *, n_windows: int, mode: str):
    b_pad = dev.shape[0]
    scores2, tot2 = _score_rows(
        dev, g, f, n, bias, mask, lam, gfree, m, lamf, mode=mode
    )
    scores = scores2[:, 0]
    tot = tot2[:, 0]
    # segmented tie-broken argmin — the same (min score, max count, min
    # row) combine as _argmin, scatter-reduced per window id.  Pad rows
    # belong to a dummy window (their masked inf scores never matter).
    seg_min = jnp.full((n_windows,), jnp.inf, dtype=scores.dtype)
    m_w = seg_min.at[wid].min(scores)
    tie = scores == m_w[wid]
    seg_tot = jnp.full((n_windows,), -1.0, dtype=tot.dtype)
    t_w = seg_tot.at[wid].max(jnp.where(tie, tot, -1.0))
    cand = tie & (tot == t_w[wid])
    ridx = jax.lax.iota(jnp.int32, b_pad)
    seg_idx = jnp.full((n_windows,), b_pad, dtype=jnp.int32)
    i_w = seg_idx.at[wid].min(jnp.where(cand, ridx, jnp.int32(b_pad)))
    best = jnp.where(jnp.isinf(m_w), jnp.int32(-1), i_w - starts)
    return scores, best


def score_reduce_multi(
    reqs: Sequence[Dict[str, Any]],
    *,
    mode: Optional[str] = None,
) -> List[Tuple[np.ndarray, int]]:
    """Reduce many independent candidate windows in one kernel launch.

    Same request dicts as :func:`score_reduce_batch` (required
    ``dev``/``g``/``n``/``lam``/``g_free``/``M``, optional
    ``f``/``lam_f``/``bias``/``mask``), but the windows concatenate on the
    row axis instead of stacking on a padded node axis — the right shape
    when windows are many and small (the COMPLETE path: one window per
    elastic resize candidate plus one per backfilling node).  Per-row
    scores are the identical elementwise Eq. (1) ops as the solo kernel
    (params broadcast per row instead of per launch), and the per-window
    argmin applies the same tie-break, so each window's (scores, best)
    pair is bit-identical to a solo :func:`score_reduce` call on it.
    ``best`` is -1 for a window with no feasible candidate (including an
    empty window).
    """
    if not reqs:
        return []
    with obs.span("kernel.pack"):
        sizes = [r["dev"].shape for r in reqs]
        total = sum(b for b, _ in sizes)
        s_max = max(s for _, s in sizes)
        b_pad = max(_BLOCK_B, 1 << max(total - 1, 0).bit_length())
        s_pad = max(_SLOT_PAD, -(-s_max // _SLOT_PAD) * _SLOT_PAD)
        W = len(reqs)
        # power-of-two window count strictly greater than W: the jit cache
        # stays small and the last segment is always the pad rows' dummy
        n_windows = 1 << max(W, 1).bit_length()
        dev = np.zeros((b_pad, s_pad), dtype=np.float32)
        g = np.zeros((b_pad, s_pad), dtype=np.float32)
        f = np.zeros((b_pad, s_pad), dtype=np.float32)
        n = np.zeros((b_pad, 1), dtype=np.float32)
        bias = np.zeros((b_pad, 1), dtype=np.float32)
        mask = np.zeros((b_pad, 1), dtype=np.float32)
        lam = np.zeros((b_pad, 1), dtype=np.float32)
        gfree = np.zeros((b_pad, 1), dtype=np.float32)
        m = np.ones((b_pad, 1), dtype=np.float32)  # benign M for pad rows
        lamf = np.zeros((b_pad, 1), dtype=np.float32)
        wid = np.full(b_pad, n_windows - 1, dtype=np.int32)
        starts = np.zeros(n_windows, dtype=np.int32)
        off = 0
        for k, r in enumerate(reqs):
            B, S = sizes[k]
            starts[k] = off
            if B == 0:
                continue  # empty window: stays all-inf, best = -1
            rows = slice(off, off + B)
            dev[rows, :S] = r["dev"]
            g[rows, :S] = r["g"]
            rf = r.get("f")
            if rf is not None:
                f[rows, :S] = rf
            n[rows, 0] = np.asarray(r["n"], dtype=np.float32).reshape(B)
            rb = r.get("bias")
            if rb is not None:
                bias[rows, 0] = np.asarray(rb, dtype=np.float32).reshape(B)
            rm = r.get("mask")
            if rm is None:
                mask[rows, 0] = 1.0
            else:
                mask[rows, 0] = np.asarray(rm, dtype=np.float32).reshape(B)
            lam[rows, 0] = r["lam"]
            gfree[rows, 0] = r["g_free"]
            m[rows, 0] = r["M"]
            lamf[rows, 0] = r.get("lam_f", 0.0)
            wid[rows] = k
            off += B
        args = (lam, gfree, m, lamf, dev, g, f, n, bias, mask, wid, starts)
        _count_launch("multi", args)
        mode = backend_mode(mode)
    with obs.span("kernel.call"):
        scores, best = _reduce_multi_jit(*args, n_windows=n_windows, mode=mode)
    with obs.span("kernel.fetch"):
        scores = np.asarray(scores)
        best = np.asarray(best)
        return [
            (scores[int(starts[k]): int(starts[k]) + sizes[k][0]],
             int(best[k]))
            for k in range(W)
        ]
