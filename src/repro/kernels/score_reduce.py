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

Each launch crosses the host/device boundary once each way.  The host
packs every operand into one float32 table of shape
(rows, 3·s_pad + 8): the ``dev | g | f`` planes, then eight per-row
columns ``n, bias, mask, λ, G_free, M, λ_f, wid`` (the window id, read by
``score_reduce_multi`` only; an exact float32 integer, never a bitcast).
Per-launch and per-node scalars are repeated over their rows on the host.
The jit slices the table apart on the device and returns one float32
array: the flat scores, then each window's winning row (row indices and
-1 are exact in float32).  The host reads it once and slices it.

Each entry point runs in three spans of the program's tracer
(``repro.obs``): ``kernel.pack`` (padding and packing on the host),
``kernel.call`` (the jitted call: the table's transfer and dispatch) and
``kernel.fetch`` (the blocking read of the answer, and slicing it), and
counts its launch, the arrays it hands the device and the arrays it
reads back (one each).  The Pallas kernel is named ``eq1_row_scores`` in
the device trace.
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
# per-row columns after the planes: n, bias, mask, λ, G_free, M, λ_f, wid
_N_COLS = 8
_EXACT = 1 << 24  # float32 holds every integer below this exactly


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


def _table_scores(table, mode: str):
    """(R, 1) scores and total counts of a packed (R, 3·s_pad + 8) table,
    and its (R, 1) window-id column, sliced apart on the device."""
    s_pad = (table.shape[1] - _N_COLS) // 3
    dev, g, f = (table[:, k * s_pad:(k + 1) * s_pad] for k in range(3))
    c = 3 * s_pad
    n, bias, mask, lam, gfree, m, lamf, wid = (
        table[:, c + k:c + k + 1] for k in range(_N_COLS)
    )
    scores, tot = _score_rows(
        dev, g, f, n, bias, mask, lam, gfree, m, lamf, mode=mode
    )
    return scores, tot, wid


def _answer(scores, best):
    """One float32 array for the host: flat scores, then the winning rows."""
    return jnp.concatenate(
        [scores.reshape(-1), best.reshape(-1).astype(jnp.float32)]
    )


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
def _reduce_jit(table, *, mode: str):
    scores, tot, _ = _table_scores(table, mode)
    return _answer(*_argmin(scores, tot))


def _pads(b: int, s: int) -> Tuple[int, int]:
    """(b_pad, s_pad): rows to a power of two of at least one block,
    slots to a multiple of _SLOT_PAD."""
    b_pad = max(_BLOCK_B, 1 << max(b - 1, 0).bit_length())
    return b_pad, max(_SLOT_PAD, -(-s // _SLOT_PAD) * _SLOT_PAD)


def _pack(reqs: Sequence[Dict[str, Any]], offsets: Sequence[int],
          rows: int, s_pad: int, dummy: int) -> np.ndarray:
    """One float32 (rows, 3·s_pad + 8) table holding request ``k`` on rows
    ``offsets[k]:offsets[k] + B_k``, with window id ``k``.  Pad rows are
    masked out, with a benign M of 1 (no 0/0) and window id ``dummy``."""
    if rows >= _EXACT or dummy >= _EXACT:
        raise ValueError(f"{rows} rows or {dummy} windows: not exact in float32")
    c = 3 * s_pad
    table = np.zeros((rows, c + _N_COLS), dtype=np.float32)
    table[:, c + 5] = 1.0
    table[:, c + 7] = dummy
    for k, (r, off) in enumerate(zip(reqs, offsets)):
        B, S = r["dev"].shape
        if B == 0:
            continue  # empty window: no rows, so best = -1
        t = table[off:off + B]
        t[:, :S] = r["dev"]
        t[:, s_pad:s_pad + S] = r["g"]
        if r.get("f") is not None:
            t[:, 2 * s_pad:2 * s_pad + S] = r["f"]
        t[:, c] = np.asarray(r["n"]).reshape(B)
        if r.get("bias") is not None:
            t[:, c + 1] = np.asarray(r["bias"]).reshape(B)
        mask = r.get("mask")
        t[:, c + 2] = 1.0 if mask is None else np.asarray(mask).reshape(B)
        t[:, c + 3:] = (r["lam"], r["g_free"], r["M"], r.get("lam_f", 0.0), k)
    return table


def _count_launch(kind: str, table: np.ndarray) -> None:
    """One launch of entry point ``kind`` that hands the device ``table``."""
    obs.count(f"kernel.launches.{kind}")
    obs.count("kernel.h2d_arrays")
    obs.count("kernel.h2d_bytes", table.nbytes)


def _fetch(answer) -> np.ndarray:
    """The launch's one blocking read of its answer."""
    obs.count("kernel.d2h_arrays")
    return np.asarray(answer)


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
        b_pad, s_pad = _pads(B, S)
        req = dict(dev=dev, g=g, n=n, f=f, bias=bias, mask=mask,
                   lam=lam, g_free=g_free, M=M, lam_f=lam_f)
        table = _pack([req], [0], b_pad, s_pad, dummy=1)
        _count_launch("solo", table)
        mode = backend_mode(mode)
    with obs.span("kernel.call"):
        answer = _reduce_jit(table, mode=mode)
    with obs.span("kernel.fetch"):
        answer = _fetch(answer)
        return answer[:B], int(answer[b_pad])


# ---------------------------------------------------------------------------
# Cross-node batched reduction: one launch serves a pod's worth of
# simultaneous per-node decisions (ISSUE 9 tentpole).
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("mode",))
def _reduce_batch_jit(table, *, mode: str):
    d_pad, b_pad, width = table.shape
    scores, tot, _ = _table_scores(table.reshape(d_pad * b_pad, width), mode)
    return _answer(*jax.vmap(_argmin)(
        scores.reshape(d_pad, b_pad, 1), tot.reshape(d_pad, b_pad, 1)
    ))


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
        b_pad, s_pad = _pads(max(b for b, _ in sizes), max(s for _, s in sizes))
        D = len(reqs)
        d_pad = 1 << max(D - 1, 0).bit_length()
        # node k on rows k·b_pad onwards; pad nodes are all pad rows
        table = _pack(reqs, range(0, D * b_pad, b_pad), d_pad * b_pad,
                      s_pad, dummy=D).reshape(d_pad, b_pad, -1)
        _count_launch("batch", table)
        mode = backend_mode(mode)
    with obs.span("kernel.call"):
        answer = _reduce_batch_jit(table, mode=mode)
    with obs.span("kernel.fetch"):
        answer = _fetch(answer)
        best = answer[d_pad * b_pad:]
        return [(answer[k * b_pad:k * b_pad + sizes[k][0]], int(best[k]))
                for k in range(D)]


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
def _reduce_multi_jit(table, *, n_windows: int, mode: str):
    b_pad = table.shape[0]
    scores2, tot2, wid2 = _table_scores(table, mode)
    scores = scores2[:, 0]
    tot = tot2[:, 0]
    wid = wid2[:, 0].astype(jnp.int32)
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
    # a window's first row is its least row index; an empty or
    # all-infeasible window keeps m_w = inf and answers -1
    first = seg_idx.at[wid].min(ridx)
    return _answer(scores, jnp.where(jnp.isinf(m_w), jnp.int32(-1), i_w - first))


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
        b_pad, s_pad = _pads(sum(b for b, _ in sizes), max(s for _, s in sizes))
        W = len(reqs)
        # power-of-two window count strictly greater than W: the jit cache
        # stays small and the last segment is always the pad rows' dummy
        n_windows = 1 << max(W, 1).bit_length()
        starts = np.cumsum([0] + [b for b, _ in sizes[:-1]]).tolist()
        table = _pack(reqs, starts, b_pad, s_pad, dummy=n_windows - 1)
        _count_launch("multi", table)
        mode = backend_mode(mode)
    with obs.span("kernel.call"):
        answer = _reduce_multi_jit(table, n_windows=n_windows, mode=mode)
    with obs.span("kernel.fetch"):
        answer = _fetch(answer)
        best = answer[b_pad:]
        return [(answer[starts[k]:starts[k] + sizes[k][0]], int(best[k]))
                for k in range(W)]
