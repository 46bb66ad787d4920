"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), in seconds (assignment §Roofline):

    compute    = HLO_FLOPs / (chips × peak_FLOP/s)
    memory     = HLO_bytes / (chips × HBM_bw)
    collective = collective_bytes / (chips × link_bw)

``cost_analysis()`` reports the *per-device* partitioned module, and counts
a ``lax.scan`` (while-loop) body **once** — so totals are reconstructed by
compiling three module variants (0 layers / 1 period / full) and
extrapolating:  total = C0 + (L / period) · (C1 − C0)   (DESIGN.md §4).

Collective bytes are parsed from the compiled HLO text: every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
op contributes its result-shape bytes (``-start`` counted, ``-done``
skipped).  This is a per-device byte count, matching the per-chip link
bandwidth in the denominator.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

from repro.roofline.hw import ChipSpec

COLLECTIVE_KINDS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

# result types of an HLO op: "f32[16,64]{1,0}" possibly inside a tuple
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(?P<result>.*?)\s+"
    r"(?P<op>all-gather-start|all-gather|all-reduce-start|all-reduce|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute)\s*\(",
)


def _shape_bytes(result: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(result):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-kind result bytes of every collective op in an HLO module."""
    out: Dict[str, int] = {k: 0 for k in COLLECTIVE_KINDS}
    counts: Dict[str, int] = {k: 0 for k in COLLECTIVE_KINDS}
    for line in hlo_text.splitlines():
        m = _OP_RE.match(line)
        if not m:
            continue
        op = m.group("op").replace("-start", "")
        out[op] += _shape_bytes(m.group("result"))
        counts[op] += 1
    out["_counts"] = counts  # type: ignore[assignment]
    return out


def cost_summary(cost) -> Dict[str, float]:
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    if byts == 0.0:
        byts = sum(
            float(v) for k, v in cost.items() if k.startswith("bytes accessed")
        )
    return {"flops": flops, "bytes": byts, "transcendentals": float(cost.get("transcendentals", 0.0))}


@dataclass
class CellCost:
    """Extrapolated per-device totals for one dry-run cell."""

    flops: float
    bytes: float
    coll_bytes: float
    coll_by_kind: Dict[str, float]
    coll_counts: Dict[str, int]


def extrapolate(
    c0: Dict[str, float],
    c1: Dict[str, float],
    cfull: Dict[str, float],
    *,
    periods_total: int,
) -> Dict[str, float]:
    """total = C0 + periods_total · (C1 − C0), with a floor at Cfull."""
    out = {}
    keys = set(c0) | set(c1) | set(cfull)
    for k in keys:
        a, b, f = c0.get(k, 0.0), c1.get(k, 0.0), cfull.get(k, 0.0)
        per_period = max(b - a, 0.0)
        out[k] = max(a + periods_total * per_period, f)
    return out


def roofline_terms(
    flops: float, byts: float, coll: float, *, chips: int, chip: ChipSpec,
    per_device: bool = True,
) -> Dict[str, float]:
    """Terms in seconds.  ``per_device=True``: inputs are per-device already
    (the partitioned module), so the chips factor is dropped."""
    div = 1 if per_device else chips
    t_compute = flops / (div * chip.peak_flops_bf16)
    t_memory = byts / (div * chip.hbm_bw)
    t_coll = coll / (div * chip.ici_bw)
    dominant = max(
        ("compute", t_compute), ("memory", t_memory), ("collective", t_coll),
        key=lambda kv: kv[1],
    )[0]
    bound = max(t_compute, t_memory, t_coll)
    return {
        "t_compute": t_compute,
        "t_memory": t_memory,
        "t_collective": t_coll,
        "t_bound": bound,
        "dominant": dominant,
    }


def model_flops(cfg, cell, *, original_cfg=None) -> float:
    """MODEL_FLOPS = 6·N·D (train) or 2·N·D (fwd), N = active params.

    Attention score/value FLOPs are added explicitly (they are not in N·D):
    12·L·hd·H·S per token causal-halved for train/prefill; 4·L·H·hd·S_cache
    per decoded token (2 matmuls × 2 flops, GQA on the query side).
    """
    c = original_cfg or cfg
    n_active = c.active_param_count()
    tokens = cell.tokens_per_step
    if cell.kind == "train":
        base = 6.0 * n_active * tokens
    else:
        base = 2.0 * n_active * tokens
    attn = 0.0
    if c.uses_attention:
        H, hd, L = c.num_heads, c.resolved_head_dim, c.num_layers
        if cell.kind in ("train", "prefill"):
            per_tok = 2 * 2 * H * hd * (cell.seq_len / 2)  # causal half
            if c.attention_pattern == "local_global":
                period_ = c.local_global_ratio + 1
                frac_g = 1.0 / period_
                w = min(c.sliding_window, cell.seq_len)
                per_tok = 2 * 2 * H * hd * (
                    frac_g * cell.seq_len / 2 + (1 - frac_g) * w
                )
            attn = L * per_tok * tokens
            if cell.kind == "train":
                attn *= 3  # fwd + 2x bwd
        else:
            per_tok = 2 * 2 * H * hd * cell.seq_len
            if c.attention_pattern == "local_global":
                period_ = c.local_global_ratio + 1
                frac_g = 1.0 / period_
                w = min(c.sliding_window, cell.seq_len)
                per_tok = 2 * 2 * H * hd * (frac_g * cell.seq_len + (1 - frac_g) * w)
            attn = L * per_tok * tokens
    return base + attn


# ---------------------------------------------------------------------------
# Post-hoc term derivation from a dry-run record (bench_roofline / tpu_pod).
#
# The CPU backend legalizes bf16 compute to f32 and fuses far less than the
# TPU backend, so raw HLO "bytes accessed" overstates TPU HBM traffic by a
# large, workload-dependent factor (verified by HLO inspection,
# EXPERIMENTS.md §Dry-run caveats).  The *memory term* therefore uses an
# analytic HBM-traffic model — the bytes that MUST move:
#   decode   : all arguments once (params + KV cache) + cache append
#   prefill  : params + 2 residual passes/layer + KV-cache write
#   train    : params+opt once + residual stream passes/layer
#              (4 = fwd in/out + bwd in/out; +2 with full remat recompute)
# The raw HLO bytes stay in every record ("t_memory_hlo") as the
# pessimistic bound, and hillclimb iterations report both.
# ---------------------------------------------------------------------------


def hbm_floor_bytes(record: dict, cfg, cell, *, dp: int, mp: int) -> float:
    args = float(record["memory"]["argument_bytes"])
    opts = record.get("opts", {})
    accum = max(int(opts.get("grad_accum", 1)), 1)
    remat = opts.get("remat", "full")
    if cell.kind == "decode":
        touched = args
        b_chip = (
            cell.global_batch / dp if cell.global_batch % max(dp, 1) == 0 else cell.global_batch
        )
        if opts.get("window_slice") and cfg.sliding_window and cfg.uses_attention:
            # local layers read only the window, not the whole cache
            period = (cfg.local_global_ratio + 1) if cfg.attention_pattern == "local_global" else 1
            n_global = (
                cfg.num_layers // period if cfg.attention_pattern == "local_global"
                else (0 if cfg.attention_pattern == "local" else cfg.num_layers)
            )
            n_local = cfg.num_layers - n_global
            kv_tok = 2 * max(cfg.num_kv_heads, 1) * cfg.resolved_head_dim * 2  # bytes
            full_cache = cfg.num_layers * b_chip * cell.seq_len * kv_tok / mp
            kept = (
                n_global * b_chip * cell.seq_len * kv_tok / mp
                + n_local * b_chip * min(cfg.sliding_window, cell.seq_len) * kv_tok / mp
            )
            touched = args - full_cache + kept
        return touched + 4 * b_chip * cfg.d_model * 2
    tokens_chip = cell.tokens_per_step / max(dp, 1)
    if cell.kind == "prefill":
        passes = 2
        kv_write = (
            cfg.num_layers * tokens_chip * 2 * max(cfg.num_kv_heads, 1)
            * cfg.resolved_head_dim * 2 / mp
        )
        return args + passes * 2 * tokens_chip * cfg.d_model * cfg.num_layers + kv_write
    passes = {"none": 4, "dots": 5, "full": 6}.get(remat, 6)
    act = passes * 2 * tokens_chip * cfg.d_model * max(cfg.num_layers, 1)
    logits = 2 * tokens_chip * (cfg.vocab_size / mp) * 4  # fwd+bwd, f32
    return args + act + logits


def derive_terms(record: dict, cfg, cell, chip) -> dict:
    """Roofline terms for one dry-run record, memory from the HBM floor."""
    mesh = record["mesh"]
    dims = [int(x) for x in mesh.split("x")]
    mp = dims[-1]
    dp = 1
    for d in dims[:-1]:
        dp *= d
    totals = record["cost_totals"]
    t_compute = totals["flops"] / chip.peak_flops_bf16
    t_mem_hlo = totals["bytes"] / chip.hbm_bw
    floor = hbm_floor_bytes(record, cfg, cell, dp=dp, mp=mp)
    t_memory = floor / chip.hbm_bw
    t_coll = totals["coll_bytes"] / chip.ici_bw
    t_bound = max(t_compute, t_memory, t_coll)
    dominant = max(
        ("compute", t_compute), ("memory", t_memory), ("collective", t_coll),
        key=lambda kv: kv[1],
    )[0]
    mf_chip = record["model_flops_total"] / record["chips"]
    ideal = mf_chip / chip.peak_flops_bf16
    # memory-side ideal: for decode the floor IS the ideal; roofline
    # fraction = ideal-time / bound where ideal includes mandatory bytes
    ideal_mem = floor / chip.hbm_bw if cell.kind == "decode" else 0.0
    frac = max(ideal, ideal_mem) / t_bound if t_bound else 0.0
    return {
        "t_compute": t_compute,
        "t_memory": t_memory,
        "t_memory_hlo": t_mem_hlo,
        "t_collective": t_coll,
        "t_bound": t_bound,
        "dominant": dominant,
        "useful_flops_ratio": (mf_chip / totals["flops"]) if totals["flops"] else 0.0,
        "roofline_fraction": frac,
    }
