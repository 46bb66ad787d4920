"""Least work of one score-reduce request, from its unpadded shape.

A request scores ``B`` candidate rows of ``S`` slots.  The reduction must
read the ``dev`` and ``g`` planes (and the ``f`` plane when the request
carries one) and the per-row columns it carries (``n`` always, ``bias``
and ``mask`` when given), all as float32, and write each row's score and
total count.  Per row it needs one add per plane slot and the Eq. (1)
combine: ``max(n, 1)``, the deviation mean, ``G_free - G``, ``lam *``,
``/ M``, one add and the mask select (7), plus three for the frequency
term and one for the bias.  Padding rows and slots are not work, so a
launch padded far past its request reads as a low roofline share.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Tuple

F32 = 4


def request_work(req: Dict) -> Tuple[float, float]:
    """(operations, bytes) of one request dict (``dev``, ``g``, ``n`` and
    optional ``f``, ``bias``, ``mask``)."""
    B, S = req["dev"].shape
    has_f = req.get("f") is not None
    has_bias = req.get("bias") is not None
    has_mask = req.get("mask") is not None
    planes = 2 + has_f
    cols = 1 + has_bias + has_mask
    ops = B * (planes * S + 7 + 3 * has_f + has_bias)
    nbytes = F32 * B * (planes * S + cols + 2)
    return float(ops), float(nbytes)


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of ``device_kind``; an unknown device is an error."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return table[device_kind]


def least_seconds(ops: float, nbytes: float, peak: Dict[str, float]) -> float:
    return max(ops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
