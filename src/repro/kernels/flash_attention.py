"""Pallas TPU flash attention (causal / sliding-window / GQA / softcap).

TPU-native design (not a CUDA port): the grid is
``(batch, kv_head, q_group, q_blocks, kv_blocks)`` with the kv-block axis
sequential ("arbitrary") and everything else parallel.  Running max / sum /
accumulator live in VMEM scratch and persist across the kv-block axis —
the online-softmax state never leaves VMEM, and each (bq×hd) output tile is
written exactly once on the last kv step.  Block shapes are BlockSpec-tiled
so the (bq×bk) score tile and the (bk×hd) K/V tiles sit in VMEM with
MXU-aligned (multiple-of-128) matmul dims.

GQA: queries carry H = KVH·G heads; K/V carry KVH.  The q-group axis of the
grid indexes the G query heads sharing one kv head, so K/V tiles are
fetched once per group from HBM.

Validated on CPU via ``interpret=True`` against ``ref.flash_attention_ref``
(tests/test_kernels_flash.py sweeps shapes × dtypes × flags).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale: float, causal: bool, window: int, softcap: float,
    block_q: int, block_k: int,
):
    iq = pl.program_id(3)
    ik = pl.program_id(4)
    nk = pl.num_programs(4)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * block_q
    k_start = ik * block_k

    should_run = jnp.bool_(True)
    if causal:
        should_run &= k_start <= q_start + block_q - 1
    if window > 0:
        should_run &= k_start + block_k - 1 > q_start - window

    @pl.when(should_run)
    def _compute():
        q = q_ref[0, :, 0, :].astype(jnp.float32) * scale  # (bq, hd)
        k = k_ref[0, :, 0, :].astype(jnp.float32)  # (bk, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), bool)
        if causal:
            mask &= k_pos <= q_pos
        if window > 0:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l_prev * alpha + p.sum(axis=-1)
        v = v_ref[0, :, 0, :].astype(jnp.float32)  # (bk, hd)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        m_ref[:, 0] = m_new
        l_ref[:, 0] = l_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0, :, 0, :] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # (B, Sq, H, hd)
    k: jax.Array,  # (B, Skv, KVH, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, Sq, H, hd = q.shape
    _, Skv, KVH, _ = k.shape
    assert H % KVH == 0, (H, KVH)
    G = H // KVH
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0, (Sq, block_q, Skv, block_k)
    nq, nk = Sq // block_q, Skv // block_k
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    grid = (B, KVH, G, nq, nk)
    kernel = functools.partial(
        _kernel,
        scale=scale, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, block_q, 1, hd), lambda b, h, g, iq, ik: (b, iq, h * G + g, 0)
            ),
            pl.BlockSpec((1, block_k, 1, hd), lambda b, h, g, iq, ik: (b, ik, h, 0)),
            pl.BlockSpec((1, block_k, 1, hd), lambda b, h, g, iq, ik: (b, ik, h, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, block_q, 1, hd), lambda b, h, g, iq, ik: (b, iq, h * G + g, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
