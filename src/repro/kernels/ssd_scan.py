"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

TPU adaptation of the SSD "dual form": the sequence is tiled into chunks
of ``Q`` tokens; per (batch, head) the chunk axis runs sequentially
("arbitrary" grid dim) while batch and heads parallelize.  The (hp × N)
recurrent state lives in VMEM scratch and never round-trips to HBM between
chunks — the HBM traffic is exactly one read of x/Δ/B/C and one write of y
per token.  The intra-chunk quadratic form is two MXU matmuls
((Q×N)·(N×Q) and (Q×Q)·(Q×hp)); Q and N default to 256/128 so every
matmul dim is 128-aligned.

Returns y **without** the D·x skip term and gating — those are
elementwise and stay in the XLA layer where they fuse with the
surrounding ops.

Validated on CPU via ``interpret=True`` against ``ref.ssd_ref``
(tests/test_kernels_ssd.py sweeps shapes/dtypes/chunk sizes).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, h_ref, *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, :, 0, :].astype(jnp.float32)  # (Q, hp)
    dt = dt_ref[0, :, 0].astype(jnp.float32)  # (Q,)
    A = a_ref[0]  # scalar (negative)
    Bm = b_ref[0].astype(jnp.float32)  # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)  # (Q, N)

    a = dt * A  # (Q,) negative log-decay
    La = jnp.cumsum(a)  # inclusive
    Ltot = La[-1]

    # intra-chunk quadratic form
    cb = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, Q) = C_i · B_j
    decay = jnp.exp(La[:, None] - La[None, :])
    qi = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    kj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    scores = jnp.where(qi >= kj, cb * decay, 0.0) * dt[None, :]
    y_intra = jax.lax.dot_general(
        scores, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, hp)

    # inter-chunk: contribution of the carried state
    h_prev = h_ref[...]  # (hp, N)
    y_inter = jax.lax.dot_general(
        Cm, h_prev, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * jnp.exp(La)[:, None]  # (Q, hp)

    # state update: deposits surviving to end of chunk
    w = jnp.exp(Ltot - La) * dt  # (Q,)
    s_chunk = jax.lax.dot_general(
        x, Bm * w[:, None], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (hp, N)
    h_ref[...] = jnp.exp(Ltot) * h_prev + s_chunk

    y_ref[0, :, 0, :] = (y_intra + y_inter).astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _finish():
        hout_ref[0, 0] = h_ref[...].astype(hout_ref.dtype)


def ssd_scan(
    xh: jax.Array,  # (B, S, nh, hp)
    dt: jax.Array,  # (B, S, nh) positive
    A: jax.Array,  # (nh,) negative
    Bm: jax.Array,  # (B, S, N)
    Cm: jax.Array,  # (B, S, N)
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y (B,S,nh,hp) fp32, final state (B,nh,hp,N) fp32)."""
    B, S, nh, hp = xh.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk

    grid = (B, nh, nc)
    kernel = functools.partial(_kernel, chunk=chunk)
    y, hout = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, hp), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, hp), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, 1, hp, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, nh, hp), jnp.float32),
            jax.ShapeDtypeStruct((B, nh, hp, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hp, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xh, dt, A, Bm, Cm)
    return y, hout
