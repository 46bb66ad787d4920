"""Mixture-of-Experts layer: top-k routing, capacity-based dispatch.

Design (shardable under pjit auto-SPMD):

* routing + position-in-expert are computed **per batch row**, so the
  dispatch never serializes across the data axis;
* tokens are scattered into an ``(E, B, C, d)`` buffer (experts sharded on
  the ``model`` axis ⇒ expert parallelism; batch on ``data``) — the
  token→expert redistribution lowers to all-to-all-style collectives;
* expert FFNs run as one grouped einsum over the stacked (E, d, ff)
  weights — MXU-shaped, no ragged shapes;
* tokens over capacity ``C = ceil(cf · S · k / E)`` are dropped (standard
  Switch-style capacity dropping, cf = 1.25).

Supports qwen2-moe (shared experts + routed) and arctic (dense-residual
FFN in parallel with the routed experts).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.common import dense_init, swiglu_apply, swiglu_init


def moe_init(key, cfg, dtype) -> dict:
    d = cfg.d_model
    e_ff = cfg.moe_d_ff or cfg.d_ff
    E = cfg.num_experts
    kr, kg, ku, kd, ks, kdr = jax.random.split(key, 6)
    scale = 1.0 / math.sqrt(d)
    p = {
        "router": dense_init(kr, d, E, jnp.float32),
        "experts": {
            "gate": (jax.random.normal(kg, (E, d, e_ff), jnp.float32) * scale).astype(dtype),
            "up": (jax.random.normal(ku, (E, d, e_ff), jnp.float32) * scale).astype(dtype),
            "down": (jax.random.normal(kd, (E, e_ff, d), jnp.float32) / math.sqrt(e_ff)).astype(dtype),
        },
    }
    if cfg.num_shared_experts:
        p["shared"] = swiglu_init(ks, d, cfg.num_shared_experts * e_ff, dtype)
        p["shared_gate"] = dense_init(kdr, d, 1, jnp.float32)
    if cfg.dense_residual:
        p["dense_ffn"] = swiglu_init(kdr, d, cfg.d_ff, dtype)
    return p


def moe_apply(p: dict, x: jax.Array, cfg, *, capacity_factor: float = 1.25) -> jax.Array:
    """x: (B, S, d) -> (B, S, d)."""
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    C = max(1, math.ceil(capacity_factor * S * k / E))

    logits = (x.astype(jnp.float32) @ p["router"]).astype(jnp.float32)  # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)  # (B,S,k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # --- per-row position-in-expert (B, S*k) ------------------------------
    flat_e = top_e.reshape(B, S * k)
    oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (B, S*k, E)
    pos = jnp.cumsum(oh, axis=1) - 1  # position among same-expert slots
    pos_of = jnp.take_along_axis(pos, flat_e[..., None], axis=2)[..., 0]  # (B,S*k)
    keep = pos_of < C
    pos_clip = jnp.where(keep, pos_of, C)  # dropped slots land in a scratch slot

    # --- scatter tokens into (E, B, C+1, d) expert buffers ------------------
    tok = jnp.repeat(x, k, axis=1)  # (B, S*k, d) token replicated per slot
    buf = jnp.zeros((E, B, C + 1, d), x.dtype)
    b_idx = jnp.broadcast_to(jnp.arange(B)[:, None], (B, S * k))
    buf = buf.at[flat_e, b_idx, pos_clip].add(tok, mode="drop")
    buf = buf[:, :, :C]  # drop scratch slot

    # --- grouped expert FFN -------------------------------------------------
    w = p["experts"]
    g = jax.nn.silu(jnp.einsum("ebcd,edf->ebcf", buf, w["gate"]))
    u = jnp.einsum("ebcd,edf->ebcf", buf, w["up"])
    eo = jnp.einsum("ebcf,efd->ebcd", g * u, w["down"])  # (E,B,C,d)

    # --- gather back + combine ----------------------------------------------
    eo = jnp.concatenate([eo, jnp.zeros((E, B, 1, d), eo.dtype)], axis=2)
    back = eo[flat_e, b_idx, pos_clip]  # (B, S*k, d)
    back = back * (keep[..., None] * top_w.reshape(B, S * k)[..., None]).astype(back.dtype)
    out = back.reshape(B, S, k, d).sum(axis=2)

    # --- shared experts / dense residual ------------------------------------
    if "shared" in p:
        sh = swiglu_apply(p["shared"], x)
        gate = jax.nn.sigmoid((x.astype(jnp.float32) @ p["shared_gate"])).astype(x.dtype)
        out = out + sh * gate
    if "dense_ffn" in p:
        out = out + swiglu_apply(p["dense_ffn"], x)
    return out


def moe_aux_loss(p: dict, x: jax.Array, cfg) -> jax.Array:
    """Load-balancing auxiliary loss (Switch-style f·P)."""
    logits = (x.astype(jnp.float32) @ p["router"])
    probs = jax.nn.softmax(logits, axis=-1)  # (B,S,E)
    top_e = jax.lax.top_k(probs, cfg.num_experts_per_tok)[1]
    E = cfg.num_experts
    frac = jax.nn.one_hot(top_e, E).mean(axis=(0, 1, 2))  # fraction routed
    imp = probs.mean(axis=(0, 1))  # mean router prob
    return E * jnp.sum(frac * imp)


# ---------------------------------------------------------------------------
# Expert-parallel shard_map path (§Perf iteration A1).
#
# The auto-SPMD scatter dispatch above forces XLA to all-gather expert
# weights (8 TB/chip/step on arctic train_4k).  Here experts stay
# stationary: the residual stream is replicated across the ``model`` axis
# (Megatron invariant), so every model column already holds every token —
# each column simply *filters* the (token, slot) pairs routed to its local
# E/mp experts, computes them, and the per-column partial outputs combine
# with one psum over ``model``.  Collective cost per layer: one
# activation-sized all-reduce — the same class as a dense FFN, with zero
# token or weight movement.
# ---------------------------------------------------------------------------


def _local_expert_compute(x, logits, w_gate, w_up, w_down, *, e_base, E, k, C):
    """One (data, model) shard: route all local tokens to local experts.

    x (T, d); logits (T, E) fp32; local experts are [e_base, e_base+E_loc).
    Returns the partial combined output (T, d).
    """
    E_loc = w_gate.shape[0]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)  # (T, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    flat_e = top_e.reshape(-1)  # (T*k,) global expert ids
    flat_w = top_w.reshape(-1)
    local = (flat_e >= e_base) & (flat_e < e_base + E_loc)
    loc_e = jnp.where(local, flat_e - e_base, E_loc)  # E_loc = drop bucket

    oh = jax.nn.one_hot(loc_e, E_loc + 1, dtype=jnp.int32)
    pos = jnp.cumsum(oh, axis=0) - 1
    pos_of = jnp.take_along_axis(pos, loc_e[:, None], axis=1)[:, 0]
    keep = local & (pos_of < C)
    pos_clip = jnp.where(keep, pos_of, C)
    loc_e_c = jnp.where(keep, loc_e, E_loc)

    T = x.shape[0]
    tok_idx = jnp.repeat(jnp.arange(T), k)
    buf = jnp.zeros((E_loc + 1, C + 1, x.shape[1]), x.dtype)
    buf = buf.at[loc_e_c, pos_clip].add(x[tok_idx], mode="drop")
    buf = buf[:E_loc, :C]

    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate))
    u = jnp.einsum("ecd,edf->ecf", buf, w_up)
    eo = jnp.einsum("ecf,efd->ecd", g * u, w_down)  # (E_loc, C, d)

    eo = jnp.pad(eo, ((0, 1), (0, 1), (0, 0)))
    back = eo[loc_e_c, pos_clip]  # (T*k, d)
    back = back * (keep * flat_w)[:, None].astype(back.dtype)
    return jnp.zeros_like(x).at[tok_idx].add(back)


def moe_apply_ep(
    p: dict, x: jax.Array, cfg, mesh, *, capacity_factor: float = 1.25
) -> jax.Array:
    """Expert-parallel MoE over ``mesh`` (model axis = EP)."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    mp = mesh.shape.get("model", 1)
    assert E % mp == 0, (E, mp)
    E_loc = E // mp
    dp_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]
    b_spec = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)
    b_ok = B % max(dp, 1) == 0 and dp > 1
    x_spec = P(b_spec if b_ok else None, None, None)

    def local_fn(xl, router, w_gate, w_up, w_down):
        # xl (B_loc, S, d) — identical across model columns
        j = jax.lax.axis_index("model")
        T = xl.shape[0] * xl.shape[1]
        x2 = xl.reshape(T, d)
        logits = x2.astype(jnp.float32) @ router
        C = max(1, math.ceil(capacity_factor * T * k / E))
        out = _local_expert_compute(
            x2, logits, w_gate, w_up, w_down,
            e_base=j * E_loc, E=E, k=k, C=C,
        )
        out = jax.lax.psum(out, "model")
        return out.reshape(xl.shape)

    w = p["experts"]
    out = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            x_spec, P(None, None),
            P("model", None, None), P("model", None, None), P("model", None, None),
        ),
        out_specs=x_spec,
        check_vma=False,
    )(x, p["router"], w["gate"], w["up"], w["down"])

    if "shared" in p:
        sh = swiglu_apply(p["shared"], x)
        gate = jax.nn.sigmoid((x.astype(jnp.float32) @ p["shared_gate"])).astype(x.dtype)
        out = out + sh * gate
    if "dense_ffn" in p:
        out = out + swiglu_apply(p["dense_ffn"], x)
    return out
