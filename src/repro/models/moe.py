"""Mixture-of-Experts FFN: top-k router + capacity-bounded dispatch.

Dispatch is **gather/scatter based** (sorted-position via one-hot cumsum),
NOT the GShard einsum form: the (tokens × experts × capacity) dispatch einsum
would cost T·E·C·d FLOPs — for qwen3's 128 experts that would exceed the
expert compute itself by 100×. Here positions are integer bookkeeping
(no matmul FLOPs) and the only matmuls are the expert GEMMs. The Pallas
``moe_gmm`` kernel computes the same expert GEMMs; no model calls it yet.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ..configs.base import MoEConfig
from .layers import P, Schema


def _constrain(x: jax.Array, *axes) -> jax.Array:
    """Best-effort sharding constraint: ``x`` placed by ``axes`` on the
    mesh in context, or ``x`` itself where no mesh is in context or the
    mesh lacks one of the axes."""
    try:
        return jax.lax.with_sharding_constraint(x, PartitionSpec(*axes))
    except (RuntimeError, ValueError):   # no mesh in context / axis missing
        return x


def moe_schema(d_model: int, moe: MoEConfig) -> Schema:
    ff = moe.d_ff_expert
    e = moe.n_experts
    return {
        "router": P((d_model, e), ("embed", "experts")),
        "w_gate": P((e, d_model, ff), ("experts", "embed", "ff")),
        "w_up": P((e, d_model, ff), ("experts", "embed", "ff")),
        "w_down": P((e, ff, d_model), ("experts", "ff", "embed")),
    }


def moe_ffn(x: jax.Array, p: Dict[str, jax.Array], moe: MoEConfig,
            ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) → (y, aux_loss). Capacity-dropped tokens pass through
    residually (their expert contribution is zero), as in Switch/Mixtral."""
    B, S, d = x.shape
    E, K = moe.n_experts, moe.top_k
    T = B * S
    xt = x.reshape(T, d)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                     # (T, E)
    gate, expert_idx = jax.lax.top_k(probs, K)                  # (T, K)
    gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)   # renormalise

    # aux load-balance loss (Switch): E * Σ_e f_e · p̄_e
    assign1 = jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32)
    aux = E * jnp.sum(assign1.mean(0) * probs.mean(0))

    capacity = int(max(1, round(T * K / E * moe.capacity_factor)))
    capacity = min(capacity, T)
    if T <= 256:
        # decode / tiny batches: capacity = T guarantees no token drops, so
        # step-by-step decode is exactly consistent with teacher forcing
        # (the buffers stay small: E·T·d)
        capacity = T

    # position of each (token, slot) within its expert, in (t, k) order
    flat_e = expert_idx.reshape(T * K)                          # (TK,)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)         # (TK, E)
    pos_all = jnp.cumsum(onehot, axis=0) - onehot               # exclusive
    pos = jnp.take_along_axis(pos_all, flat_e[:, None], axis=1)[:, 0]
    keep = pos < capacity                                       # (TK,)

    pos_c = jnp.where(keep, pos, 0)                             # (TK,)
    xk = jnp.repeat(xt, K, axis=0)                              # token per slot
    contrib = jnp.where(keep[:, None], xk, 0)
    # dispatch buffers are the MoE memory hot-spot (E·C·d and E·C·ff): shard
    # capacity over the data axis (so gradient accumulation stays
    # data-local) and the expert hidden dim over model — without
    # constraints they (and their backward cotangents) replicate per
    # device. The 2-D indexed scatter/gather keeps (E, C, d) shape
    # throughout so one constraint covers fwd + bwd.
    buf = jnp.zeros((E, capacity, d), x.dtype).at[flat_e, pos_c].add(contrib)
    buf = _constrain(buf, None, "data", None)

    # expert GEMMs (the only matmul FLOPs in the MoE layer)
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    h = _constrain(jax.nn.silu(g) * u, None, "data", "model")
    out = jnp.einsum("ecf,efd->ecd", h, p["w_down"])            # (E, C, d)
    out = _constrain(out, None, "data", None)

    y_slots = out[flat_e, pos_c]                                # (TK, d)
    y_slots = _constrain(y_slots, "data", None)
    w = (gate.reshape(T * K) * keep).astype(x.dtype)
    y = (y_slots * w[:, None]).reshape(T, K, d).sum(axis=1)
    return y.reshape(B, S, d), aux.astype(jnp.float32)
