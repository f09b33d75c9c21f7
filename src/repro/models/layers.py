"""Shared model primitives (pure JAX, pytree params — no flax).

Parameters are described by a *schema*: a nested dict whose leaves are
``P(shape, axes, init)``. The same schema yields
  * ``init_params``  — materialised arrays (smoke tests / real training),
  * ``param_specs``  — ShapeDtypeStructs (lowering with no allocation),
  * ``param_axes``   — logical-axis tuples (sharding rules input).
Logical axis names used throughout:
  "embed" (d_model), "heads" (q heads × head_dim fused), "kv_heads",
  "ff" (mlp hidden), "vocab", "experts", "ssm_inner", "conv", "layers",
  "groups" (scan-stacked blocks), ``None`` (replicate).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class P:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"         # normal | zeros | ones | ssm_a | dt_bias
    scale: float = 1.0

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


Schema = Dict[str, Any]          # nested dict of P


def stack_schema(schema: Schema, n: int, axis_name: Optional[str] = "layers") -> Schema:
    """Prepend a stacking dimension (for lax.scan over layers)."""
    out: Schema = {}
    for k, v in schema.items():
        if isinstance(v, dict):
            out[k] = stack_schema(v, n, axis_name)
        else:
            out[k] = P((n, *v.shape), (axis_name, *v.axes), v.init, v.scale)
    return out


def _init_leaf(p: P, key: jax.Array, dtype) -> jax.Array:
    if p.init == "zeros":
        return jnp.zeros(p.shape, dtype)
    if p.init == "ones":
        return jnp.ones(p.shape, dtype)
    if p.init == "ssm_a":        # A_log ~ log(uniform[1,16]) (Mamba2 init)
        u = jax.random.uniform(key, p.shape, jnp.float32, 1.0, 16.0)
        return jnp.log(u).astype(dtype)
    if p.init == "dt_bias":      # softplus^-1 of dt ~ U[1e-3, 1e-1]
        u = jax.random.uniform(key, p.shape, jnp.float32, 1e-3, 1e-1)
        return jnp.log(jnp.expm1(u)).astype(dtype)
    # truncated-normal fan-in init
    fan_in = p.shape[-2] if len(p.shape) >= 2 else p.shape[-1]
    std = p.scale / math.sqrt(max(fan_in, 1))
    return (jax.random.truncated_normal(key, -2.0, 2.0, p.shape, jnp.float32)
            * std).astype(dtype)


def init_params(schema: Schema, rng: jax.Array, dtype=jnp.bfloat16) -> Dict[str, Any]:
    flat = _flatten(schema)
    keys = jax.random.split(rng, max(len(flat), 1))
    leaves = {path: _init_leaf(p, k, dtype) for (path, p), k in zip(flat.items(), keys)}
    return _unflatten(leaves)


def param_specs(schema: Schema, dtype=jnp.bfloat16) -> Dict[str, Any]:
    return jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, dtype),
        schema, is_leaf=lambda x: isinstance(x, P))


def param_axes(schema: Schema) -> Dict[str, Any]:
    return jax.tree.map(lambda p: p.axes, schema,
                        is_leaf=lambda x: isinstance(x, P))


def _flatten(schema: Schema, prefix: str = "") -> Dict[str, P]:
    out: Dict[str, P] = {}
    for k in sorted(schema):
        v = schema[k]
        path = f"{prefix}/{k}"
        if isinstance(v, dict):
            out.update(_flatten(v, path))
        else:
            out[path] = v
    return out


def _unflatten(leaves: Dict[str, Any]) -> Dict[str, Any]:
    root: Dict[str, Any] = {}
    for path, v in leaves.items():
        parts = path.strip("/").split("/")
        d = root
        for p in parts[:-1]:
            d = d.setdefault(p, {})
        d[parts[-1]] = v
    return root


def count_params(tree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------
def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, w_gate)
    u = jnp.einsum("...d,df->...f", x, w_up)
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * u, w_down)


def gelu_mlp(x: jax.Array, w_up: jax.Array, b_up: jax.Array,
             w_down: jax.Array, b_down: jax.Array) -> jax.Array:
    h = jax.nn.gelu(jnp.einsum("...d,df->...f", x, w_up) + b_up)
    return jnp.einsum("...f,fd->...d", h, w_down) + b_down


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim: int, fraction: float, theta: float) -> jax.Array:
    rot = int(head_dim * fraction) // 2 * 2
    return 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))


def apply_rope(x: jax.Array, positions: jax.Array, *, fraction: float = 1.0,
               theta: float = 10000.0) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) or (S,). Rotates the first
    ``fraction`` of each head dim (chatglm's 2d RoPE = fraction 0.5)."""
    d = x.shape[-1]
    rot = int(d * fraction) // 2 * 2
    if rot == 0:
        return x
    freqs = rope_frequencies(d, fraction, theta)            # (rot/2,)
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B,S,rot/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., : rot // 2], xr[..., rot // 2:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1.astype(x.dtype), y2.astype(x.dtype), xp], axis=-1)


def sinusoidal_positions(n: int, d: int) -> jax.Array:
    pos = np.arange(n)[:, None]
    dim = np.arange(0, d, 2)[None, :]
    angle = pos / np.power(10000.0, dim / d)
    out = np.zeros((n, d), np.float32)
    out[:, 0::2] = np.sin(angle)
    out[:, 1::2] = np.cos(angle)
    return jnp.asarray(out)


# ---------------------------------------------------------------------------
# attention (GQA, causal / sliding-window / bidirectional / cross, decode)
# ---------------------------------------------------------------------------
# query-chunking bounds the materialised score tensor to
# (B, H, Q_CHUNK, T) — the difference between fitting and OOMing a 32k
# prefill on 16 GiB chips. The Pallas flash kernel subsumes this on TPU;
# this is the XLA reference path.
Q_CHUNK = 2048
Q_CHUNK_THRESHOLD = 8192


def attention(
    q: jax.Array,                  # (B, S, Hq, D)
    k: jax.Array,                  # (B, T, Hkv, D)
    v: jax.Array,                  # (B, T, Hkv, D)
    *,
    causal: bool = True,
    window: int = 0,               # >0: sliding window (causal implied)
    q_offset: Optional[jax.Array] = None,  # absolute position of q[0]
    kv_len: Optional[jax.Array] = None,    # valid prefix length of k/v
    q_chunk: Optional[int] = None,  # None → auto (chunk when S is large)
) -> jax.Array:
    """XLA reference attention with GQA. Softmax statistics in f32.

    ``q_offset`` supports decode: queries at absolute positions
    offset+0..S-1 against a cache of T slots of which ``kv_len`` are valid.
    """
    B, S, Hq, D = q.shape
    if q_chunk is None and S > Q_CHUNK_THRESHOLD:
        q_chunk = Q_CHUNK
    if q_chunk and S > q_chunk:
        # pad queries to a chunk multiple (e.g. vlm's 32768+576 patches);
        # padded rows compute garbage causally-valid attention and are
        # sliced off — one extra chunk at most.
        pad = (-S) % q_chunk
        qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else q
        n = (S + pad) // q_chunk
        qs = qp.reshape(B, n, q_chunk, Hq, D).transpose(1, 0, 2, 3, 4)
        offs = jnp.arange(n, dtype=jnp.int32) * q_chunk
        if q_offset is not None:
            offs = offs + q_offset

        def body(_, inp):
            qc, off = inp
            return None, _attention_block(qc, k, v, causal=causal,
                                          window=window, q_offset=off,
                                          kv_len=kv_len)

        _, out = jax.lax.scan(body, None, (qs, offs))
        out = out.transpose(1, 0, 2, 3, 4).reshape(B, S + pad, Hq, D)
        return out[:, :S]
    return _attention_block(q, k, v, causal=causal, window=window,
                            q_offset=q_offset, kv_len=kv_len)


def _attention_block(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
    window: int, q_offset: Optional[jax.Array],
    kv_len: Optional[jax.Array],
) -> jax.Array:
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    qh = q.reshape(B, S, Hkv, g, D)
    scale = 1.0 / math.sqrt(D)
    scores = jnp.einsum("bskgd,btkd->bkgst", qh, k).astype(jnp.float32) * scale

    q_pos = jnp.arange(S)[:, None]
    if q_offset is not None:
        q_pos = q_pos + q_offset
    k_pos = jnp.arange(T)[None, :]
    mask = (k_pos <= q_pos) if causal else jnp.ones((S, T), dtype=bool)
    if window > 0:
        mask = mask & (k_pos > q_pos - window)
    if kv_len is not None:
        mask = mask & (k_pos < kv_len)
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(B, S, Hq, D)


def attention_schema(d_model: int, n_heads: int, n_kv_heads: int,
                     head_dim: int, qkv_bias: bool) -> Schema:
    s: Schema = {
        "wq": P((d_model, n_heads * head_dim), ("embed", "heads")),
        "wk": P((d_model, n_kv_heads * head_dim), ("embed", "kv_heads")),
        "wv": P((d_model, n_kv_heads * head_dim), ("embed", "kv_heads")),
        "wo": P((n_heads * head_dim, d_model), ("heads", "embed")),
    }
    if qkv_bias:
        s["bq"] = P((n_heads * head_dim,), ("heads",), "zeros")
        s["bk"] = P((n_kv_heads * head_dim,), ("kv_heads",), "zeros")
        s["bv"] = P((n_kv_heads * head_dim,), ("kv_heads",), "zeros")
    return s


def qkv_project(x: jax.Array, p: Dict[str, jax.Array], n_heads: int,
                n_kv_heads: int, head_dim: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"])
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return (q.reshape(B, S, n_heads, head_dim),
            k.reshape(B, S, n_kv_heads, head_dim),
            v.reshape(B, S, n_kv_heads, head_dim))


def mlp_schema(d_model: int, d_ff: int) -> Schema:
    return {
        "w_gate": P((d_model, d_ff), ("embed", "ff")),
        "w_up": P((d_model, d_ff), ("embed", "ff")),
        "w_down": P((d_ff, d_model), ("ff", "embed")),
    }
