"""Decoder-only transformer stack (dense / MoE / VLM families).

Layers are stacked and driven by ``lax.scan`` to bound HLO size and compile
time at 56 layers. Architectures with repeating layer *patterns* (gemma3's
5 local : 1 global) scan over superblocks: params carry a leading
(groups, pattern_len) stack and the scan body unrolls the pattern.

KV caches are per-kind: "full" layers cache all positions; "local"
(sliding-window) layers keep a **ring buffer of window slots** — at 500k
context this is the difference between 4 GB and 500 GB of cache.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import (
    P,
    Schema,
    attention,
    attention_schema,
    mlp_schema,
    qkv_project,
    rmsnorm,
    stack_schema,
    swiglu,
    apply_rope,
)
from .moe import _constrain, moe_ffn, moe_schema

# Sequence parallelism (SP): shard the residual stream's seq dim over the
# "model" axis when a *global* microbatch residual exceeds this threshold.
# Shrinks the per-layer saved carries (the remat stacks) by the TP degree;
# XLA inserts the gather at attention where full sequence is needed.
SEQ_SHARD_MIN_BYTES = 256 << 20


def maybe_seq_shard(h: jax.Array) -> jax.Array:
    if h.ndim == 3 and h.size * h.dtype.itemsize > SEQ_SHARD_MIN_BYTES:
        return _constrain(h, "data", "model", None)
    return h


# ---------------------------------------------------------------------------
# layer pattern
# ---------------------------------------------------------------------------
def layer_pattern(cfg: ModelConfig) -> List[str]:
    if cfg.local_global > 0:
        return ["local"] * cfg.local_global + ["full"]
    if cfg.window > 0:
        return ["window"]
    return ["full"]


def n_groups(cfg: ModelConfig) -> int:
    pat = layer_pattern(cfg)
    assert cfg.n_layers % len(pat) == 0, (cfg.n_layers, pat)
    return cfg.n_layers // len(pat)


def _window_of(cfg: ModelConfig, kind: str) -> int:
    if kind == "local":
        return cfg.local_window
    if kind == "window":
        return cfg.window
    return 0


# ---------------------------------------------------------------------------
# schema
# ---------------------------------------------------------------------------
def block_schema(cfg: ModelConfig) -> Schema:
    s: Schema = {
        "ln1": P((cfg.d_model,), ("embed",), "ones"),
        "attn": attention_schema(cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                 cfg.head_dim_, cfg.qkv_bias),
        "ln2": P((cfg.d_model,), ("embed",), "ones"),
    }
    if cfg.family == "moe":
        assert cfg.moe is not None
        s["ffn"] = moe_schema(cfg.d_model, cfg.moe)
    else:
        s["ffn"] = mlp_schema(cfg.d_model, cfg.d_ff)
    return s


def lm_schema(cfg: ModelConfig) -> Schema:
    pat = layer_pattern(cfg)
    g = n_groups(cfg)
    blocks = stack_schema(stack_schema(block_schema(cfg), len(pat), "pattern"),
                          g, "layers")
    s: Schema = {
        "embed": {"table": P((cfg.vocab, cfg.d_model), ("vocab", "embed"))},
        "blocks": blocks,
        "final_norm": P((cfg.d_model,), ("embed",), "ones"),
    }
    if not cfg.tie_embeddings:
        s["lm_head"] = P((cfg.d_model, cfg.vocab), ("embed", "vocab"))
    if cfg.vision is not None:
        s["vision_proj"] = P((cfg.vision.patch_dim, cfg.d_model),
                             (None, "embed"))
    return s


# ---------------------------------------------------------------------------
# forward (train / prefill): full-sequence causal
# ---------------------------------------------------------------------------
def _block(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array,
           positions: jax.Array, kind: str, use_pallas: bool = False,
           interpret: Optional[bool] = None) -> Tuple[jax.Array, jax.Array]:
    # named scopes label the step's device ops by layer in the HLO metadata
    # (and so in a profile); they leave the computation as it is
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    with jax.named_scope("attn_proj"):
        q, k, v = qkv_project(h, p["attn"], cfg.n_heads, cfg.n_kv_heads,
                              cfg.head_dim_)
        q = apply_rope(q, positions, fraction=cfg.rope_fraction,
                       theta=cfg.rope_theta)
        k = apply_rope(k, positions, fraction=cfg.rope_fraction,
                       theta=cfg.rope_theta)
    win = _window_of(cfg, kind)
    with jax.named_scope("attn_core"):
        if use_pallas:
            from ..kernels import ops as kops
            attn = kops.flash_attention(q, k, v, causal=True, window=win,
                                        interpret=interpret)
        else:
            attn = attention(q, k, v, causal=True, window=win)
    B, S = x.shape[:2]
    with jax.named_scope("attn_proj"):
        x = x + jnp.einsum("bsh,hd->bsd", attn.reshape(B, S, -1),
                           p["attn"]["wo"])

    h = rmsnorm(x, p["ln2"], cfg.norm_eps)
    with jax.named_scope("mlp"):
        if cfg.family == "moe":
            # nested remat: during the layer backward, re-dispatch instead
            # of holding E×C×ff expert intermediates + cotangents at once
            y, aux = jax.checkpoint(
                lambda hh, pp: moe_ffn(hh, pp, cfg.moe))(h, p["ffn"])
        else:
            y = swiglu(h, p["ffn"]["w_gate"], p["ffn"]["w_up"],
                       p["ffn"]["w_down"])
            aux = jnp.zeros((), jnp.float32)
    return x + y, aux


def embed_inputs(cfg: ModelConfig, params: Dict[str, Any],
                 tokens: jax.Array,
                 patches: Optional[jax.Array] = None) -> jax.Array:
    with jax.named_scope("embed"):
        x = params["embed"]["table"][tokens]
        if patches is not None and cfg.vision is not None:
            pe = jnp.einsum("bpc,cd->bpd", patches.astype(x.dtype),
                            params["vision_proj"])
            x = jnp.concatenate([pe, x], axis=1)
        return x


def forward(cfg: ModelConfig, params: Dict[str, Any], tokens: jax.Array,
            patches: Optional[jax.Array] = None, remat: str = "block",
            use_pallas: bool = False, interpret: Optional[bool] = None,
            ) -> Tuple[jax.Array, jax.Array]:
    """→ (logits over the *token* positions, aux_loss).

    ``use_pallas`` runs causal attention as the flash kernel, in interpret
    mode where ``interpret`` says (None: unless the backend is a TPU)."""
    x = embed_inputs(cfg, params, tokens, patches)
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :]
    pat = layer_pattern(cfg)

    def group_body(carry, gp):
        h, aux = carry
        h = maybe_seq_shard(h)
        for i, kind in enumerate(pat):
            pi = jax.tree.map(lambda a: a[i], gp)
            h, a = _block(cfg, pi, h, positions, kind, use_pallas,
                          interpret)
            aux = aux + a
        return (maybe_seq_shard(h), aux), None

    if remat != "none":
        group_body = jax.checkpoint(
            group_body, policy=jax.checkpoint_policies.nothing_saveable)
    (x, aux), _ = jax.lax.scan(group_body,
                               (x, jnp.zeros((), jnp.float32)),
                               params["blocks"])
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if patches is not None and cfg.vision is not None:
        x = x[:, cfg.vision.n_patches:, :]       # logits for text positions
    logits = unembed(cfg, params, x)
    return logits, aux


def unembed(cfg: ModelConfig, params: Dict[str, Any], x: jax.Array) -> jax.Array:
    with jax.named_scope("head_loss"):
        if cfg.tie_embeddings:
            return jnp.einsum("bsd,vd->bsv", x, params["embed"]["table"])
        return jnp.einsum("bsd,dv->bsv", x, params["lm_head"])


# ---------------------------------------------------------------------------
# KV cache + decode
# ---------------------------------------------------------------------------
def cache_shapes(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    """Ring-buffered window slots for local layers; full slots otherwise."""
    pat = layer_pattern(cfg)
    g = n_groups(cfg)
    hd, hkv = cfg.head_dim_, cfg.n_kv_heads
    shapes: Dict[str, Any] = {}
    for kind in ("full", "window", "local"):
        cnt = sum(1 for k in pat if k == kind)
        if cnt == 0:
            continue
        w = _window_of(cfg, kind)
        slots = max_len if w == 0 else min(w, max_len)
        shapes[kind] = {
            "k": (g, cnt, batch, slots, hkv, hd),
            "v": (g, cnt, batch, slots, hkv, hd),
        }
    return shapes


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict[str, Any]:
    return jax.tree.map(lambda s: jnp.zeros(s, dtype),
                        cache_shapes(cfg, batch, max_len),
                        is_leaf=lambda x: isinstance(x, tuple))


def decode_step(cfg: ModelConfig, params: Dict[str, Any],
                cache: Dict[str, Any], token: jax.Array, pos: jax.Array,
                ) -> Tuple[jax.Array, Dict[str, Any]]:
    """One decode step. token: (B,) int32; pos: () current absolute position
    (number of tokens already in cache). Returns (logits (B, V), new cache).

    The scan consumes the cache as per-group xs (leading dim = groups) and
    re-emits the updated per-group slices, so the cache round-trips through
    the step functionally (and in-place with buffer donation).
    """
    x = params["embed"]["table"][token][:, None, :]      # (B, 1, d)
    positions = jnp.full((1, 1), pos, jnp.int32)
    pat = layer_pattern(cfg)
    kind_of: List[Tuple[str, int]] = []
    counters: Dict[str, int] = {}
    for k in pat:
        kind_of.append((k, counters.get(k, 0)))
        counters[k] = counters.get(k, 0) + 1

    def scan_body(h, inp):
        gp, cache_g = inp          # cache_g leaves: (cnt, B, slots, hkv, hd)
        for i, kind in enumerate(pat):
            pi = jax.tree.map(lambda a: a[i], gp)
            knd, slot = kind_of[i]
            w = _window_of(cfg, knd)
            hh = rmsnorm(h, pi["ln1"], cfg.norm_eps)
            q, k, v = qkv_project(hh, pi["attn"], cfg.n_heads,
                                  cfg.n_kv_heads, cfg.head_dim_)
            q = apply_rope(q, positions, fraction=cfg.rope_fraction,
                           theta=cfg.rope_theta)
            k = apply_rope(k, positions, fraction=cfg.rope_fraction,
                           theta=cfg.rope_theta)
            kc, vc = cache_g[knd]["k"], cache_g[knd]["v"]
            slots = kc.shape[2]
            write = jnp.where(w > 0, pos % slots, pos)
            k_all = jax.lax.dynamic_update_slice(
                kc[slot], k, (0, write, 0, 0))    # (B, slots, hkv, hd)
            v_all = jax.lax.dynamic_update_slice(
                vc[slot], v, (0, write, 0, 0))
            kv_len = jnp.minimum(pos + 1, slots)
            o = attention(q, k_all, v_all, causal=False, kv_len=kv_len)
            B = h.shape[0]
            h = h + jnp.einsum("bsh,hd->bsd", o.reshape(B, 1, -1),
                               pi["attn"]["wo"])
            hh = rmsnorm(h, pi["ln2"], cfg.norm_eps)
            if cfg.family == "moe":
                y, _ = moe_ffn(hh, pi["ffn"], cfg.moe)
            else:
                y = swiglu(hh, pi["ffn"]["w_gate"], pi["ffn"]["w_up"],
                           pi["ffn"]["w_down"])
            h = h + y
            cache_g = {
                **cache_g,
                knd: {"k": kc.at[slot].set(k_all),
                      "v": vc.at[slot].set(v_all)},
            }
        return h, cache_g

    x, new_cache = jax.lax.scan(scan_body, x, (params["blocks"], cache))
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(cfg, params, x)[:, 0, :]
    return logits, new_cache

