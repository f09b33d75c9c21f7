"""Plain reference of a dense decoder-only transformer: pre-norm layers of
grouped-query attention (RoPE, optional QKV bias) and a SwiGLU MLP, the
head tied to the embedding or its own.

The configuration's keys are Hugging Face's (``num_attention_heads``,
``intermediate_size``, ``tie_word_embeddings``...). The loss is taken row
by row under ``vmap``: no term couples the rows of a block.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

import flops
import reference as R

SCOPES = ()              # the shared scopes cover every part of the model
INITS: Dict[str, Any] = {}


def layout(cfg: Dict[str, Any]) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    d, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, ff = cfg["head_dim"], cfg["intermediate_size"]
    s = (L, 1)           # (layers, pattern of one full-attention layer)
    out = {
        "embed/table": ((V, d), "normal"),
        "final_norm": ((d,), "ones"),
        "blocks/ln1": (s + (d,), "ones"),
        "blocks/ln2": (s + (d,), "ones"),
        "blocks/attn/wq": (s + (d, H * hd), "normal"),
        "blocks/attn/wk": (s + (d, Hkv * hd), "normal"),
        "blocks/attn/wv": (s + (d, Hkv * hd), "normal"),
        "blocks/attn/wo": (s + (H * hd, d), "normal"),
        "blocks/ffn/w_gate": (s + (d, ff), "normal"),
        "blocks/ffn/w_up": (s + (d, ff), "normal"),
        "blocks/ffn/w_down": (s + (ff, d), "normal"),
    }
    if cfg["qkv_bias"]:
        out.update({"blocks/attn/bq": (s + (H * hd,), "zeros"),
                    "blocks/attn/bk": (s + (Hkv * hd,), "zeros"),
                    "blocks/attn/bv": (s + (Hkv * hd,), "zeros")})
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = ((d, V), "normal")
    return out


def _layer(cfg, mm, x, p):
    """One pre-norm transformer layer on one row. x: (S, d)."""
    S = x.shape[0]
    H, Hkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = R.rmsnorm(x, p["ln1"], eps)
    q = mm("sd,dh->sh", h, p["wq"])
    k = mm("sd,dh->sh", h, p["wk"])
    v = mm("sd,dh->sh", h, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = R.rope(q.reshape(S, H, D), theta)
    k = R.rope(k.reshape(S, Hkv, D), theta)
    v = v.reshape(S, Hkv, D)
    rep = H // Hkv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = mm("shd,thd->hst", q, k) / math.sqrt(D)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = mm("hst,thd->shd", probs, v).reshape(S, H * D)
    x = x + mm("sh,hd->sd", attn, p["wo"])
    h = R.rmsnorm(x, p["ln2"], eps)
    g = mm("sd,df->sf", h, p["w_gate"])
    u = mm("sd,df->sf", h, p["w_up"])
    return x + mm("sf,fd->sd", jax.nn.silu(g) * u, p["w_down"])


def _row_loss(cfg, mm, params, tokens, labels):
    stack = {k.split("/")[-1]: v[:, 0] for k, v in params.items()
             if k.startswith("blocks/")}
    return R.lm_row_loss(mm, params, tokens, labels, partial(_layer, cfg, mm),
                         stack, cfg["rms_norm_eps"],
                         cfg["tie_word_embeddings"])


def block_loss(cfg, mm, params, tokens, labels):
    """Mean cross-entropy of a block of rows. params: flat by path,
    float32; tokens, labels: (rows, S)."""
    return jnp.mean(jax.vmap(partial(_row_loss, cfg, mm, params))(tokens,
                                                                   labels))


def matmul_params(cfg: Dict) -> int:
    """Weights that enter a matrix product once per token (the head
    included, the embedding lookup not)."""
    d, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, ff = cfg["head_dim"], cfg["intermediate_size"]
    attn = d * H * hd * 2 + d * Hkv * hd * 2            # q, o; k, v
    layer = attn + 3 * d * ff                           # SwiGLU
    return L * layer + d * V


def mixer_flops_per_token(cfg: Dict, seq: int) -> float:
    return flops.attention_flops_per_token(cfg, seq)
