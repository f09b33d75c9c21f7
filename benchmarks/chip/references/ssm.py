"""Plain reference of a Mamba-2 stack: pre-norm layers of an input
projection, a causal depthwise convolution, the SSD recurrence in its
quadratic "attention" form over the whole row, a gated RMSNorm and an
output projection; the head tied to the embedding or its own.

The configuration's keys are those of ``mamba_ssm`` (``expand``,
``state_size``, ``n_groups``, ``conv_kernel``, ``tie_embeddings``...). The
loss is taken row by row under ``vmap``: no term couples the rows of a
block.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

import flops
import reference as R

SCOPES = ("ssd",)


def _a_log(key, shape):
    """A = -exp(a_log), a_log = log U[1, 16]."""
    u = jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
    return jnp.log(u).astype(jnp.bfloat16)


def _dt_bias(key, shape):
    """softplus^-1 of U[1e-3, 1e-1]."""
    u = jax.random.uniform(key, shape, jnp.float32, 1e-3, 1e-1)
    return jnp.log(jnp.expm1(u)).astype(jnp.bfloat16)


INITS = {"ssm_a": _a_log, "dt_bias": _dt_bias}


def _sizes(cfg):
    d_in = cfg["expand"] * cfg["hidden_size"]
    return (d_in, d_in // cfg["head_dim"], cfg["n_groups"], cfg["state_size"],
            cfg["conv_kernel"])


def layout(cfg: Dict[str, Any]) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    d, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    d_in, nh, G, N, W = _sizes(cfg)
    conv_ch = d_in + 2 * G * N
    out = {
        "embed/table": ((V, d), "normal"),
        "final_norm": ((d,), "ones"),
        "layers/ln": ((L, d), "ones"),
        "layers/in_proj": ((L, d, 2 * d_in + 2 * G * N + nh), "normal"),
        "layers/conv_w": ((L, W, conv_ch), "normal"),
        "layers/conv_b": ((L, conv_ch), "zeros"),
        "layers/a_log": ((L, nh), "ssm_a"),
        "layers/dt_bias": ((L, nh), "dt_bias"),
        "layers/d_skip": ((L, nh), "ones"),
        "layers/norm": ((L, d_in), "ones"),
        "layers/out_proj": ((L, d_in, d), "normal"),
    }
    if not cfg["tie_embeddings"]:
        out["lm_head"] = ((d, V), "normal")
    return out


def _ssd(xh, dt, a, B, C, mm):
    """y[t] = Σ_{s≤t} (C_t·B_s) exp(Σ_{k=s+1..t} dt_k a) dt_s x_s, the
    quadratic form of the SSD recurrence over the whole row.
    xh: (S, H, P); dt: (S, H); a: (H,); B, C: (S, G, N)."""
    S, H, _ = xh.shape
    G = B.shape[1]
    cs = jnp.cumsum(dt * a, axis=0)                         # (S, H)
    causal = jnp.tril(jnp.ones((S, S), bool))[:, :, None]
    seg = jnp.where(causal, cs[:, None, :] - cs[None, :, :], 0.0)
    decay = jnp.where(causal, jnp.exp(seg), 0.0)            # (t, s, H)
    cb = mm("tgn,sgn->tsg", C, B)                           # (t, s, G)
    cb = jnp.repeat(cb, H // G, axis=2)                     # (t, s, H)
    return mm("tsh,shp->thp", decay * cb, xh * dt[..., None])


def _layer(cfg, mm, x, p):
    """One Mamba-2 layer (pre-norm, residual) on one row. x: (S, d)."""
    d_in, nh, G, N, W = _sizes(cfg)
    P_ = cfg["head_dim"]
    eps = cfg["norm_epsilon"]
    S = x.shape[0]
    h = R.rmsnorm(x, p["ln"], eps)
    zxbcdt = mm("sd,dk->sk", h, p["in_proj"])
    z = zxbcdt[:, :d_in]
    xbc = zxbcdt[:, d_in: 2 * d_in + 2 * G * N]
    dt = zxbcdt[:, 2 * d_in + 2 * G * N:]
    pad = jnp.pad(xbc, ((W - 1, 0), (0, 0)))
    xbc = sum(pad[i: i + S] * p["conv_w"][i] for i in range(W)) + p["conv_b"]
    xbc = jax.nn.silu(xbc)
    xs = xbc[:, :d_in].reshape(S, nh, P_)
    B = xbc[:, d_in: d_in + G * N].reshape(S, G, N)
    C = xbc[:, d_in + G * N:].reshape(S, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["a_log"])
    y = _ssd(xs, dt, a, B, C, mm) + xs * p["d_skip"][None, :, None]
    y = R.rmsnorm(y.reshape(S, d_in) * jax.nn.silu(z), p["norm"], eps)
    return x + mm("sk,kd->sd", y, p["out_proj"])


def _row_loss(cfg, mm, params, tokens, labels):
    stack = {k.split("/")[-1]: v for k, v in params.items()
             if k.startswith("layers/")}
    return R.lm_row_loss(mm, params, tokens, labels, partial(_layer, cfg, mm),
                         stack, cfg["norm_epsilon"], cfg["tie_embeddings"])


def block_loss(cfg, mm, params, tokens, labels):
    """Mean cross-entropy of a block of rows. params: flat by path,
    float32; tokens, labels: (rows, S)."""
    return jnp.mean(jax.vmap(partial(_row_loss, cfg, mm, params))(tokens,
                                                                   labels))


def matmul_params(cfg: Dict) -> int:
    """Weights that enter a matrix product once per token (the head
    included, the embedding lookup not)."""
    d, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    d_in, nh, _, _, _ = _sizes(cfg)
    gn = cfg["n_groups"] * cfg["state_size"]
    layer = d * (2 * d_in + 2 * gn + nh) + d_in * d     # in_proj, out_proj
    return L * layer + d * V


def mixer_flops_per_token(cfg: Dict, seq: int) -> float:
    return flops.ssd_flops_per_token(cfg, seq)
