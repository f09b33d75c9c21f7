"""Mamba2 / SSD (state-space duality) blocks + pure-SSM LM stack.

Implements the chunked SSD computation of Dao & Gu (arXiv:2405.21060):
within a chunk the dual "attention" form (MXU-friendly matmuls), across
chunks a linear state recurrence via ``lax.scan``. This is the XLA reference
path; ``kernels/ssd_scan`` provides the Pallas TPU version of the same
algorithm. Decode runs the O(1)-per-token recurrent form with a
(conv_state, ssm_state) cache.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, SSMConfig
from .layers import P, Schema, rmsnorm


def ssm_dims(cfg: ModelConfig) -> Tuple[int, int, int, int]:
    s = cfg.ssm
    assert s is not None
    d_in = s.expand * cfg.d_model
    nh = d_in // s.head_dim
    return d_in, nh, s.n_groups, s.state_dim


def mamba_schema(cfg: ModelConfig) -> Schema:
    s = cfg.ssm
    assert s is not None
    d_in, nh, g, n = ssm_dims(cfg)
    conv_ch = d_in + 2 * g * n
    proj_out = 2 * d_in + 2 * g * n + nh
    return {
        "in_proj": P((cfg.d_model, proj_out), ("embed", "ssm_inner")),
        "conv_w": P((s.conv_width, conv_ch), (None, "ssm_inner")),
        "conv_b": P((conv_ch,), ("ssm_inner",), "zeros"),
        "a_log": P((nh,), (None,), "ssm_a"),
        "dt_bias": P((nh,), (None,), "dt_bias"),
        "d_skip": P((nh,), (None,), "ones"),
        "norm": P((d_in,), ("ssm_inner",), "ones"),
        "out_proj": P((d_in, cfg.d_model), ("ssm_inner", "embed")),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv along seq. x: (B, S, C); w: (W, C)."""
    W = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(W))
    return out + b


def _prefix_sum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum of float32 x along its last axis, as one matmul
    with the lower-triangular ones matrix.

    XLA lowers ``jnp.cumsum`` to a reduce-window that sums a whole window
    for each output, O(Q²) adds on the vector units, and its transpose in
    the backward is another; a matmul runs on the MXU and transposes to a
    matmul. HIGHEST keeps the sum at float32 accuracy (the TPU's default
    is one bfloat16 pass), since exp(segsum) amplifies its error.
    """
    Q = x.shape[-1]
    tri = jnp.tril(jnp.ones((Q, Q), jnp.float32))             # [i, j]: j ≤ i
    return jax.lax.dot_general(x, tri, (((x.ndim - 1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _segsum(cs: jax.Array) -> jax.Array:
    """cs: (..., Q) inclusive prefix sums of x → (..., Q, Q);
    [i, j] = cs[i] - cs[j] = Σ_{k=j+1..i} x[k]; -inf above diag."""
    Q = cs.shape[-1]
    diff = cs[..., :, None] - cs[..., None, :]
    ok = jnp.tril(jnp.ones((Q, Q), bool))
    return jnp.where(ok, diff, -jnp.inf)


def ssd_chunked(xh: jax.Array, dt: jax.Array, a: jax.Array,
                B_: jax.Array, C_: jax.Array, chunk: int,
                h0: Optional[jax.Array] = None,
                ) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan.

    xh: (B, S, H, Pd) head inputs;  dt: (B, S, H) (post-softplus);
    a:  (H,) negative decay rates;  B_, C_: (B, S, G, N), H = G·R.
    Returns (y: (B, S, H, Pd), final_state: (B, H, Pd, N) in float32).

    The decays run in float32 whatever the inputs' dtype, as in mamba_ssm:
    dt·a, its cumsum within a chunk (|Σ| reaches thousands at chunk 256,
    where a bfloat16 step is 16–32), the segment sums, every exp of them
    and the state carried across chunks. The matmul operands (x·dt, B, C,
    the decays and the carried state) go in at ``xh``'s dtype. The cumsum
    is taken once, as a matmul (``_prefix_sum``), and feeds the segment
    sums and every decay.
    """
    Bb, S, H, Pd = xh.shape
    G, N = B_.shape[2], B_.shape[3]
    R = H // G
    assert S % chunk == 0, f"seq {S} not divisible by chunk {chunk}"
    nc = S // chunk
    cdt = xh.dtype
    dt = dt.astype(jnp.float32)

    # fold dt into x (the "discretised input"), dA per step
    x_dt = (xh * dt[..., None]).astype(cdt)                     # (B,S,H,Pd)
    dA = dt * a.astype(jnp.float32)[None, None, :]              # (B,S,H) ≤ 0

    def r4(t, last):  # (B, S, ...) → (B, nc, chunk, ...)
        return t.reshape(Bb, nc, chunk, *last)

    xc = r4(x_dt, (G, R, Pd))
    dAc = r4(dA, (G, R)).transpose(0, 3, 4, 1, 2)               # (B,G,R,c,l)
    Bc = r4(B_, (G, N))
    Cc = r4(C_, (G, N))

    dA_cum = _prefix_sum(dAc)                                   # (B,G,R,c,l)
    L = jnp.exp(_segsum(dA_cum))                                # (B,G,R,c,l,l)

    # intra-chunk (dual / attention-like form)
    y_diag = jnp.einsum("bclgn,bcsgn,bgrcls,bcsgrp->bclgrp",
                        Cc, Bc, L.astype(cdt), xc)

    # chunk summary states: (B, c, G, R, Pd, N)
    decay_states = jnp.exp(dA_cum[..., -1:] - dA_cum)           # (B,G,R,c,l)
    states = jnp.einsum("bclgn,bgrcl,bclgrp->bcgrpn",
                        Bc, decay_states.astype(cdt), xc)

    # inter-chunk recurrence h_{c+1} = h_c * exp(ΣdA_c) + S_c
    chunk_decay = jnp.exp(dA_cum[..., -1])                      # (B,G,R,c)
    h0 = (jnp.zeros((Bb, G, R, Pd, N), jnp.float32) if h0 is None
          else h0.astype(jnp.float32))

    def step(h, inp):
        dec, s = inp                                            # (B,G,R), (B,G,R,Pd,N)
        h_new = h * dec[..., None, None] + s
        return h_new, h                                         # emit state *entering* chunk

    decay_t = chunk_decay.transpose(3, 0, 1, 2)                 # (c,B,G,R)
    states_t = states.transpose(1, 0, 2, 3, 4, 5)               # (c,B,G,R,Pd,N)
    h_final, h_in = jax.lax.scan(step, h0, (decay_t, states_t))
    h_in = h_in.transpose(1, 0, 2, 3, 4, 5)                     # (B,c,G,R,Pd,N)

    # inter-chunk contribution
    state_decay = jnp.exp(dA_cum)                               # (B,G,R,c,l)
    y_off = jnp.einsum("bclgn,bcgrpn,bgrcl->bclgrp",
                       Cc, h_in.astype(cdt), state_decay.astype(cdt))

    y = (y_diag + y_off).reshape(Bb, nc, chunk, H, Pd)
    return y.reshape(Bb, S, H, Pd), h_final.reshape(Bb, H, Pd, N)


def mamba_block(x: jax.Array, p: Dict[str, jax.Array], cfg: ModelConfig,
                use_pallas: bool = False) -> jax.Array:
    """Full Mamba2 block (training/prefill path). x: (B, S, d_model)."""
    s = cfg.ssm
    assert s is not None
    d_in, nh, g, n = ssm_dims(cfg)
    Bb, S, _ = x.shape

    zxbcdt = jnp.einsum("bsd,dk->bsk", x, p["in_proj"])
    z, xBC, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * g * n], axis=-1)
    xBC = jax.nn.silu(_causal_conv(xBC, p["conv_w"], p["conv_b"]))
    xs, B_, C_ = jnp.split(xBC, [d_in, d_in + g * n], axis=-1)
    xh = xs.reshape(Bb, S, nh, s.head_dim)
    B_ = B_.reshape(Bb, S, g, n)
    C_ = C_.reshape(Bb, S, g, n)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["a_log"].astype(jnp.float32))

    chunk = min(s.chunk, S)
    while S % chunk:
        chunk //= 2
    with jax.named_scope("ssd"):
        if use_pallas:
            from ..kernels import ops as kops
            y, _ = kops.ssd_scan(xh, dt.astype(x.dtype), a.astype(x.dtype),
                                 B_, C_, chunk=chunk)
        else:
            y, _ = ssd_chunked(xh, dt, a, B_, C_, chunk=chunk)
    y = y + xh * p["d_skip"].astype(x.dtype)[None, None, :, None]
    y = y.reshape(Bb, S, d_in)
    y = rmsnorm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    return jnp.einsum("bsk,kd->bsd", y, p["out_proj"])


# ---------------------------------------------------------------------------
# decode (recurrent form)
# ---------------------------------------------------------------------------
def mamba_cache_shape(cfg: ModelConfig, batch: int):
    s = cfg.ssm
    d_in, nh, g, n = ssm_dims(cfg)
    conv_ch = d_in + 2 * g * n
    return {
        "conv": (batch, s.conv_width - 1, conv_ch),
        "ssm": (batch, nh, s.head_dim, n),
    }


def mamba_decode_step(x: jax.Array, cache: Dict[str, jax.Array],
                      p: Dict[str, jax.Array], cfg: ModelConfig,
                      ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token step. x: (B, d_model); cache: {"conv", "ssm"}."""
    s = cfg.ssm
    assert s is not None
    d_in, nh, g, n = ssm_dims(cfg)
    Bb = x.shape[0]

    zxbcdt = jnp.einsum("bd,dk->bk", x, p["in_proj"])
    z, xBC, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * g * n], axis=-1)

    # causal conv over (cached W-1 inputs + current)
    conv_in = jnp.concatenate([cache["conv"], xBC[:, None, :]], axis=1)  # (B,W,C)
    conv_out = jnp.einsum("bwc,wc->bc", conv_in, p["conv_w"]) + p["conv_b"]
    xBC_t = jax.nn.silu(conv_out)
    new_conv = conv_in[:, 1:, :]

    xs, B_, C_ = jnp.split(xBC_t, [d_in, d_in + g * n], axis=-1)
    xh = xs.reshape(Bb, nh, s.head_dim)
    B_ = B_.reshape(Bb, g, n)
    C_ = C_.reshape(Bb, g, n)
    r = nh // g

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    dA = jnp.exp(dt * a[None, :])                                # (B,nh)

    h = cache["ssm"].reshape(Bb, g, r, s.head_dim, n)
    xdt = (xh * dt[..., None]).reshape(Bb, g, r, s.head_dim)
    h_new = (h * dA.reshape(Bb, g, r)[..., None, None].astype(h.dtype)
             + jnp.einsum("bgrp,bgn->bgrpn", xdt.astype(h.dtype),
                          B_.astype(h.dtype)))
    y = jnp.einsum("bgn,bgrpn->bgrp", C_.astype(h.dtype), h_new)
    y = y.reshape(Bb, nh, s.head_dim) + xh * p["d_skip"].astype(x.dtype)[None, :, None]
    y = y.reshape(Bb, d_in)
    y = rmsnorm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    out = jnp.einsum("bk,kd->bd", y, p["out_proj"])
    return out, {"conv": new_conv, "ssm": h_new.reshape(Bb, nh, s.head_dim, n)}


# ---------------------------------------------------------------------------
# pure-SSM language model stack (mamba2-370m family)
# ---------------------------------------------------------------------------
def ssm_lm_schema(cfg: ModelConfig) -> Schema:
    from .layers import stack_schema
    layer = {"ln": P((cfg.d_model,), ("embed",), "ones"), **mamba_schema(cfg)}
    return {
        "embed": {"table": P((cfg.vocab, cfg.d_model), ("vocab", "embed"))},
        "layers": stack_schema(layer, cfg.n_layers, "layers"),
        "final_norm": P((cfg.d_model,), ("embed",), "ones"),
        "lm_head": P((cfg.d_model, cfg.vocab), ("embed", "vocab")),
    }


def ssm_forward(cfg: ModelConfig, params, tokens: jax.Array,
                remat: str = "block", use_pallas: bool = False):
    with jax.named_scope("embed"):
        x = params["embed"]["table"][tokens]

    def body(h, p):
        return h + mamba_block(rmsnorm(h, p["ln"], cfg.norm_eps), p, cfg,
                               use_pallas), None

    if remat != "none":
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    with jax.named_scope("head_loss"):
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    return logits, jnp.zeros((), jnp.float32)


def ssm_cache_shapes(cfg: ModelConfig, batch: int, max_len: int = 0):
    ms = mamba_cache_shape(cfg, batch)
    return {"conv": (cfg.n_layers, *ms["conv"]),
            "ssm": (cfg.n_layers, *ms["ssm"])}


def ssm_init_cache(cfg: ModelConfig, batch: int, max_len: int = 0,
                   dtype=jnp.bfloat16):
    return {k: jnp.zeros(s, dtype)
            for k, s in ssm_cache_shapes(cfg, batch, max_len).items()}


def ssm_decode_step(cfg: ModelConfig, params, cache, token: jax.Array,
                    pos: jax.Array):
    x = params["embed"]["table"][token]          # (B, d)

    def body(h, inp):
        p, cg = inp
        y, st = mamba_decode_step(rmsnorm(h, p["ln"], cfg.norm_eps), cg, p, cfg)
        return h + y, st

    x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bd,dv->bv", x, params["lm_head"])
    return logits, new_cache
