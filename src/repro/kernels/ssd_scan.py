"""Mamba2 SSD chunked-scan Pallas TPU kernel.

Grid = (B·H, S/Q): the chunk axis is innermost and sequential on TPU, so the
inter-chunk SSM state h (P×N, f32) lives in VMEM scratch and flows across
grid steps — the recurrence costs no HBM round-trips. Within a chunk the
dual (attention-like) form runs on the MXU:

    L   = exp(segsum(dA))            (Q×Q lower-triangular decay)
    y   = (C·Bᵀ ∘ L) · (dt·x)        intra-chunk
        + (C · h_in) ∘ exp(cumsum dA) inter-chunk
    h' += decay-weighted chunk state

Q (chunk) and P (head dim) are the MXU tile knobs; N (SSM state) rides the
lane dimension. Group-to-head mapping (GVA) happens in the B/C index maps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, h_ref, *,
                chunk: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    # f32 inputs contract at full f32 precision (Mosaic's default is one
    # bf16 pass); the prefix sums always do, since exp(segsum) amplifies
    # their error
    hi = jax.lax.Precision.HIGHEST
    prec = hi if x_ref.dtype == jnp.float32 else None
    x = x_ref[0].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)        # (Q, 1) — padded lane dim
    a = a_ref[0].astype(jnp.float32)          # (1, 1)
    b = b_ref[0].astype(jnp.float32)          # (Q, N)
    c = c_ref[0].astype(jnp.float32)          # (Q, N)

    dA = dt * a                               # (Q, 1), ≤ 0
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive prefix sums as matmuls with the lower-triangular ones
    # matrix (Mosaic has no cumsum): dAb[t, j] = dA[t], so
    # cum_col[i, j] = Σ_{t≤i} dA[t] = cum[i] and cum_row[i, j] = cum[j]
    ones = tri.astype(jnp.float32)
    dAb = jnp.broadcast_to(dA, (chunk, chunk))
    cum_col = jax.lax.dot_general(ones, dAb, (((1,), (0,)), ((), ())),
                                  precision=hi,
                                  preferred_element_type=jnp.float32)
    cum_row = jax.lax.dot_general(dAb, ones, (((0,), (1,)), ((), ())),
                                  precision=hi,
                                  preferred_element_type=jnp.float32)
    cum = cum_col[:, :1]                      # (Q, 1) inclusive
    # segsum(i, j) = cum[i] - cum[j]  for i ≥ j (strictly: sum_{j+1..i})
    L = jnp.where(tri, jnp.exp(cum_col - cum_row), 0.0)

    xdt = x * dt                              # (Q, P)
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())), precision=prec,
                             preferred_element_type=jnp.float32)   # (Q, Q)
    y = jax.lax.dot_general(cb * L, xdt, (((1,), (0,)), ((), ())),
                            precision=prec,
                            preferred_element_type=jnp.float32)    # (Q, P)

    # inter-chunk: contribution of the incoming state
    h_in = h_ref[...]                         # (P, N)
    decay_in = jnp.exp(cum)                   # (Q, 1)
    y += decay_in * jax.lax.dot_general(
        c, h_in, (((1,), (1,)), ((), ())), precision=prec,
        preferred_element_type=jnp.float32)   # (Q, N)·(P, N)ᵀ → (Q, P)

    # state update: h' = h·exp(sum dA) + Σ_s exp(cum[-1]-cum[s]) dt_s x_s B_sᵀ
    total = jnp.sum(dA, axis=0, keepdims=True)  # (1, 1)
    w = jnp.exp(total - cum)                  # (Q, 1)
    hs = jax.lax.dot_general(xdt * w, b, (((0,), (0,)), ((), ())),
                             precision=prec,
                             preferred_element_type=jnp.float32)   # (P, N)
    h_ref[...] = h_in * jnp.exp(total) + hs
    y_ref[0] = y.astype(y_ref.dtype)


def ssd_scan_pallas(xh: jax.Array, dt: jax.Array, a: jax.Array,
                    B_: jax.Array, C_: jax.Array, *, chunk: int = 256,
                    interpret: bool = False):
    """xh: (B, S, H, P); dt: (B, S, H); a: (H,); B_/C_: (B, S, G, N).

    Returns (y: (B, S, H, P), h_final is not emitted — training path only).
    """
    Bb, S, H, P = xh.shape
    G, N = B_.shape[2], B_.shape[3]
    R = H // G
    Q = min(chunk, S)
    while S % Q:
        Q //= 2
    nc = S // Q

    xr = xh.transpose(0, 2, 1, 3).reshape(Bb * H, S, P)
    dtr = dt.transpose(0, 2, 1).reshape(Bb * H, S, 1)
    ar = a.reshape(H, 1, 1)
    br = B_.transpose(0, 2, 1, 3).reshape(Bb * G, S, N)
    cr = C_.transpose(0, 2, 1, 3).reshape(Bb * G, S, N)

    def bc_index(bh, ic):
        b, h = bh // H, bh % H
        return (b * G + h // R, ic, 0)

    y = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=Q),
        grid=(Bb * H, nc),
        in_specs=[
            pl.BlockSpec((1, Q, P), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, Q, 1), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, 1, 1), lambda bh, ic: (bh % H, 0, 0)),
            pl.BlockSpec((1, Q, N), bc_index),
            pl.BlockSpec((1, Q, N), bc_index),
        ],
        out_specs=pl.BlockSpec((1, Q, P), lambda bh, ic: (bh, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((Bb * H, S, P), xh.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xr, dtr, ar, br, cr)
    return y.reshape(Bb, H, S, P).transpose(0, 2, 1, 3), None
