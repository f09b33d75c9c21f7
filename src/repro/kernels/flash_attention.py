"""Flash attention (forward) Pallas TPU kernel: causal / sliding-window / GQA.

TPU adaptation of the flash algorithm (DESIGN.md §6): q/k/v blocks are tiled
into VMEM with MXU-aligned shapes (block_q × head_dim and block_k × head_dim,
multiples of 128 where the head dim allows); the online-softmax statistics
(m, l) and the f32 accumulator live in VMEM scratch and persist across the
innermost (kv) grid dimension, which TPU executes sequentially. Sliding
windows skip nothing structurally (grid is static) but fully-masked kv
blocks short-circuit via ``pl.when`` so they cost neither DMA waits nor MXU
issue slots on real hardware.

GQA is expressed in the BlockSpec index maps: the kv block index maps
q-head → kv-head (h // group), so no repeated K/V materialisation.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_NT = ((1,), (1,))      # a·bᵀ
_NN = ((1,), (0,))      # a·b
_TN = ((0,), (0,))      # aᵀ·b


def _precision(dtype):
    """f32 inputs contract at full f32 precision: Mosaic's default takes
    one bf16 pass over f32 operands, which misses f32 tolerances on the
    chip. Narrower inputs keep the default."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _dot(a, b, contract, precision):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                  acc_ref, *, scale: float, causal: bool, window: int,
                  block_q: int, block_k: int, kv_len: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = iq * block_q
    k_lo = ik * block_k
    # block-level reachability (static grid; dynamic skip)
    reachable = True
    if causal:
        reachable = k_lo <= q_lo + block_q - 1
    in_window = True
    if window > 0:
        in_window = k_lo + block_k - 1 > q_lo - window

    @pl.when(jnp.asarray(reachable) & jnp.asarray(in_window))
    def _compute():
        prec = _precision(q_ref.dtype)
        q = q_ref[0].astype(jnp.float32)               # (bq, d)
        k = k_ref[0].astype(jnp.float32)               # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = _dot(q, k, _NT, prec) * scale
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < kv_len
        if causal:
            mask &= k_pos <= q_pos
        if window > 0:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                            # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + _dot(p, v, _NN, prec)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)                # fully-masked rows
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        # logsumexp rows — consumed by the backward kernels
        lse_ref[0] = m_ref[...] + jnp.log(l)


def _blocks(S: int, T: int, block_q: int, block_k: int):
    bq = min(block_q, S)
    bk = min(block_k, T)
    while S % bq:
        bq //= 2
    while T % bk:
        bk //= 2
    return bq, bk


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int = 0,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = False):
    """→ (out (B,S,Hq,D), lse (B*Hq, S, 1)).

    The logsumexp rows keep a trailing unit dim: a ``(1, bq, 1)`` block is
    one the TPU accepts (second-minor a multiple of 8, minor the whole
    dim), where a ``(1, bq)`` block of a 2-D array is not.
    """
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    bq, bk = _blocks(S, T, block_q, block_k)
    scale = 1.0 / math.sqrt(D)

    qr = q.transpose(0, 2, 1, 3).reshape(B * Hq, S, D)
    kr = k.transpose(0, 2, 1, 3).reshape(B * Hkv, T, D)
    vr = v.transpose(0, 2, 1, 3).reshape(B * Hkv, T, D)

    def kv_index(h, iq, ik):
        b, hq = h // Hq, h % Hq
        return (b * Hkv + hq // group, ik, 0)

    out, lse = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          window=window, block_q=bq, block_k=bk, kv_len=T),
        grid=(B * Hq, S // bq, T // bk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda h, iq, ik: (h, iq, 0)),
            pl.BlockSpec((1, bk, D), kv_index),
            pl.BlockSpec((1, bk, D), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda h, iq, ik: (h, iq, 0)),
            pl.BlockSpec((1, bq, 1), lambda h, iq, ik: (h, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hq, S, D), q.dtype),
            jax.ShapeDtypeStruct((B * Hq, S, 1), jnp.float32),
        ],
        scratch_shapes=[
            # (bq, 1) running max / sum, (bq, D) f32 accumulator — VMEM
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, Hq, S, D).transpose(0, 2, 1, 3), lse


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False) -> jax.Array:
    """q: (B, S, Hq, D); k/v: (B, T, Hkv, D) → (B, S, Hq, D)."""
    return flash_attention_fwd(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)[0]


# ---------------------------------------------------------------------------
# backward (flash v2 style): one kernel for dq (kv innermost), one for dk/dv
# (q innermost). ds = p ∘ (do·vᵀ − Δ) with Δ = rowsum(do ∘ o); p recomputed
# from the saved logsumexp — no S×T materialisation anywhere.
# ---------------------------------------------------------------------------
def _mask(s_shape, q_lo, k_lo, causal, window, kv_len):
    q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, s_shape, 0)
    k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s_shape, 1)
    m = k_pos < kv_len
    if causal:
        m &= k_pos <= q_pos
    if window > 0:
        m &= k_pos > q_pos - window
    return m


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, acc_ref, *, scale, causal, window,
                         block_q, block_k, kv_len):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = pl.program_id(1) * block_q
    k_lo = ik * block_k
    reachable = (k_lo <= q_lo + block_q - 1) if causal else True
    in_window = (k_lo + block_k - 1 > q_lo - window) if window > 0 else True

    @pl.when(jnp.asarray(reachable) & jnp.asarray(in_window))
    def _compute():
        prec = _precision(q_ref.dtype)
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = _dot(q, k, _NT, prec) * scale
        mask = _mask(s.shape, q_lo, k_lo, causal, window, kv_len)
        p = jnp.where(mask, jnp.exp(s - lse_ref[0]), 0.0)
        ds = p * (_dot(do, v, _NT, prec) - delta_ref[0])
        acc_ref[...] += _dot(ds, k, _NN, prec) * scale

    @pl.when(ik == nk - 1)
    def _done():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                          window, block_q, block_k, kv_len, nq_per_head):
    jq = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(jq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    k_lo = pl.program_id(1) * block_k
    # jq walks (group × q-blocks); the q row block is jq % nq_per_head
    q_lo = (jq % nq_per_head) * block_q

    prec = _precision(q_ref.dtype)
    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    s = _dot(q, k, _NT, prec) * scale
    mask = _mask(s.shape, q_lo, k_lo, causal, window, kv_len)
    p = jnp.where(mask, jnp.exp(s - lse_ref[0]), 0.0)
    dv_acc[...] += _dot(p, do, _TN, prec)
    ds = p * (_dot(do, v, _NT, prec) - delta_ref[0])
    dk_acc[...] += _dot(ds, q, _TN, prec) * scale

    @pl.when(jq == nq - 1)
    def _done():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, *, causal=True, window=0,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = False):
    """Returns (dq, dk, dv). lse: (B*Hq, S, 1) from the forward."""
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    bq, bk = _blocks(S, T, block_q, block_k)
    scale = 1.0 / math.sqrt(D)
    nq = S // bq

    qr = q.transpose(0, 2, 1, 3).reshape(B * Hq, S, D)
    kr = k.transpose(0, 2, 1, 3).reshape(B * Hkv, T, D)
    vr = v.transpose(0, 2, 1, 3).reshape(B * Hkv, T, D)
    dor = do.transpose(0, 2, 1, 3).reshape(B * Hq, S, D)
    # Δ = rowsum(do ∘ o) — cheap elementwise precompute
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1).reshape(B * Hq, S, 1)

    def kv_index(h, iq, ik):
        b, hq = h // Hq, h % Hq
        return (b * Hkv + hq // group, ik, 0)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, scale=scale, causal=causal,
                          window=window, block_q=bq, block_k=bk, kv_len=T),
        grid=(B * Hq, nq, T // bk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda h, iq, ik: (h, iq, 0)),
            pl.BlockSpec((1, bk, D), kv_index),
            pl.BlockSpec((1, bk, D), kv_index),
            pl.BlockSpec((1, bq, D), lambda h, iq, ik: (h, iq, 0)),
            pl.BlockSpec((1, bq, 1), lambda h, iq, ik: (h, iq, 0)),
            pl.BlockSpec((1, bq, 1), lambda h, iq, ik: (h, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda h, iq, ik: (h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
    )(qr, kr, vr, dor, lse, delta)

    # dk/dv: grid walks (b·kv-head, k-block, group·q-blocks); the q-side
    # index map routes each (group, q-block) pair to the right q head
    def q_index(hk, ik, j):
        b, hkv = hk // Hkv, hk % Hkv
        g, iq = j // nq, j % nq
        return (b * Hq + hkv * group + g, iq, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, scale=scale, causal=causal,
                          window=window, block_q=bq, block_k=bk, kv_len=T,
                          nq_per_head=nq),
        grid=(B * Hkv, T // bk, group * nq),
        in_specs=[
            pl.BlockSpec((1, bq, D), q_index),
            pl.BlockSpec((1, bk, D), lambda hk, ik, j: (hk, ik, 0)),
            pl.BlockSpec((1, bk, D), lambda hk, ik, j: (hk, ik, 0)),
            pl.BlockSpec((1, bq, D), q_index),
            pl.BlockSpec((1, bq, 1), q_index),
            pl.BlockSpec((1, bq, 1), q_index),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda hk, ik, j: (hk, ik, 0)),
            pl.BlockSpec((1, bk, D), lambda hk, ik, j: (hk, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hkv, T, D), k.dtype),
            jax.ShapeDtypeStruct((B * Hkv, T, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        interpret=interpret,
    )(qr, kr, vr, dor, lse, delta)

    rs = lambda t, H: t.reshape(B, H, -1, D).transpose(0, 2, 1, 3)  # noqa: E731
    return rs(dq, Hq), rs(dk, Hkv), rs(dv, Hkv)
