"""Flash attention Pallas TPU kernels: forward, dq and dk/dv; causal,
sliding-window and GQA (DESIGN.md §6).

Layout. The kernels read q, k, v and dO as the model makes them, with the
heads side by side on the lanes: (B, S, H·D), a free reshape of
(B, S, H, D). No transpose and no lane padding in HBM: at D = 64 a
(B·H, S, D) copy would pad every row to 128 lanes. A grid step takes
``hk`` kv heads with their ``g`` = Hq / Hkv query heads each, ``bq`` query
and ``bk`` key positions, and slices each head's D lanes out of the block
in VMEM. The logsumexp and Δ rows are (B, Hq, 1, S): one lane-dense row a
head. In VMEM the forward's running max and sum, and the dq kernel's
logsumexp and Δ, are (bq, 128) blocks equal across their lanes, which
widen to a score tile without a lane broadcast; the forward ran 1.8×
slower on (bq, 1) columns (TPU v5e). The dk/dv kernel takes its scores
keys-major, (bk, bq), where the rows broadcast as they are.

Tiles. ``_tiles`` sizes a grid step from the shapes alone: the largest
divisors of S and T up to ``MAX_BLOCK`` that are multiples of 128, then as
many kv heads as keep ``hk · g · bq · bk`` scores a step within
``SCORE_BUDGET``, on a lane block of whole 128-lane tiles. At
(B, S, H, D) = (4, 1024, 16, 64) that is 4 heads of 512 × 512, 64 grid
steps a call where one head of 128 × 128 took 4,096.

Skips. A (q, kv) block pair that the causal mask, the window or
``kv_len`` leaves empty is neither computed (``pl.when``) nor fetched: its
index map is clamped to the nearest reachable block, so the pipeline sees
the block it already holds and issues no copy. A pair wholly inside the
mask skips the element mask.

Precision. q, k, v and dO go into the MXU in their own dtype with f32
accumulation; p and dS are cast to that dtype before their matmuls, as the
XLA path casts its probabilities. The running max and sum, the rescaling,
the logsumexp and Δ stay f32. f32 inputs contract at ``HIGHEST``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
MAX_BLOCK = 512                 # positions of q or k/v in a block
SCORE_BUDGET = 1 << 20          # f32 scores a grid step holds (4 MiB)
VMEM_DEFAULT = 16 << 20         # Mosaic's scoped VMEM unless raised
_NT = (((1,), (1,)), ((), ()))  # a·bᵀ
_NN = (((1,), (0,)), ((), ()))  # a·b


def _precision(dtype):
    """f32 inputs contract at full f32 precision: Mosaic's default takes
    one bf16 pass over f32 operands, which misses f32 tolerances on the
    chip. Narrower inputs keep the default."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, precision=_precision(a.dtype),
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# tiles
# ---------------------------------------------------------------------------
def _seq_block(n: int, cap: int) -> int:
    """The largest divisor of ``n`` up to ``cap`` that is a multiple of 128
    (else of 8, the sublane tile); ``n`` itself where there is none."""
    for unit in (128, 8):
        for b in range(min(cap, n) // unit * unit, 0, -unit):
            if n % b == 0:
                return b
    return n


def _fit(n: int, block: int) -> int:
    """An explicit block, halved until it divides ``n``."""
    b = min(block, n)
    while n % b:
        b //= 2
    return b


def _tiles(S: int, T: int, Hq: int, Hkv: int, D: int,
           block_q: Optional[int] = None, block_k: Optional[int] = None,
           block_h: Optional[int] = None):
    """(kv heads, q positions, k positions) of a grid step.

    Explicit blocks are taken (halved to divide); the rest follow from the
    shapes: blocks up to ``MAX_BLOCK``, q blocks halved while one kv head's
    g query heads overflow ``SCORE_BUDGET``, then the most kv heads that
    divide Hkv, fill whole 128-lane tiles (or are all of them) and keep
    ``hk · g · bq · bk`` within the budget.
    """
    g = Hq // Hkv
    bq = _fit(S, block_q) if block_q else _seq_block(S, MAX_BLOCK)
    bk = _fit(T, block_k) if block_k else _seq_block(T, MAX_BLOCK)
    if not block_q:
        while g * bq * bk > SCORE_BUDGET and bq > 128:
            smaller = _seq_block(S, bq // 2)
            if smaller >= bq:
                break
            bq = smaller
    if block_h:
        return block_h, bq, bk
    legal = [h for h in range(1, Hkv + 1)
             if Hkv % h == 0 and (h * D % 128 == 0 or h == Hkv)]
    fits = [h for h in legal if h * g * bq * bk <= SCORE_BUDGET]
    return (max(fits) if fits else min(legal)), bq, bk


def _compiler_params(block_bytes: int, scratch_bytes: int, score_elems: int):
    """Grid semantics and, where the tiles need it, a raised VMEM limit:
    double-buffered blocks, the scratch and about six f32 score-sized
    temporaries (s, p and its cast, dP, dS)."""
    need = 2 * block_bytes + scratch_bytes + 6 * 4 * score_elems
    limit = None if need <= VMEM_DEFAULT // 2 else min(2 * need, 100 << 20)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"),
        vmem_limit_bytes=limit)


def _vmem_bytes(shape, dtype) -> int:
    """Bytes of a VMEM block: the minor dim padded to 128 lanes, the
    second-minor to 8 sublanes."""
    *lead, sub, minor = shape
    return (math.prod(lead) * (-(-sub // 8) * 8) * (-(-minor // 128) * 128)
            * jnp.dtype(dtype).itemsize)


# ---------------------------------------------------------------------------
# which block pairs hold work
# ---------------------------------------------------------------------------
def _kv_span(iq, bq, bk, nk, causal, window, kv_len):
    """First and last kv block that q block ``iq`` reaches."""
    lo, hi = 0, nk - 1
    if causal:
        hi = jnp.minimum(hi, (iq * bq + bq - 1) // bk)
    if window > 0:
        lo = jnp.maximum(lo, (iq * bq - window + 1) // bk)
    if kv_len is not None:
        hi = jnp.minimum(hi, (kv_len - 1) // bk)
    return lo, hi


def _q_span(ik, bq, bk, nq, causal, window):
    """First and last q block that reaches kv block ``ik``."""
    lo, hi = 0, nq - 1
    if causal:
        lo = jnp.maximum(lo, (ik * bk) // bq)
    if window > 0:
        hi = jnp.minimum(hi, (ik * bk + bk + window - 2) // bq)
    return lo, hi


def _clamp(i, span):
    lo, hi = span
    return jnp.minimum(jnp.maximum(i, lo), hi)


def _q_major_maps(bq, bk, nk, causal, window, kv_len):
    """Index maps of q, k/v and the statistic rows on a (batch, head
    block, q block, kv block) grid, the kv block clamped into reach."""
    def q_map(b, i, iq, ik):
        return (b, iq, i)

    def kv_map(b, i, iq, ik):
        return (b, _clamp(ik, _kv_span(iq, bq, bk, nk, causal, window,
                                       kv_len)), i)

    def stat_map(b, i, iq, ik):
        return (b, i, 0, iq)

    return q_map, kv_map, stat_map


def _interior(q_lo, k_lo, bq, bk, causal, window, kv_len):
    """Whether no position of the pair is masked (``True`` statically)."""
    inside = True
    if causal:
        inside = k_lo + bk - 1 <= q_lo
    if window > 0:
        inside = jnp.logical_and(inside, k_lo > q_lo + bq - 1 - window)
    if kv_len is not None:
        inside = jnp.logical_and(inside, k_lo + bk <= kv_len)
    return inside


def _mask(bq, bk, q_lo, k_lo, causal, window, kv_len, keys_major=False):
    """The pair's mask, (bq, bk) or with ``keys_major`` (bk, bq)."""
    shape, qa, ka = ((bk, bq), 1, 0) if keys_major else ((bq, bk), 0, 1)
    q = q_lo + jax.lax.broadcasted_iota(jnp.int32, shape, qa)
    k = k_lo + jax.lax.broadcasted_iota(jnp.int32, shape, ka)
    keep = jnp.ones(shape, jnp.bool_)
    if causal:
        keep &= k <= q
    if window > 0:
        keep &= k > q - window
    if kv_len is not None:
        keep &= k < kv_len
    return keep


def _run(step, reach, interior):
    """``step(masked)`` where the pair holds work, unmasked where it can."""
    if interior is True:
        pl.when(reach)(functools.partial(step, False))
        return
    pl.when(jnp.logical_and(reach, interior))(functools.partial(step, False))
    pl.when(jnp.logical_and(reach, jnp.logical_not(interior)))(
        functools.partial(step, True))


def _lanes(h, D):
    return slice(h * D, (h + 1) * D)


def _widen(x, n):
    """A (rows, 128) value, equal across its lanes, at width ``n``: sliced,
    tiled, or (for a width neither allows) one column to broadcast."""
    if n <= 128:
        return x[:, :n]
    if n % 128 == 0:
        return jnp.tile(x, (1, n // 128))
    return x[:, :1]


def _column(row):
    """A (1, n) row as an (n, 128) column block, equal across its lanes."""
    return jnp.broadcast_to(row, (128, row.shape[1])).T


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, scale, causal, window, kv_len, g, D, bq, bk):
    iq, ik, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo, k_lo = iq * bq, ik * bk
    lo, hi = _kv_span(iq, bq, bk, nk, causal, window, kv_len)

    def step(masked):
        keep = (_mask(bq, bk, q_lo, k_lo, causal, window, kv_len)
                if masked else None)
        for j in range(k_ref.shape[2] // D):
            k, v = k_ref[0, :, _lanes(j, D)], v_ref[0, :, _lanes(j, D)]
            for h in range(j * g, (j + 1) * g):
                s = _dot(q_ref[0, :, _lanes(h, D)], k, _NT) * scale
                if keep is not None:
                    s = jnp.where(keep, s, NEG_INF)
                m_prev = m_ref[h]                      # (bq, 128)
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
                p = jnp.exp(s - _widen(m_new, bk))
                alpha = jnp.exp(m_prev - m_new)
                l_ref[h] = alpha * l_ref[h] + jnp.sum(p, 1)[:, None]
                acc_ref[h] = (_widen(alpha, D) * acc_ref[h]
                              + _dot(p.astype(v.dtype), v, _NN))
                m_ref[h] = m_new

    _run(step, jnp.logical_and(ik >= lo, ik <= hi),
         _interior(q_lo, k_lo, bq, bk, causal, window, kv_len))

    @pl.when(ik == nk - 1)
    def _finalize():
        for h in range(acc_ref.shape[0]):
            l = l_ref[h]
            l = jnp.where(l == 0.0, 1.0, l)            # fully masked rows
            o_ref[0, :, _lanes(h, D)] = (
                acc_ref[h] * _widen(1.0 / l, D)).astype(o_ref.dtype)
            lse_ref[0, h] = (m_ref[h] + jnp.log(l)).T[:1]  # a row, for bwd


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int = 0,
                        kv_len: Optional[int] = None,
                        block_q: Optional[int] = None,
                        block_k: Optional[int] = None,
                        block_h: Optional[int] = None,
                        interpret: bool = False):
    """→ (out (B, S, Hq, D), lse (B, Hq, 1, S)).

    ``kv_len`` (static) masks keys from that position on; ``block_*``
    override ``_tiles`` (``block_h`` in kv heads).
    """
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    hk, bq, bk = _tiles(S, T, Hq, Hkv, D, block_q, block_k, block_h)
    nq, nk = S // bq, T // bk
    kv_len = None if kv_len is None or kv_len >= T else kv_len
    q_map, kv_map, stat_map = _q_major_maps(bq, bk, nk, causal, window,
                                            kv_len)
    q_blk, kv_blk = (1, bq, hk * g * D), (1, bk, hk * D)
    stat = (1, hk * g, 1, bq)
    col, acc = (hk * g, bq, 128), (hk * g, bq, D)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=1.0 / math.sqrt(D),
                          causal=causal, window=window, kv_len=kv_len, g=g,
                          D=D, bq=bq, bk=bk),
        grid=(B, Hkv // hk, nq, nk),
        in_specs=[pl.BlockSpec(q_blk, q_map), pl.BlockSpec(kv_blk, kv_map),
                  pl.BlockSpec(kv_blk, kv_map)],
        out_specs=[pl.BlockSpec(q_blk, q_map), pl.BlockSpec(stat, stat_map)],
        out_shape=[jax.ShapeDtypeStruct((B, S, Hq * D), q.dtype),
                   jax.ShapeDtypeStruct((B, Hq, 1, S), jnp.float32)],
        scratch_shapes=[pltpu.VMEM(col, jnp.float32),
                        pltpu.VMEM(col, jnp.float32),
                        pltpu.VMEM(acc, jnp.float32)],
        compiler_params=_compiler_params(
            2 * _vmem_bytes(q_blk, q.dtype) + 2 * _vmem_bytes(kv_blk, k.dtype)
            + _vmem_bytes(stat, jnp.float32),
            2 * _vmem_bytes(col, jnp.float32) + _vmem_bytes(acc, jnp.float32),
            hk * g * bq * bk),
        interpret=interpret,
    )(q.reshape(B, S, Hq * D), k.reshape(B, T, Hkv * D),
      v.reshape(B, T, Hkv * D))
    return out.reshape(B, S, Hq, D), lse


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int = 0,
                           kv_len: Optional[int] = None,
                           block_q: Optional[int] = None,
                           block_k: Optional[int] = None,
                           block_h: Optional[int] = None,
                           interpret: bool = False) -> jax.Array:
    """q: (B, S, Hq, D); k/v: (B, T, Hkv, D) → (B, S, Hq, D)."""
    return flash_attention_fwd(q, k, v, causal=causal, window=window,
                               kv_len=kv_len, block_q=block_q,
                               block_k=block_k, block_h=block_h,
                               interpret=interpret)[0]


# ---------------------------------------------------------------------------
# backward (flash v2 style): one kernel for dq (kv innermost), one for dk/dv
# (q innermost). dS = p ∘ (dO·vᵀ − Δ) with Δ = rowsum(dO ∘ o); p recomputed
# from the saved logsumexp — no S×T materialisation anywhere.
# ---------------------------------------------------------------------------
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, scale, causal, window, kv_len, g, D, bq, bk):
    iq, ik, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo, k_lo = iq * bq, ik * bk
    lo, hi = _kv_span(iq, bq, bk, nk, causal, window, kv_len)

    def step(masked):
        keep = (_mask(bq, bk, q_lo, k_lo, causal, window, kv_len)
                if masked else None)
        for j in range(k_ref.shape[2] // D):
            k, v = k_ref[0, :, _lanes(j, D)], v_ref[0, :, _lanes(j, D)]
            for h in range(j * g, (j + 1) * g):
                s = _dot(q_ref[0, :, _lanes(h, D)], k, _NT) * scale
                p = jnp.exp(s - _widen(_column(lse_ref[0, h]), bk))
                if keep is not None:
                    p = jnp.where(keep, p, 0.0)
                dp = _dot(do_ref[0, :, _lanes(h, D)], v, _NT)
                ds = p * (dp - _widen(_column(delta_ref[0, h]), bk))
                acc_ref[h] += _dot(ds.astype(k.dtype), k, _NN)

    _run(step, jnp.logical_and(ik >= lo, ik <= hi),
         _interior(q_lo, k_lo, bq, bk, causal, window, kv_len))

    @pl.when(ik == nk - 1)
    def _done():
        for h in range(acc_ref.shape[0]):
            dq_ref[0, :, _lanes(h, D)] = (acc_ref[h] * scale).astype(
                dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_acc, dv_acc, *, scale, causal, window, kv_len, g,
                D, bq, bk):
    """Keys major: the scores are (bk, bq), so the logsumexp and Δ rows
    broadcast as they are and every matmul takes its operands untransposed;
    the g query heads of a kv head sum into its dk and dv."""
    ik, jq, nq = pl.program_id(2), pl.program_id(3), pl.num_programs(3)

    @pl.when(jq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_lo, k_lo = jq * bq, ik * bk
    lo, hi = _q_span(ik, bq, bk, nq, causal, window)
    reach = jnp.logical_and(jq >= lo, jq <= hi)
    if kv_len is not None:
        reach = jnp.logical_and(reach, k_lo < kv_len)

    def step(masked):
        keep = (_mask(bq, bk, q_lo, k_lo, causal, window, kv_len,
                      keys_major=True) if masked else None)
        for j in range(dk_acc.shape[0]):
            k, v = k_ref[0, :, _lanes(j, D)], v_ref[0, :, _lanes(j, D)]
            for h in range(j * g, (j + 1) * g):
                q = q_ref[0, :, _lanes(h, D)]
                do = do_ref[0, :, _lanes(h, D)]
                st = _dot(k, q, _NT) * scale           # (bk, bq)
                pt = jnp.exp(st - lse_ref[0, h])
                if keep is not None:
                    pt = jnp.where(keep, pt, 0.0)
                dv_acc[j] += _dot(pt.astype(do.dtype), do, _NN)
                dst = pt * (_dot(v, do, _NT) - delta_ref[0, h])
                dk_acc[j] += _dot(dst.astype(q.dtype), q, _NN)

    _run(step, reach, _interior(q_lo, k_lo, bq, bk, causal, window, kv_len))

    @pl.when(jq == nq - 1)
    def _done():
        for j in range(dk_acc.shape[0]):
            dk_ref[0, :, _lanes(j, D)] = (dk_acc[j] * scale).astype(
                dk_ref.dtype)
            dv_ref[0, :, _lanes(j, D)] = dv_acc[j].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, *, causal: bool = True,
                        window: int = 0, kv_len: Optional[int] = None,
                        block_q: Optional[int] = None,
                        block_k: Optional[int] = None,
                        block_h: Optional[int] = None,
                        interpret: bool = False):
    """Returns (dq, dk, dv). lse: (B, Hq, 1, S) from the forward."""
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    hk, bq, bk = _tiles(S, T, Hq, Hkv, D, block_q, block_k, block_h)
    nq, nk = S // bq, T // bk
    kv_len = None if kv_len is None or kv_len >= T else kv_len
    # Δ = rowsum(dO ∘ o) — cheap elementwise precompute, a row a head
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
    delta = delta.transpose(0, 2, 1)[:, :, None, :]
    args = (q.reshape(B, S, Hq * D), k.reshape(B, T, Hkv * D),
            v.reshape(B, T, Hkv * D), do.reshape(B, S, Hq * D), lse, delta)

    q_blk, kv_blk = (1, bq, hk * g * D), (1, bk, hk * D)
    stat = (1, hk * g, 1, bq)
    blocks = (2 * _vmem_bytes(q_blk, q.dtype) + 2 * _vmem_bytes(kv_blk, k.dtype)
              + 2 * _vmem_bytes(stat, jnp.float32))
    kw = dict(scale=1.0 / math.sqrt(D), causal=causal, window=window,
              kv_len=kv_len, g=g, D=D, bq=bq, bk=bk)

    # dq: grid (batch, head block, q block, kv block), kv innermost
    q_map, kv_map, stat_map = _q_major_maps(bq, bk, nk, causal, window,
                                            kv_len)
    acc = (hk * g, bq, D)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        grid=(B, Hkv // hk, nq, nk),
        in_specs=[pl.BlockSpec(q_blk, q_map), pl.BlockSpec(kv_blk, kv_map),
                  pl.BlockSpec(kv_blk, kv_map), pl.BlockSpec(q_blk, q_map),
                  pl.BlockSpec(stat, stat_map),
                  pl.BlockSpec(stat, stat_map)],
        out_specs=pl.BlockSpec(q_blk, q_map),
        out_shape=jax.ShapeDtypeStruct((B, S, Hq * D), q.dtype),
        scratch_shapes=[pltpu.VMEM(acc, jnp.float32)],
        compiler_params=_compiler_params(
            blocks + _vmem_bytes(q_blk, q.dtype),
            _vmem_bytes(acc, jnp.float32), hk * g * bq * bk),
        interpret=interpret,
    )(*args)

    # dk/dv: grid (batch, head block, kv block, q block), q innermost
    def q_map_t(b, i, ik, jq):
        return (b, _clamp(jq, _q_span(ik, bq, bk, nq, causal, window)), i)

    def kv_map_t(b, i, ik, jq):
        return (b, ik, i)

    def stat_map_t(b, i, ik, jq):
        return (b, i, 0, _clamp(jq, _q_span(ik, bq, bk, nq, causal, window)))

    acc = (hk, bk, D)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        grid=(B, Hkv // hk, nk, nq),
        in_specs=[pl.BlockSpec(q_blk, q_map_t),
                  pl.BlockSpec(kv_blk, kv_map_t),
                  pl.BlockSpec(kv_blk, kv_map_t),
                  pl.BlockSpec(q_blk, q_map_t),
                  pl.BlockSpec(stat, stat_map_t),
                  pl.BlockSpec(stat, stat_map_t)],
        out_specs=[pl.BlockSpec(kv_blk, kv_map_t),
                   pl.BlockSpec(kv_blk, kv_map_t)],
        out_shape=[jax.ShapeDtypeStruct((B, T, Hkv * D), k.dtype),
                   jax.ShapeDtypeStruct((B, T, Hkv * D), v.dtype)],
        scratch_shapes=[pltpu.VMEM(acc, jnp.float32),
                        pltpu.VMEM(acc, jnp.float32)],
        compiler_params=_compiler_params(
            blocks + 2 * _vmem_bytes(kv_blk, k.dtype),
            2 * _vmem_bytes(acc, jnp.float32), hk * g * bq * bk),
        interpret=interpret,
    )(*args)

    return (dq.reshape(B, S, Hq, D), dk.reshape(B, T, Hkv, D),
            dv.reshape(B, T, Hkv, D))
