"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracles."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.flash_attention import (
    flash_attention_bwd,
    flash_attention_fwd,
    flash_attention_pallas,
)
from repro.kernels.moe_gmm import moe_gmm_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas

RNG = np.random.default_rng(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("shape", [(1, 7, 64), (4, 33, 128), (2, 256, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(shape, dtype):
    x = jnp.asarray(RNG.normal(0, 1, shape), dtype)
    s = jnp.asarray(RNG.normal(1, 0.1, shape[-1:]), dtype)
    got = rmsnorm_pallas(x, s, interpret=True)
    want = ref.rmsnorm_ref(x, s)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def _case(*args, tiles=None, dtype=None, id=None):
    """A flash case; the first cases keep their ids from before ``tiles``
    (and, for the backward, ``dtype``) were parameters."""
    extra = () if dtype is None else (dtype,)
    return pytest.param(*args, *extra, tiles,
                        id=id or "-".join(map(str, args)))


# tiles: (block_q, block_k, block_h) — None takes the shape rule (_tiles)
@pytest.mark.parametrize("S,T,Hq,Hkv,D,causal,window,tiles", [
    _case(128, 128, 4, 4, 64, True, 0, tiles=(64, 64, None)),   # MHA causal
    _case(128, 128, 8, 2, 64, True, 0, tiles=(64, 64, None)),   # GQA 4:1
    _case(256, 256, 4, 1, 32, True, 64, tiles=(64, 64, None)),  # MQA + window
    _case(64, 192, 4, 2, 64, False, 0, tiles=(64, 64, None)),   # cross, bidir
    _case(96, 96, 2, 2, 128, True, 32, tiles=(64, 64, None)),   # non-pow2, win
    # several heads a block, blocks above 128, bq != bk
    _case(512, 512, 8, 8, 64, True, 0, tiles=(256, 128, 4),
          id="512-512-8-8-64-True-0-q256k128h4"),
    # the shape rule: S a multiple of 128 but not of the 512 block
    _case(640, 640, 4, 4, 64, True, 0, id="640-640-4-4-64-True-0-auto"),
    # GQA, two kv heads of four query heads a block, bk > bq
    _case(256, 256, 8, 2, 32, True, 0, tiles=(128, 256, 2),
          id="256-256-8-2-32-True-0-q128k256h2"),
    # a window that cuts blocks, q and kv blocks of their own sizes
    _case(512, 512, 4, 2, 32, True, 100, tiles=(128, 256, None),
          id="512-512-4-2-32-True-100-q128k256"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(S, T, Hq, Hkv, D, causal, window, tiles, dtype):
    q = jnp.asarray(RNG.normal(0, 1, (2, S, Hq, D)), dtype)
    k = jnp.asarray(RNG.normal(0, 1, (2, T, Hkv, D)), dtype)
    v = jnp.asarray(RNG.normal(0, 1, (2, T, Hkv, D)), dtype)
    bq, bk, bh = tiles or (None, None, None)
    got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 block_q=bq, block_k=bk, block_h=bh,
                                 interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_flash_attention_kv_len():
    """Keys from ``kv_len`` on are masked: the same as attending to the
    first ``kv_len`` keys alone."""
    q = jnp.asarray(RNG.normal(0, 1, (2, 128, 4, 32)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (2, 384, 2, 32)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (2, 384, 2, 32)), jnp.float32)
    got = flash_attention_pallas(q, k, v, causal=False, kv_len=200,
                                 block_k=128, interpret=True)
    want = ref.flash_attention_ref(q, k[:, :200], v[:, :200], causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **_tol(jnp.float32))


@pytest.mark.parametrize("S,T,bq,bk,causal,window,kv_len", [
    (512, 512, 128, 128, True, 0, None),
    (512, 512, 256, 128, True, 0, None),
    (512, 512, 128, 256, True, 100, None),
    (384, 384, 128, 128, True, 129, None),
    (256, 512, 128, 128, False, 0, 300),
    (512, 512, 512, 512, True, 0, None),
])
def test_flash_block_spans_are_the_reachable_blocks(S, T, bq, bk, causal,
                                                    window, kv_len):
    """The kv blocks each q block visits (forward, dq) and the q blocks
    each kv block visits (dk/dv) are exactly the pairs that hold an
    unmasked position, and ``_interior`` holds just where none is masked:
    the three kernels skip the same pairs."""
    from repro.kernels import flash_attention as fa
    q_pos = np.arange(S)[:, None]
    k_pos = np.arange(T)[None, :]
    keep = np.ones((S, T), bool)
    if causal:
        keep &= k_pos <= q_pos
    if window:
        keep &= k_pos > q_pos - window
    if kv_len is not None:
        keep &= k_pos < kv_len
    nq, nk = S // bq, T // bk
    for iq in range(nq):
        for ik in range(nk):
            blk = keep[iq * bq:(iq + 1) * bq, ik * bk:(ik + 1) * bk]
            lo, hi = fa._kv_span(iq, bq, bk, nk, causal, window, kv_len)
            assert (int(lo) <= ik <= int(hi)) == blk.any(), (iq, ik)
            qlo, qhi = fa._q_span(ik, bq, bk, nq, causal, window)
            beyond = kv_len is not None and ik * bk >= kv_len
            assert (int(qlo) <= iq <= int(qhi) and not beyond) == blk.any()
            inner = fa._interior(iq * bq, ik * bk, bq, bk, causal, window,
                                 kv_len)
            if blk.any():
                assert bool(inner) == blk.all(), (iq, ik)


@pytest.mark.parametrize("E,C,D,F", [(2, 64, 128, 96), (8, 128, 64, 256),
                                     (3, 96, 160, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gmm(E, C, D, F, dtype):
    buf = jnp.asarray(RNG.normal(0, 1, (E, C, D)), dtype)
    w = jnp.asarray(RNG.normal(0, 0.5, (E, D, F)), dtype)
    got = moe_gmm_pallas(buf, w, interpret=True)
    want = ref.moe_gmm_ref(buf, w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2 if dtype == jnp.bfloat16 else 1e-3,
                               atol=5e-1 if dtype == jnp.bfloat16 else 1e-3)


@pytest.mark.parametrize("B,S,H,P,G,N,chunk", [
    (1, 64, 2, 32, 1, 16, 16),
    (2, 128, 4, 32, 2, 16, 32),
    (1, 96, 4, 64, 1, 32, 32),          # 96 = 3 chunks of 32
    (2, 256, 8, 64, 2, 64, 64),
])
def test_ssd_scan(B, S, H, P, G, N, chunk):
    xh = jnp.asarray(RNG.normal(0, 1, (B, S, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(1e-3, 0.1, (B, S, H)), jnp.float32)
    a = jnp.asarray(-RNG.uniform(0.5, 2.0, (H,)), jnp.float32)
    B_ = jnp.asarray(RNG.normal(0, 0.5, (B, S, G, N)), jnp.float32)
    C_ = jnp.asarray(RNG.normal(0, 0.5, (B, S, G, N)), jnp.float32)
    got, _ = ssd_scan_pallas(xh, dt, a, B_, C_, chunk=chunk, interpret=True)
    want, _ = ref.ssd_scan_ref(xh, dt, a, B_, C_)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_ssd_chunked_xla_matches_sequential():
    """The model's XLA path (ssd_chunked) against the sequential oracle,
    including the returned final state."""
    from repro.models.mamba2 import ssd_chunked
    B, S, H, P, G, N = 2, 128, 4, 32, 2, 16
    xh = jnp.asarray(RNG.normal(0, 1, (B, S, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(1e-3, 0.1, (B, S, H)), jnp.float32)
    a = jnp.asarray(-RNG.uniform(0.5, 2.0, (H,)), jnp.float32)
    B_ = jnp.asarray(RNG.normal(0, 0.5, (B, S, G, N)), jnp.float32)
    C_ = jnp.asarray(RNG.normal(0, 0.5, (B, S, G, N)), jnp.float32)
    got, hf = ssd_chunked(xh, dt, a, B_, C_, chunk=32)
    want, hf_ref = ref.ssd_scan_ref(xh, dt, a, B_, C_)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(
        np.asarray(hf).reshape(hf_ref.shape), np.asarray(hf_ref),
        rtol=2e-3, atol=2e-3)


def _ssd_chunk256_inputs(seed):
    """``ssd_chunked`` inputs at chunk 256, with ``a_log`` and ``dt_bias``
    drawn by the chip benchmark's Mamba-2 reference's own rules, so that
    |Σ dt·a| within a chunk passes 256."""
    import sys
    from pathlib import Path
    chip = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
    if str(chip) not in sys.path:
        sys.path.append(str(chip))
    import reference
    inits = reference.load({"reference": "ssm"}).INITS
    B, S, H, P, G, N = 1, 512, 8, 16, 2, 16
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    a = -jnp.exp(inits["ssm_a"](k[0], (H,)).astype(jnp.float32))
    dt_bias = inits["dt_bias"](k[1], (H,)).astype(jnp.float32)
    dt = jax.nn.softplus(0.5 * jax.random.normal(k[2], (B, S, H)) + dt_bias)
    xh = jax.random.normal(k[3], (B, S, H, P))
    B_ = 0.5 * jax.random.normal(k[4], (B, S, G, N))
    C_ = 0.5 * jax.random.normal(k[5], (B, S, G, N))
    most = float(jnp.max(jnp.abs(jnp.sum(
        (dt * a).reshape(B, S // 256, 256, H), axis=2))))
    assert most > 256, most
    return xh, dt, a, B_, C_


@pytest.mark.parametrize("seed", [2, 7])
def test_ssd_chunked_prefix_sum_matmul_matches_cumsum_at_chunk_256(
        seed, monkeypatch):
    """At chunk 256 with |Σ dt·a| past 256, the prefix sum taken as a
    triangular matmul gives the sequential oracle's output and final
    state, and the same gradients in dt and a as ``jnp.cumsum`` (the
    formulation it replaced) to 1e-4 of their norm."""
    from repro.models import mamba2
    xh, dt, a, B_, C_ = _ssd_chunk256_inputs(seed)
    got, hf = mamba2.ssd_chunked(xh, dt, a, B_, C_, chunk=256)
    want, hf_ref = ref.ssd_scan_ref(xh, dt, a, B_, C_)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(
        np.asarray(hf).reshape(hf_ref.shape), np.asarray(hf_ref),
        rtol=2e-3, atol=2e-3)

    wy = jax.random.normal(jax.random.PRNGKey(1), got.shape)
    wh = jax.random.normal(jax.random.PRNGKey(2), hf.shape)

    def loss(dt, a):
        y, h = mamba2.ssd_chunked(xh, dt, a, B_, C_, chunk=256)
        return jnp.sum(y * wy) + jnp.sum(h * wh)

    grads = jax.grad(loss, argnums=(0, 1))(dt, a)
    monkeypatch.setattr(mamba2, "_prefix_sum",
                        lambda x: jnp.cumsum(x, axis=-1))
    old = jax.grad(loss, argnums=(0, 1))(dt, a)
    for g, o in zip(grads, old):
        rel = float(jnp.linalg.norm(g - o) / jnp.linalg.norm(o))
        assert rel < 1e-4, rel


def _primitive_names(jaxpr):
    """Every primitive in a jaxpr, through the sub-jaxprs of scans,
    checkpoints and calls."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _primitive_names(sub)


def test_ssd_chunked_training_gradient_takes_no_cumsum(monkeypatch):
    """The gradient of a remat'd ``ssd_chunked`` (as the training step
    takes it) holds no ``cumsum``, which XLA lowers to an O(Q²)
    reduce-window forward and backward; with ``jnp.cumsum`` put back as
    the prefix sum, the same walk finds it."""
    from repro.models import mamba2
    args = _ssd_chunk256_inputs(2)

    def primitives():
        # a fresh function each time: jax.checkpoint caches its trace
        def loss(xh, dt, a, B_, C_):
            y, h = mamba2.ssd_chunked(xh, dt, a, B_, C_, chunk=256)
            return jnp.sum(y) + jnp.sum(h)
        step = jax.grad(jax.checkpoint(
            loss, policy=jax.checkpoint_policies.nothing_saveable),
            argnums=(0, 1, 2, 3, 4))
        return set(_primitive_names(jax.make_jaxpr(step)(*args).jaxpr))

    names = primitives()
    assert "scan" in names and "dot_general" in names
    assert "cumsum" not in names
    monkeypatch.setattr(mamba2, "_prefix_sum",
                        lambda x: jnp.cumsum(x, axis=-1))
    assert "cumsum" in primitives()


def test_attention_q_chunking_equivalence():
    """The XLA reference attention must be invariant to query chunking."""
    from repro.models.layers import attention
    q = jnp.asarray(RNG.normal(0, 1, (2, 128, 4, 32)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (2, 128, 2, 32)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (2, 128, 2, 32)), jnp.float32)
    full = attention(q, k, v, causal=True, window=48, q_chunk=None)
    chunked = attention(q, k, v, causal=True, window=48, q_chunk=32)
    np.testing.assert_allclose(np.asarray(full), np.asarray(chunked),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("S,T,Hq,Hkv,D,causal,window,dtype,tiles", [
    _case(128, 128, 4, 2, 32, True, 0, dtype=jnp.float32),
    _case(128, 128, 4, 4, 64, True, 48, dtype=jnp.float32),
    _case(64, 192, 4, 1, 32, False, 0, dtype=jnp.float32),
    # the new tiling: several heads, bq != bk, causal skips in dk/dv
    _case(512, 512, 4, 4, 64, True, 0, dtype=jnp.float32,
          tiles=(256, 128, 2), id="512-512-4-4-64-True-0-q256k128h2"),
    _case(256, 256, 8, 2, 32, True, 80, dtype=jnp.float32,
          tiles=(128, 256, None), id="256-256-8-2-32-True-80-q128k256"),
    # bf16 in, as the models feed it
    _case(128, 128, 4, 2, 32, True, 0, dtype=jnp.bfloat16,
          id="128-128-4-2-32-True-0-bf16"),
    _case(512, 512, 8, 8, 64, True, 0, dtype=jnp.bfloat16,
          id="512-512-8-8-64-True-0-bf16"),
    _case(256, 256, 8, 2, 32, True, 80, dtype=jnp.bfloat16,
          tiles=(128, 256, None), id="256-256-8-2-32-True-80-q128k256-bf16"),
])
def test_flash_attention_backward(S, T, Hq, Hkv, D, causal, window, dtype,
                                  tiles):
    """Pallas flash-v2 backward (dq/dk/dv) vs jax.grad of the oracle."""
    q = jnp.asarray(RNG.normal(0, 1, (2, S, Hq, D)), dtype)
    k = jnp.asarray(RNG.normal(0, 1, (2, T, Hkv, D)), dtype)
    v = jnp.asarray(RNG.normal(0, 1, (2, T, Hkv, D)), dtype)
    do = jnp.asarray(RNG.normal(0, 1, (2, S, Hq, D)), dtype)
    if tiles is None:
        _, pullback = jax.vjp(lambda q, k, v: ops.flash_attention(
            q, k, v, causal=causal, window=window), q, k, v)
    else:
        o, lse = flash_attention_fwd(q, k, v, causal=causal, window=window,
                                     block_q=tiles[0], block_k=tiles[1],
                                     block_h=tiles[2], interpret=True)
        pullback = lambda do: flash_attention_bwd(  # noqa: E731
            q, k, v, o, lse, do, causal=causal, window=window,
            block_q=tiles[0], block_k=tiles[1], block_h=tiles[2],
            interpret=True)
    _, ref_pullback = jax.vjp(lambda q, k, v: ref.flash_attention_ref(
        q, k, v, causal=causal, window=window), q, k, v)
    tol = dict(rtol=2e-3, atol=2e-3) if dtype == jnp.float32 \
        else dict(rtol=5e-2, atol=5e-2)
    for a, b in zip(pullback(do), ref_pullback(do)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **tol)


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 130)])
def test_flash_attention_backward_skips_unreachable_blocks(causal, window):
    """NaN in the queries of the first q block reaches only the kv blocks
    that block attends to: a dk/dv kernel that fetched and computed (even
    masked) the pairs it cannot reach, as the one-head 128 × 128 kernel
    did, carries 0 · NaN into every dk."""
    S, bq = 512, 128
    q = np.asarray(RNG.normal(0, 1, (1, S, 2, 32)), np.float32)
    q[:, :bq] = np.nan
    q = jnp.asarray(q)
    k, v, do = (jnp.asarray(RNG.normal(0, 1, (1, S, 2, 32)), jnp.float32)
                for _ in range(3))
    kw = dict(causal=causal, window=window, block_q=bq, block_k=bq,
              interpret=True)
    o, lse = flash_attention_fwd(q, k, v, **kw)
    dq, dk, dv = flash_attention_bwd(q, k, v, o, lse, do, **kw)
    assert np.isnan(np.asarray(dk[:, :bq])).any()     # reached: poisoned
    for g in (o, dq, dk, dv):
        assert np.isfinite(np.asarray(g[:, bq:])).all()
