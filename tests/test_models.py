"""Per-architecture smoke tests (reduced configs) + decode consistency.

Every assigned arch: one forward/train step on CPU, asserting output shapes
and finite values. For each *family*, the strongest correctness check we
have: teacher-forced forward logits must match step-by-step decode logits
(prefill-free, decode-from-empty-cache) — this exercises KV caches, ring
buffers, SSM recurrence vs chunked scan, and cross-attention caches.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SMOKE_ARCHS
from repro.models import build_model

RNG = np.random.default_rng(0)
B, S = 2, 32


def _batch(cfg):
    b = {"tokens": jnp.asarray(RNG.integers(2, cfg.vocab, (B, S)), jnp.int32)}
    b["labels"] = jnp.asarray(RNG.integers(2, cfg.vocab, (B, S)), jnp.int32)
    if cfg.family == "vlm":
        b["patches"] = jnp.asarray(
            RNG.normal(0, 1, (B, cfg.vision.n_patches, cfg.vision.patch_dim)),
            jnp.bfloat16)
    if cfg.family == "audio":
        b["frames"] = jnp.asarray(
            RNG.normal(0, 0.1, (B, cfg.encdec.n_frames, cfg.d_model)),
            jnp.bfloat16)
    return b


@pytest.mark.parametrize("arch", sorted(SMOKE_ARCHS))
def test_smoke_forward_and_loss(arch):
    cfg = SMOKE_ARCHS[arch]
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    logits, aux = jax.jit(model.logits)(params, batch)
    assert logits.shape == (B, S, cfg.vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    loss, metrics = jax.jit(model.loss)(params, batch)
    assert np.isfinite(float(loss))
    assert float(loss) > 0


@pytest.mark.parametrize("arch", sorted(SMOKE_ARCHS))
def test_smoke_train_step_reduces_loss(arch):
    from repro.configs.base import ShapeConfig, TrainConfig
    from repro.launch.mesh import make_host_mesh
    from repro.runtime.train import init_state, make_train_step

    cfg = SMOKE_ARCHS[arch]
    model = build_model(cfg)
    mesh = make_host_mesh()
    shape = ShapeConfig("tiny", S, B)
    tcfg = TrainConfig(learning_rate=5e-3, warmup_steps=2,
                       microbatch_per_device=B)
    step, state_sh, batch_sh, _ = make_train_step(model, tcfg, shape, mesh)
    state = init_state(model, tcfg, jax.random.PRNGKey(1))
    batch = _batch(cfg)
    jstep = jax.jit(step)
    losses = []
    for i in range(8):
        state, m = jstep(state, batch)       # same batch → must memorise
        losses.append(float(m["loss"]))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses


@pytest.mark.parametrize("arch", sorted(SMOKE_ARCHS))
def test_cpu_mesh_train_step_keeps_xla_attention(arch):
    """On a CPU mesh the train step selects XLA attention and traces no
    Pallas call, so the CPU loss tests compute what they did before the
    kernel became the TPU default."""
    from repro.configs.base import ShapeConfig, TrainConfig
    from repro.launch.mesh import make_host_mesh
    from repro.runtime.train import attention_path, make_train_step

    cfg = SMOKE_ARCHS[arch]
    model = build_model(cfg)
    mesh = make_host_mesh()
    shape = ShapeConfig("tiny", S, B)
    assert attention_path(model, mesh, shape) == "xla"
    step, _, _, specs = make_train_step(
        model, TrainConfig(microbatch_per_device=B), shape, mesh)
    jaxpr = str(jax.make_jaxpr(step)(specs, model.input_specs(shape)))
    assert "pallas_call" not in jaxpr


FAMILY_REPRESENTATIVE = {
    "dense": "gemma3-12b",          # exercises local:global + ring buffers
    "moe": "mixtral-8x22b",         # SWA + experts
    "ssm": "mamba2-370m",
    "hybrid": "zamba2-2.7b",
    "vlm": "phi-3-vision-4.2b",
    "audio": "whisper-tiny",
}


@pytest.mark.parametrize("family,arch", sorted(FAMILY_REPRESENTATIVE.items()))
def test_decode_matches_forward(family, arch):
    """Greedy decode logits at each position == teacher-forced forward."""
    cfg = SMOKE_ARCHS[arch]
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    T = 16
    tokens = jnp.asarray(RNG.integers(2, cfg.vocab, (B, T)), jnp.int32)
    batch = {"tokens": tokens, "labels": tokens}
    if family == "vlm":
        batch["patches"] = jnp.zeros(
            (B, cfg.vision.n_patches, cfg.vision.patch_dim), jnp.bfloat16)
    if family == "audio":
        batch["frames"] = jnp.asarray(
            RNG.normal(0, 0.1, (B, cfg.encdec.n_frames, cfg.d_model)),
            jnp.bfloat16)
    fwd_logits, _ = model.logits(params, batch, remat="none")

    cache = model.init_cache(B, T)
    if family == "audio":
        from repro.models.encdec import prefill_cross_kv
        ck, cv = prefill_cross_kv(cfg, params, batch["frames"])
        cache = {**cache, "cross_k": ck, "cross_v": cv}
    step = jax.jit(model.decode_step)
    errs = []
    for t in range(T):
        logits, cache = step(params, cache, tokens[:, t], jnp.int32(t))
        if family == "vlm":
            continue   # decode path has no patch prefix; skip comparison
        a = np.asarray(logits, np.float32)
        b2 = np.asarray(fwd_logits[:, t, :], np.float32)
        errs.append(np.max(np.abs(a - b2)) /
                    max(np.max(np.abs(b2)), 1e-6))
    if errs:
        assert max(errs) < 0.08, f"max rel err {max(errs):.4f}"


def test_window_ring_buffer_decode_matches_forward():
    """Sliding-window arch (mixtral smoke, window=64): decode past the
    window must agree with windowed teacher forcing."""
    cfg = SMOKE_ARCHS["mixtral-8x22b"]
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(2))
    T = 96                                   # > window (64) → ring wraps
    tokens = jnp.asarray(RNG.integers(2, cfg.vocab, (1, T)), jnp.int32)
    fwd_logits, _ = model.logits(params, {"tokens": tokens,
                                          "labels": tokens}, remat="none")
    cache = model.init_cache(1, T)
    step = jax.jit(model.decode_step)
    errs = []
    for t in range(T):
        logits, cache = step(params, cache, tokens[:, t], jnp.int32(t))
        a = np.asarray(logits, np.float32)
        b2 = np.asarray(fwd_logits[:, t, :], np.float32)
        errs.append(np.max(np.abs(a - b2)) / max(np.max(np.abs(b2)), 1e-6))
    assert max(errs) < 0.08, f"max rel err {max(errs):.4f}"


def _ssm_reference():
    """The chip benchmark's plain Mamba-2 reference
    (``benchmarks/chip/references/ssm.py``) and its f32 matmul."""
    import sys
    from pathlib import Path
    chip = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
    if str(chip) not in sys.path:
        sys.path.append(str(chip))
    import reference
    return reference.load({"reference": "ssm"}), reference.MATMUL["f32"]


@pytest.mark.parametrize("seed", [2, 7])   # |Σ dt·a| 504 and 417
def test_mamba_block_keeps_the_ssd_decays_in_float32(seed, monkeypatch):
    """One Mamba-2 layer at smoke widths but a row of 1,024 tokens in
    chunks of 256, with ``a_log`` and ``dt_bias`` drawn as the reference
    draws them, so that |Σ dt·a| within a chunk passes 256, where a
    bfloat16 step is 2. What ``mamba_block`` hands ``ssd_chunked`` and what
    it gets back are held against the reference's quadratic SSD in f32.

    Tolerance 2e-2 on the relative norm of the difference: the matmul
    operands (x·dt, B, C, the decays) are bfloat16, 2^-9 of relative
    rounding each, and the readings are 3.2e-3 and 3.3e-3; with the cumsum
    in bfloat16 they are 0.17 and 0.20."""
    import dataclasses
    from repro.models import mamba2
    from repro.models.layers import rmsnorm
    ssm, mm = _ssm_reference()
    cfg = SMOKE_ARCHS["mamba2-370m"]
    cfg = dataclasses.replace(cfg, ssm=dataclasses.replace(cfg.ssm,
                                                           chunk=256))
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    p = jax.tree.map(lambda v: v[0],
                     build_model(cfg).init(keys[0])["layers"])
    p = dict(p, a_log=ssm.INITS["ssm_a"](keys[1], p["a_log"].shape),
             dt_bias=ssm.INITS["dt_bias"](keys[2], p["dt_bias"].shape))
    x = jax.random.normal(keys[3], (2, 1024, cfg.d_model), jnp.bfloat16)

    seen = {}
    base = mamba2.ssd_chunked

    def spy(*args, **kw):
        out = base(*args, **kw)
        seen["args"], seen["y"] = args, out[0]
        return out
    monkeypatch.setattr(mamba2, "ssd_chunked", spy)
    mamba2.mamba_block(rmsnorm(x, p["ln"], cfg.norm_eps), p, cfg)

    xh, dt, a, B_, C_ = (jnp.asarray(v, jnp.float32) for v in seen["args"])
    assert float(jnp.max(jnp.abs(jnp.cumsum(
        (dt * a).reshape(2, 4, 256, -1), axis=2)))) > 256
    want = jax.vmap(lambda *r: ssm._ssd(r[0], r[1], a, r[2], r[3], mm))(
        xh, dt, B_, C_)
    got = jnp.asarray(seen["y"], jnp.float32)
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert rel < 2e-2, rel
    assert seen["args"][1].dtype == seen["args"][2].dtype == jnp.float32


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_full_config_specs_no_allocation(arch):
    """The FULL configs are only ever touched via ShapeDtypeStructs."""
    cfg = ARCHS[arch]
    model = build_model(cfg)
    specs = model.param_specs()
    n = model.n_params()
    assert n > 1e8 or arch == "whisper-tiny", (arch, n)
    axes = model.param_axes()
    flat_s = jax.tree.flatten(
        specs, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))[0]
    treedef = jax.tree.flatten(
        specs, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))[1]
    flat_a = treedef.flatten_up_to(axes)
    for s, a in zip(flat_s, flat_a):
        assert len(s.shape) == len(a), (s.shape, a)


def test_param_count_analytic_matches_schema():
    """configs.base._param_count (roofline source) vs actual schema sizes."""
    for arch, cfg in ARCHS.items():
        model = build_model(cfg)
        analytic = cfg.param_count()
        actual = model.n_params()
        rel = abs(analytic - actual) / actual
        assert rel < 0.02, (arch, analytic, actual, rel)
