"""Chip smoke: the CWS-scheduled training path, end to end, on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four-chip mesh against one chip

One process drives the chip. With no option it checks, in order:

1. device   -- JAX's first device is a TPU; there is no CPU path.
2. kernels  -- flash attention (forward and backward) and the SSD scan,
               compiled natively (``interpret=False``), against their
               ``kernels/ref.py`` oracles at real widths; then one full-width
               qwen1.5-0.5b loss through the Pallas kernels against the same
               loss through XLA.
3. training -- ``run_training`` on full-width qwen1.5-0.5b: chunk tasks go
               CWSI -> CWS -> LocalExecutor -> jitted step; each runs once,
               and the losses are finite and fall.
4. backends -- the initial loss of the same parameters on the chip and on
               the host CPU agree.

``--chips 4`` runs the training phase on a mesh over every device of the
host, then the same steps on one device, and compares them step by step.

Any failure exits non-zero and prints no ok line. A pass ends with one JSON
line: ``{"ok": true, "device": {"platform", "kind", "count"}}``. The other
lines are informational smoke output, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import functools
import json
import math
import os
import statistics
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# the backends phase needs the host CPU beside the TPU
_platforms = os.environ.get("JAX_PLATFORMS", "")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.data import DataConfig, TokenPipeline  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.flash_attention import (  # noqa: E402
    flash_attention_bwd,
    flash_attention_fwd,
)
from repro.kernels.ssd_scan import ssd_scan_pallas  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.launch.train import enable_compile_cache, run_training  # noqa: E402
from repro.models import build_model  # noqa: E402

ARCH = "qwen1.5-0.5b"
# (batch, seq, q heads, kv heads, head dim)
FLASH_WIDTHS = {
    "qwen1.5-0.5b": (2, 1024, 16, 16, 64),
    "qwen2-7b": (1, 1024, 28, 4, 128),          # GQA 7:1
}
# mamba2-370m: d_inner 2048 = 32 heads x 64, state 128, chunk 256
SSD_WIDTHS = dict(B=2, S=1024, H=32, P=64, G=1, N=128, chunk=256)
# tests/test_kernels.py tolerances
TOL_BF16 = dict(rtol=2e-2, atol=2e-2)
TOL_F32 = dict(rtol=2e-3, atol=2e-3)
# the training phase: 3 chunk tasks x 3 steps, global batch 8 x 1024
TRAIN = dict(steps=9, chunk=3, batch=8, seq=1024, microbatch=4, lr=3e-3)
LOSS_AGREE_REL = 2e-2


class Smoke:
    """Records checks; a phase that raises counts as a failed check."""

    def __init__(self) -> None:
        self.failed: list = []

    def check(self, phase: str, what: str, ok: bool, detail: str = "") -> None:
        print(f"[{phase}] {'PASS' if ok else 'FAIL'} {what}"
              f"{': ' + detail if detail else ''}", flush=True)
        if not ok:
            self.failed.append(f"{phase}: {what}")

    def phase(self, name: str, fn, *args) -> None:
        t = time.perf_counter()
        try:
            fn(self, *args)
        except Exception as e:  # noqa: BLE001 — report, fail, go on
            traceback.print_exc()
            self.check(name, f"phase raised {type(e).__name__}", False,
                       str(e)[:500])
        print(f"[{name}] phase seconds {time.perf_counter() - t:.1f}",
              flush=True)

    def close(self, n_devices: int) -> int:
        if self.failed:
            print("chip_smoke FAILED: " + "; ".join(self.failed),
                  file=sys.stderr, flush=True)
            return 1
        d = jax.devices()[0]
        print(json.dumps({"ok": True, "device": {
            "platform": d.platform, "kind": d.device_kind,
            "count": n_devices}}), flush=True)
        return 0


def _allclose(got, want, rtol: float, atol: float):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = np.abs(got - want)
    ok = bool(np.isfinite(got).all()) and bool(
        (err <= atol + rtol * np.abs(want)).all())
    return ok, f"max |err| {float(err.max()):.3e} (rtol {rtol}, atol {atol})"


def _native(fn, *args):
    """Compile ``fn`` for the chip; the program must hold a Mosaic kernel
    (``tpu_custom_call``), i.e. no interpret-mode fallback."""
    compiled = jax.jit(fn).lower(*args).compile()
    if "tpu_custom_call" not in compiled.as_text():
        raise RuntimeError("the compiled program holds no native kernel")
    return compiled(*args)


def _highest(fn, *args):
    """An oracle at full f32 matmul precision."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(fn)(*args)


def _peak_bytes(d) -> str:
    """``peak_bytes_in_use``, beside the other peaks and the limit the
    backend reports (TPU keeps program temporaries apart, as reserved)."""
    stats = d.memory_stats()
    if not stats:
        return "not reported"
    return ", ".join(f"{k} {v:,}" for k, v in sorted(stats.items())
                     if k.startswith("peak_") or k == "bytes_limit")


# ---------------------------------------------------------------------------
def phase_kernels(sm: Smoke, seed: int) -> None:
    rng = np.random.default_rng(seed)
    normal = lambda *s: jnp.asarray(rng.normal(0, 1, s), jnp.float32)  # noqa: E731
    fwd = functools.partial(flash_attention_fwd, causal=True, interpret=False)
    bwd = functools.partial(flash_attention_bwd, causal=True, interpret=False)
    att_ref = functools.partial(ref.flash_attention_ref, causal=True)

    def ref_vjp(q, k, v, do):
        o, pullback = jax.vjp(att_ref, q, k, v)
        return (o, *pullback(do))

    for arch, (B, S, Hq, Hkv, D) in FLASH_WIDTHS.items():
        q, k, v = normal(B, S, Hq, D), normal(B, S, Hkv, D), normal(B, S, Hkv, D)
        bf = [x.astype(jnp.bfloat16) for x in (q, k, v)]
        o_bf, _ = _native(fwd, *bf)
        sm.check("kernels", f"flash fwd bf16 {arch} {(B, S, Hq, Hkv, D)}",
                 *_allclose(o_bf, _highest(att_ref, *bf), **TOL_BF16))
        o, lse = _native(fwd, q, k, v)
        do = normal(B, S, Hq, D)
        grads = _native(bwd, q, k, v, o, lse, do)
        want_o, *want_g = _highest(ref_vjp, q, k, v, do)
        sm.check("kernels", f"flash fwd f32 {arch}",
                 *_allclose(o, want_o, **TOL_F32))
        for name, g, w in zip(("dq", "dk", "dv"), grads, want_g):
            sm.check("kernels", f"flash bwd f32 {arch} {name}",
                     *_allclose(g, w, **TOL_F32))

    w = SSD_WIDTHS
    B, S, H, P, G, N = (w[x] for x in "BSHPGN")
    xh = normal(B, S, H, P)
    dt = jnp.asarray(rng.uniform(1e-3, 0.1, (B, S, H)), jnp.float32)
    a = jnp.asarray(-rng.uniform(0.5, 2.0, (H,)), jnp.float32)
    B_ = 0.5 * normal(B, S, G, N)
    C_ = 0.5 * normal(B, S, G, N)
    y, _ = _native(functools.partial(ssd_scan_pallas, chunk=w["chunk"],
                                     interpret=False), xh, dt, a, B_, C_)
    want, _ = _highest(ref.ssd_scan_ref, xh, dt, a, B_, C_)
    sm.check("kernels", f"ssd_scan f32 mamba2-370m {tuple(w.values())}",
             *_allclose(y, want, **TOL_F32))

    # one full-width loss: Pallas kernels in the model against XLA
    cfg = get_config(ARCH)
    params = jax.jit(build_model(cfg).init)(jax.random.PRNGKey(seed))
    batch = jax.device_put(TokenPipeline(DataConfig(
        vocab=cfg.vocab, seq_len=1024, global_batch=2, seed=seed)).batch(0))
    # both paths forced: the train step would pick the kernel on a TPU
    losses = {}
    for use_pallas in (True, False):
        loss_fn = build_model(cfg, use_pallas=use_pallas).loss
        compiled = jax.jit(loss_fn).lower(params, batch).compile()
        sm.check("kernels", ("" if use_pallas else "no ") + "tpu_custom_call "
                 f"in the use_pallas={use_pallas} program",
                 ("tpu_custom_call" in compiled.as_text()) == use_pallas)
        losses[use_pallas] = float(compiled(params, batch)[0])
    rel = abs(losses[True] - losses[False]) / abs(losses[False])
    sm.check("kernels", f"{ARCH} full-width loss, Pallas vs XLA",
             math.isfinite(rel) and rel <= 1e-2,
             f"{losses[True]:.6f} vs {losses[False]:.6f} (rel {rel:.2e})")


# ---------------------------------------------------------------------------
def _train(sm: Smoke, tag: str, devices, seed: int):
    """``run_training`` on ``devices``; checks the run and returns it."""
    cfg = get_config(ARCH)
    mesh = make_host_mesh(devices)
    print(f"[{tag}] {ARCH}: layers {cfg.n_layers}, d_model {cfg.d_model}, "
          f"heads {cfg.n_heads}/{cfg.n_kv_heads}, vocab {cfg.vocab:,}; "
          f"mesh {dict(mesh.shape)}; {TRAIN}", flush=True)
    out = run_training(cfg, seed=seed, mesh=mesh,
                       log=lambda s: print(f"[{tag}] {s}", flush=True),
                       **TRAIN)
    want = "pallas" if mesh.devices.size == 1 else "xla"
    sm.check(tag, f"the step runs {want} attention", out["attention"] == want,
             out["attention"])
    chunks = [t for t in out["dag"].tasks.values() if t.name == "train_chunk"]
    n_chunks = -(-TRAIN["steps"] // TRAIN["chunk"])
    once = (len(chunks) == n_chunks == len(out["chunk_runs"])
            and all(t.state.value == "SUCCEEDED" and t.attempt == 0
                    for t in chunks)
            and all(n == 1 for n in out["chunk_runs"]))
    sm.check(tag, "every chunk task SUCCEEDED once through LocalRuntime",
             once, f"states {[t.state.value for t in chunks]}, "
                   f"attempts {[t.attempt for t in chunks]}, "
                   f"body runs {out['chunk_runs']}")
    losses = out["losses"]
    sm.check(tag, "losses finite", all(map(math.isfinite, losses)),
             f"{losses}")
    ln_v = math.log(cfg.vocab)
    sm.check(tag, "first loss within 1.0 of ln(vocab)",
             bool(losses) and abs(losses[0] - ln_v) <= 1.0,
             f"{losses[0] if losses else None} vs {ln_v:.4f}")
    c = TRAIN["chunk"]
    first, last = losses[:c], losses[-c:]
    sm.check(tag, "last chunk's mean loss below the first's",
             bool(losses) and statistics.fmean(last) < statistics.fmean(first),
             f"{statistics.fmean(first):.4f} -> {statistics.fmean(last):.4f}")
    print(f"[{tag}] compile seconds {out['compile_seconds']:.3f}", flush=True)
    print(f"[{tag}] median warm step seconds "
          f"{statistics.median(out['step_seconds'][1:]):.4f} "
          f"(block_until_ready; {len(out['step_seconds']) - 1} steps)",
          flush=True)
    return out


def phase_training(sm: Smoke, seed: int) -> None:
    _train(sm, "training", jax.devices()[:1], seed)
    print(f"[training] device memory: {_peak_bytes(jax.devices()[0])}",
          flush=True)


def phase_backends(sm: Smoke, seed: int) -> None:
    """Initial loss of the training run's parameters (same seed) on a short
    batch, on the chip and on the host CPU."""
    cfg = get_config(ARCH)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    batch = TokenPipeline(DataConfig(vocab=cfg.vocab, seq_len=256,
                                     global_batch=2, seed=seed)).batch(0)
    chip = float(jax.jit(model.loss)(params, batch)[0])
    cpu = jax.devices("cpu")[0]
    t = time.perf_counter()
    host = float(jax.jit(model.loss)(jax.device_put(params, cpu),
                                     jax.device_put(batch, cpu))[0])
    rel = abs(chip - host) / abs(host)
    sm.check("backends", "initial loss, chip vs CPU (2 x 256 tokens)",
             math.isfinite(rel) and rel <= LOSS_AGREE_REL,
             f"{chip:.6f} vs {host:.6f} (rel {rel:.2e}, "
             f"CPU side {time.perf_counter() - t:.1f} s)")


def phase_mesh(sm: Smoke, seed: int) -> None:
    """Every device of the host on one mesh against one device."""
    devices = jax.devices()
    sm.check("mesh", "at least 4 devices", len(devices) >= 4,
             f"{len(devices)}")
    out = _train(sm, "mesh", None, seed)
    leaves = jax.tree.leaves(out["state"]["params"])
    on_mesh = all(x.sharding.device_set == set(devices) for x in leaves)
    split = sum(not x.sharding.is_fully_replicated for x in leaves)
    sm.check("mesh", "every parameter leaf on every device of the mesh",
             on_mesh, f"{len(leaves)} leaves, {split} split across devices")
    for d in devices:
        print(f"[mesh] device {d.id} memory: {_peak_bytes(d)}",
              flush=True)
    many = out["losses"]
    del out, leaves
    one = _train(sm, "one", devices[:1], seed)["losses"]
    rel = max((abs(a - b) / abs(b) for a, b in zip(many, one)),
              default=math.inf)
    sm.check("mesh", f"losses per step, {len(devices)} devices vs 1",
             len(many) == len(one) and rel <= LOSS_AGREE_REL,
             f"{many} vs {one} (max rel {rel:.2e})")


# ---------------------------------------------------------------------------
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the multi-chip mesh check only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cache = enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU; JAX found platform {dev.platform!r}",
              file=sys.stderr, flush=True)
        return 1
    n = len(jax.devices())
    print(f"[device] platform {dev.platform}, kind {dev.device_kind}, "
          f"count {n}; compile cache {cache}", flush=True)

    sm = Smoke()
    if args.chips == 4:
        sm.phase("mesh", phase_mesh, args.seed)
        return sm.close(n)
    sm.phase("kernels", phase_kernels, args.seed)
    sm.phase("training", phase_training, args.seed)
    sm.phase("backends", phase_backends, args.seed)
    return sm.close(n)


if __name__ == "__main__":
    sys.exit(main())
