"""Training driver: CWS-orchestrated, checkpointed, resumable.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --smoke \
        --steps 60 --chunk 10 --ckpt-dir /tmp/ckpt
    # kill it any time; rerun the same command → resumes from the last
    # committed checkpoint with bit-identical data order.

``--preset 100m`` trains a ~100M-param dense model (full-size run for real
hardware; on CPU use --smoke). The training job is compiled into a workflow
DAG and scheduled through the CWSI (chunks → eval → checkpoint tasks), so
restarts, provenance, and runtime prediction all come from the CWS.

The state lives in the step's own shardings on the host mesh, so the same
code trains on one chip or on every chip of a host.
"""
from __future__ import annotations

import argparse
import os
import time
from pathlib import Path
from typing import Any, Callable, Dict, Optional

import jax
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh

from ..checkpoint import latest_checkpoint, restore_checkpoint, save_checkpoint
from ..configs import get_config
from ..configs.base import ModelConfig, ShapeConfig, TrainConfig
from ..data import DataConfig, TokenPipeline
from ..models import build_model
from ..runtime.orchestrator import (
    LocalRuntime,
    SharedState,
    TrainJobSpec,
    build_training_workflow,
)
from ..runtime.train import attention_path, init_state, make_train_step
from .mesh import make_host_mesh

REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is read by JAX itself and
    nothing here overrides it. Otherwise the cache sits at a fixed path in
    the checkout: the path is part of the cache key, so it must not move.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def preset_100m(cfg):
    """~100M-param dense config of the same family (full driver target)."""
    return cfg.scaled(n_layers=12, d_model=768, n_heads=12, n_kv_heads=12,
                      d_ff=3072, vocab=32768)


def run_training(cfg: ModelConfig, *, steps: int, chunk: int, batch: int,
                 seq: int, microbatch: int, lr: float, seed: int = 0,
                 mesh: Optional[Mesh] = None, ckpt_dir: str = "",
                 ckpt_every: int = 20, strategy: str = "rank_min_rr",
                 log: Callable[[str], None] = print) -> Dict[str, Any]:
    """Train ``cfg`` as a chunked workflow run by CWS → ``LocalExecutor``.

    Returns the per-step ``losses`` and ``step_seconds`` (each step timed
    to ``block_until_ready``), ``compile_seconds``, the ``compiled`` step,
    the ``attention`` it runs (``"pallas"`` or ``"xla"``, as
    ``attention_path`` picks it and the first log line says),
    ``chunk_runs`` (how often each chunk task's body ran, in chunk order),
    the finished ``dag`` and the final ``state``.

    Each step's host work is spanned for the profiler (``train.batch``,
    ``train.put``, ``train.step``, ``train.read``, and ``train.log`` once a
    chunk, each with its ``step``); with no profiler session a span
    records nothing.
    """
    if batch % microbatch:
        raise ValueError(f"batch {batch} is not a multiple of "
                         f"microbatch {microbatch}")
    mesh = mesh if mesh is not None else make_host_mesh()
    model = build_model(cfg)

    shape = ShapeConfig("driver", seq, batch)
    attention = attention_path(model, mesh, shape)
    log(f"[train] arch={cfg.name} params={model.n_params():,} "
        f"mesh={dict(mesh.shape)} attention={attention}")
    tcfg = TrainConfig(learning_rate=lr, warmup_steps=10,
                       microbatch_per_device=microbatch)
    step, state_sh, batch_sh, state_specs = make_train_step(
        model, tcfg, shape, mesh, total_steps=steps)
    jstep = jax.jit(step, in_shardings=(state_sh, batch_sh),
                    out_shardings=(state_sh, None), donate_argnums=(0,))
    pipe = TokenPipeline(DataConfig(vocab=cfg.vocab, seq_len=seq,
                                    global_batch=batch, seed=seed))

    ck = latest_checkpoint(ckpt_dir) if ckpt_dir else None
    if ck:
        state, manifest = restore_checkpoint(ck, state_specs, state_sh)
        start_step = int(manifest["step"])
        log(f"[train] resumed from {ck} at step {start_step}")
    else:
        # built in place: each device makes only its own shards
        state = jax.jit(lambda key: init_state(model, tcfg, key,
                                               total_steps=steps),
                        out_shardings=state_sh)(jax.random.PRNGKey(seed))
        start_step = 0

    t0 = time.perf_counter()
    compiled = jstep.lower(state, jax.device_put(pipe.batch(start_step),
                                                 batch_sh)).compile()
    compile_s = time.perf_counter() - t0

    shared = SharedState(state)
    losses, step_s = [], []
    chunk_runs: Dict[int, int] = {}

    def run_chunk(sh: SharedState, start: int, stop: int):
        chunk_runs[start] = chunk_runs.get(start, 0) + 1
        for s in range(start, stop):
            with TraceAnnotation("train.batch", step=s):
                hb = pipe.batch(s)
            with TraceAnnotation("train.put", step=s):
                b = jax.device_put(hb, batch_sh)
            with TraceAnnotation("train.step", step=s):
                t = time.perf_counter()
                sh.state, m = compiled(sh.state, b)
                jax.block_until_ready((sh.state, m))
                step_s.append(time.perf_counter() - t)
            with TraceAnnotation("train.read", step=s):
                losses.append(float(m["loss"]))
        with TraceAnnotation("train.log", step=stop - 1):
            log(f"[train] step {stop:5d} loss {losses[-1]:.4f} "
                f"lr {float(m['lr']):.2e} gnorm {float(m['grad_norm']):.2f}")
        return {"step": stop, "loss": losses[-1]}

    def run_ckpt(sh: SharedState, step_no: int):
        save_checkpoint(ckpt_dir, step_no, sh.state, {"arch": cfg.name})
        log(f"[train] checkpoint @ {step_no}")

    spec = TrainJobSpec(job_id=f"train-{cfg.name}",
                        n_steps=steps - start_step, chunk=chunk,
                        ckpt_every=ckpt_every if ckpt_dir else 0)
    dag = build_training_workflow(
        spec, lambda sh, a, b: run_chunk(sh, a + start_step, b + start_step),
        shared,
        run_ckpt=(lambda sh, s: run_ckpt(sh, s + start_step))
        if ckpt_dir else None)
    rt = LocalRuntime(n_nodes=1, strategy=strategy)
    try:
        rt.run(dag, timeout_s=6000)
    finally:
        rt.shutdown()
    return {"losses": losses, "step_seconds": step_s,
            "compile_seconds": compile_s, "compiled": compiled,
            "attention": attention,
            "chunk_runs": [chunk_runs.get(a, 0) for a in
                           range(start_step, steps, chunk)],
            "dag": dag, "state": shared.state}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--preset", choices=["none", "100m"], default="none")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--chunk", type=int, default=10)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--microbatch", type=int, default=None,
                    help="rows per gradient-accumulation micro-step "
                         "(default: the whole batch in one)")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--strategy", default="rank_min_rr")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    if args.preset == "100m":
        cfg = preset_100m(cfg)
    out = run_training(cfg, steps=args.steps, chunk=args.chunk,
                       batch=args.batch, seq=args.seq,
                       microbatch=args.microbatch or args.batch, lr=args.lr,
                       seed=args.seed, ckpt_dir=args.ckpt_dir,
                       ckpt_every=args.ckpt_every, strategy=args.strategy)
    losses = out["losses"]
    if losses:
        print(f"[train] done: first-step loss {losses[0]:.3f} → "
              f"last-step loss {losses[-1]:.3f}")


if __name__ == "__main__":
    main()
