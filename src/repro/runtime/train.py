"""Train-step factory.

Composes: microbatched gradient accumulation (``lax.scan``), remat (inside
the model's layer scan), AdamW with fp32 master weights and bf16 moments,
ZeRO-1 optimizer-state sharding (an extra data-axis assignment per state
tensor), ZeRO-2 gradient accumulation in that sharding, and global-norm
clipping.

The returned artifacts are *specs + a pure function*, so a caller can
``jax.jit(...).lower(...).compile()`` them against ShapeDtypeStructs (a
described chip) or run them for real without code changes.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..configs.base import ShapeConfig, TrainConfig
from ..models.model import Model
from ..optim.adamw import AdamW, AdamWState, warmup_cosine
from .sharding import (
    Rules,
    input_axes,
    shardings_for_tree,
    spec_for,
    train_rules,
)

MOMENT_DTYPE = "bfloat16"   # AdamW's moments; the master weights stay f32


# ---------------------------------------------------------------------------
def n_microbatches(shape: ShapeConfig, mesh: Mesh, tcfg: TrainConfig) -> int:
    per_dev = shape.global_batch // mesh.shape.get("data", 1)
    return max(1, per_dev // max(tcfg.microbatch_per_device, 1))


# ---------------------------------------------------------------------------
def attention_path(model: Model, mesh: Mesh, shape: ShapeConfig) -> str:
    """The causal attention a train step of ``model`` on ``mesh`` runs:
    ``"pallas"`` (the flash kernel) or ``"xla"``.

    An explicit ``model.use_pallas`` decides. Otherwise the kernel runs
    where it is known to pay: a mesh of one TPU device and a sequence
    (patches included) that is a multiple of 128. A mesh of several
    devices keeps XLA until a ``shard_map`` wraps the kernel; so do the
    families that call ``attention`` directly (hybrid, audio) and those
    without it.
    """
    cfg = model.cfg
    if cfg.family not in ("dense", "moe", "vlm"):
        return "xla"
    if model.use_pallas is not None:
        return "pallas" if model.use_pallas else "xla"
    seq = shape.seq_len + (cfg.vision.n_patches if cfg.family == "vlm"
                           else 0)
    one_tpu = mesh.devices.size == 1 and mesh.devices.flat[0].platform == "tpu"
    return "pallas" if one_tpu and seq % 128 == 0 else "xla"


def make_train_step(model: Model, tcfg: TrainConfig, shape: ShapeConfig,
                    mesh: Mesh, total_steps: int = 10_000):
    """Returns (train_step, state_shardings, batch_shardings, state_specs).

    Attention is chosen here, by ``attention_path``; the SSD kernel runs
    only where ``model.use_pallas`` asks for it: it has no backward pass,
    so training takes XLA's ``ssd_chunked``. Kernels compile natively for
    a TPU mesh, in interpret mode for any other.
    """
    model = Model(model.cfg,
                  attention_path(model, mesh, shape) == "pallas"
                  or bool(model.use_pallas),
                  interpret=mesh.devices.flat[0].platform != "tpu")
    opt = _optimizer(tcfg, total_steps)
    n_micro = n_microbatches(shape, mesh, tcfg)
    rules = train_rules(model.cfg.family)

    # ---- state specs ----
    p_specs = model.param_specs()
    p_axes = model.param_axes()
    param_sh = shardings_for_tree(p_specs, p_axes, rules, mesh)
    opt_sh = _zero1_shardings(p_specs, p_axes, rules, mesh)
    f32 = lambda t: jax.tree.map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), t)
    fm = lambda t: jax.tree.map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(MOMENT_DTYPE)), t)
    state_specs = {
        "params": p_specs,
        "opt": AdamWState(jax.ShapeDtypeStruct((), jnp.int32),
                          f32(p_specs), fm(p_specs), fm(p_specs)),
        "data_step": jax.ShapeDtypeStruct((), jnp.int32),
    }
    scalar_sh = NamedSharding(mesh, PartitionSpec())
    state_sh = {
        "params": param_sh,
        "opt": AdamWState(scalar_sh, opt_sh, opt_sh, opt_sh),
        "data_step": scalar_sh,
    }

    # ---- batch specs ----
    in_ax = input_axes(model.cfg)
    batch_specs = model.input_specs(shape)
    batch_sh = shardings_for_tree(batch_specs, in_ax, rules, mesh)

    # f32 gradient accumulators: ZeRO-2 — accumulate in the *optimizer*
    # sharding (param sharding + the ZeRO data axis), so each device holds
    # only its update shard and the backward emits reduce-scatters. An
    # unconstrained scan carry would replicate them (observed: +30 GB/device
    # on qwen2-7b; mixtral's f32 grads alone are 4.9 GB/device unsharded).
    grad_sh = opt_sh

    # ---- the step ----
    def train_step(state: Dict[str, Any], batch: Dict[str, jax.Array]):
        params = state["params"]

        def micro_batches(b):
            # CAREFUL: reshape (B,...)→(n_micro, B/n,...) would move the
            # data-sharded batch dim onto the scan axis (the contiguous
            # groups of the major dim), silently replicating each micro
            # step's batch on every device (observed 16x activation blow-up).
            # Keep n_micro minor, swap, and pin the sharding explicitly.
            bspec = ("data",) if "data" in mesh.shape else ()

            def split(x):
                x = x.reshape(x.shape[0] // n_micro, n_micro,
                              *x.shape[1:]).swapaxes(0, 1)
                return jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, PartitionSpec(None, bspec)))
            return jax.tree.map(split, b)

        def micro_step(acc, mb):
            (loss, metrics), grads = jax.value_and_grad(
                model.loss, has_aux=True)(params, mb, tcfg.remat)
            # pin per-micro grads too: bidirectional SPMD propagation then
            # turns the backward weight-grad einsums into reduce-scatters
            # instead of materialising full f32 tensors per device
            grads = jax.lax.with_sharding_constraint(grads, grad_sh)
            acc_g, acc_l = acc
            acc_g = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32) / n_micro, acc_g, grads)
            acc_g = jax.lax.with_sharding_constraint(acc_g, grad_sh)
            return (acc_g, acc_l + loss / n_micro), metrics

        zeros = jax.lax.with_sharding_constraint(
            jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
            grad_sh)
        (grads, loss), metrics = jax.lax.scan(
            micro_step, (zeros, jnp.zeros((), jnp.float32)),
            micro_batches(batch))

        with jax.named_scope("optimizer"):
            new_params, new_opt, opt_metrics = opt.update(
                grads, state["opt"], params)
        out_metrics = {
            "loss": loss,
            "ce": metrics["ce"].mean(),
            **opt_metrics,
        }
        return {
            "params": new_params,
            "opt": new_opt,
            "data_step": state["data_step"] + 1,
        }, out_metrics

    return train_step, state_sh, batch_sh, state_specs


def _zero1_shardings(p_specs: Any, p_axes: Any, rules: Rules,
                     mesh: Mesh) -> Any:
    """Optimizer-state shardings: the param spec + one extra data-axis
    assignment on the first unsharded divisible dim (ZeRO-1)."""
    is_sds = lambda x: isinstance(x, jax.ShapeDtypeStruct)  # noqa: E731
    flat_s, treedef = jax.tree.flatten(p_specs, is_leaf=is_sds)
    flat_a = treedef.flatten_up_to(p_axes)
    data_n = mesh.shape.get("data", 1)
    out = []
    for s, ax in zip(flat_s, flat_a):
        spec = list(spec_for(s.shape, ax, rules, mesh))
        spec += [None] * (len(s.shape) - len(spec))
        if data_n > 1:
            used = {a for e in spec if e
                    for a in (e if isinstance(e, tuple) else (e,))}
            if "data" not in used:
                for i, (size, cur) in enumerate(zip(s.shape, spec)):
                    if cur is None and size % data_n == 0:
                        spec[i] = "data"
                        break
        out.append(NamedSharding(mesh, PartitionSpec(*spec)))
    return jax.tree.unflatten(treedef, out)


def _optimizer(tcfg: TrainConfig, total_steps: int) -> AdamW:
    return AdamW(lr=warmup_cosine(tcfg.learning_rate, tcfg.warmup_steps,
                                  total_steps),
                 weight_decay=tcfg.weight_decay, grad_clip=tcfg.grad_clip,
                 mom_dtype=MOMENT_DTYPE)


def init_state(model: Model, tcfg: TrainConfig, rng: jax.Array,
               total_steps: int = 10_000) -> Dict[str, Any]:
    """Unsharded state init on the default device; its dtypes match what
    the train step returns, so a compiled step takes its own output."""
    opt = _optimizer(tcfg, total_steps)
    params = model.init(rng)
    return {"params": params, "opt": opt.init(params),
            "data_step": jnp.zeros((), jnp.int32)}
