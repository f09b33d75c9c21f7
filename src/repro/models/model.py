"""Unified model API over all assigned architecture families.

``Model`` is a thin, stateless dispatcher: one schema (→ init / specs /
logical axes from a single source of truth), one ``loss`` for training, and
``init_cache``/``decode_step`` for serving. Everything is a pure function
of (params, batch) so pjit/shard_map wrap it directly.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, ShapeConfig
from . import encdec, hybrid, mamba2, transformer
from .layers import (
    Schema,
    count_params,
    init_params,
    param_axes,
    param_specs,
)

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
           "float16": jnp.float16}


class Model:
    """``use_pallas``: True or False forces the Pallas kernels on or off;
    None leaves causal attention to the train step, which picks it by the
    devices of its mesh (``runtime/train.py::attention_path``), and
    elsewhere means off. ``interpret`` runs them in interpret mode (None:
    unless the default backend is a TPU)."""

    def __init__(self, cfg: ModelConfig, use_pallas: Optional[bool] = None,
                 interpret: Optional[bool] = None) -> None:
        self.cfg = cfg
        self.use_pallas = use_pallas
        self.interpret = interpret
        self.param_dtype = _DTYPES[cfg.param_dtype]
        if cfg.family in ("dense", "vlm", "moe"):
            self.schema: Schema = transformer.lm_schema(cfg)
        elif cfg.family == "ssm":
            self.schema = mamba2.ssm_lm_schema(cfg)
        elif cfg.family == "hybrid":
            self.schema = hybrid.hybrid_schema(cfg)
        elif cfg.family == "audio":
            self.schema = encdec.encdec_schema(cfg)
        else:
            raise ValueError(f"unknown family {cfg.family!r}")

    # ---------------- params ----------------
    def init(self, rng: jax.Array) -> Dict[str, Any]:
        return init_params(self.schema, rng, self.param_dtype)

    def param_specs(self) -> Dict[str, Any]:
        return param_specs(self.schema, self.param_dtype)

    def param_axes(self) -> Dict[str, Any]:
        return param_axes(self.schema)

    def n_params(self) -> int:
        return count_params(self.param_specs())

    # ---------------- training ----------------
    def logits(self, params: Dict[str, Any], batch: Dict[str, jax.Array],
               remat: str = "block") -> Tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        use_pallas = bool(self.use_pallas)
        if cfg.family in ("dense", "moe", "vlm"):
            return transformer.forward(cfg, params, batch["tokens"],
                                       patches=batch.get("patches"),
                                       remat=remat, use_pallas=use_pallas,
                                       interpret=self.interpret)
        if cfg.family == "ssm":
            return mamba2.ssm_forward(cfg, params, batch["tokens"],
                                      remat=remat, use_pallas=use_pallas)
        if cfg.family == "hybrid":
            return hybrid.forward(cfg, params, batch["tokens"], remat=remat,
                                  use_pallas=use_pallas)
        if cfg.family == "audio":
            return encdec.forward(cfg, params, batch["tokens"],
                                  batch["frames"], remat=remat)
        raise ValueError(cfg.family)

    def loss(self, params: Dict[str, Any], batch: Dict[str, jax.Array],
             remat: str = "block") -> Tuple[jax.Array, Dict[str, jax.Array]]:
        logits, aux = self.logits(params, batch, remat)
        with jax.named_scope("head_loss"):
            lg = logits.astype(jnp.float32)
            labels = batch["labels"]
            lse = jax.nn.logsumexp(lg, axis=-1)
            gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
            ce = (lse - gold).mean()
        total = ce
        if self.cfg.moe is not None:
            total = total + self.cfg.moe.aux_loss_weight * aux
        return total, {"ce": ce, "aux": aux,
                       "ppl_proxy": jnp.exp(jnp.clip(ce, 0, 20.0))}

    # ---------------- serving ----------------
    def init_cache(self, batch: int, max_len: int) -> Dict[str, Any]:
        cfg = self.cfg
        dt = self.param_dtype
        if cfg.family in ("dense", "moe", "vlm"):
            return transformer.init_cache(cfg, batch, max_len, dt)
        if cfg.family == "ssm":
            return mamba2.ssm_init_cache(cfg, batch, max_len, dt)
        if cfg.family == "hybrid":
            return hybrid.init_cache(cfg, batch, max_len, dt)
        if cfg.family == "audio":
            return encdec.init_cache(cfg, batch, max_len, dt)
        raise ValueError(cfg.family)

    def cache_specs(self, batch: int, max_len: int) -> Dict[str, Any]:
        cfg = self.cfg
        if cfg.family in ("dense", "moe", "vlm"):
            shapes = transformer.cache_shapes(cfg, batch, max_len)
        elif cfg.family == "ssm":
            shapes = mamba2.ssm_cache_shapes(cfg, batch, max_len)
        elif cfg.family == "hybrid":
            shapes = hybrid.cache_shapes(cfg, batch, max_len)
        elif cfg.family == "audio":
            shapes = encdec.cache_shapes(cfg, batch, max_len)
        else:
            raise ValueError(cfg.family)
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s, self.param_dtype), shapes,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(i, int) for i in x))

    def decode_step(self, params: Dict[str, Any], cache: Dict[str, Any],
                    token: jax.Array, pos: jax.Array,
                    ) -> Tuple[jax.Array, Dict[str, Any]]:
        cfg = self.cfg
        if cfg.family in ("dense", "moe", "vlm"):
            return transformer.decode_step(cfg, params, cache, token, pos)
        if cfg.family == "ssm":
            return mamba2.ssm_decode_step(cfg, params, cache, token, pos)
        if cfg.family == "hybrid":
            return hybrid.decode_step(cfg, params, cache, token, pos)
        if cfg.family == "audio":
            return encdec.decode_step(cfg, params, cache, token, pos)
        raise ValueError(cfg.family)

    # ---------------- train-step inputs ----------------
    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for a train step's batch."""
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        specs = {
            "tokens": jax.ShapeDtypeStruct((B, S), i32),
            "labels": jax.ShapeDtypeStruct((B, S), i32),
        }
        if cfg.family == "vlm":
            assert cfg.vision is not None
            specs["patches"] = jax.ShapeDtypeStruct(
                (B, cfg.vision.n_patches, cfg.vision.patch_dim),
                self.param_dtype)
        if cfg.family == "audio":
            assert cfg.encdec is not None
            specs["frames"] = jax.ShapeDtypeStruct(
                (B, cfg.encdec.n_frames, cfg.d_model), self.param_dtype)
        return specs


def build_model(cfg: ModelConfig, use_pallas: Optional[bool] = None) -> Model:
    return Model(cfg, use_pallas)
