"""Jit'd public wrappers for the Pallas kernels.

On CPU (this container) the kernels execute in interpret mode — Python
evaluation of the kernel body, used by the test suite to validate against
the ``ref.py`` oracles. On TPU backends they compile natively. The model
code calls these where its ``use_pallas`` is set or, for attention, where
the train step selects the kernel (``runtime/train.py::attention_path``).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .flash_attention import (
    flash_attention_bwd,
    flash_attention_fwd,
    flash_attention_pallas,
)
from .moe_gmm import moe_gmm_pallas
from .rmsnorm import rmsnorm_pallas
from .ssd_scan import ssd_scan_pallas


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# differentiable flash attention: Pallas forward + Pallas flash-v2 backward
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal: bool, window: int, interpret: bool):
    return flash_attention_fwd(q, k, v, causal=causal, window=window,
                               interpret=interpret)[0]


def _flash_fwd(q, k, v, causal, window, interpret):
    o, lse = flash_attention_fwd(q, k, v, causal=causal, window=window,
                                 interpret=interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, window, interpret, res, do):
    q, k, v, o, lse = res
    return flash_attention_bwd(q, k, v, o, lse, do, causal=causal,
                               window=window, interpret=interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    interpret: Optional[bool] = None):
    """``interpret`` None: interpret mode unless the default backend is a
    TPU. A step compiled for a TPU from another host passes False."""
    return _flash(q, k, v, causal, window,
                  _interpret() if interpret is None else interpret)


@jax.jit
def rmsnorm(x, scale, eps: float = 1e-6):
    return rmsnorm_pallas(x, scale, eps, interpret=_interpret())


@jax.jit
def moe_gmm(buf, w):
    return moe_gmm_pallas(buf, w, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(xh, dt, a, B_, C_, *, chunk: int = 256):
    return ssd_scan_pallas(xh, dt, a, B_, C_, chunk=chunk,
                           interpret=_interpret())
