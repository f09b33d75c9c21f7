"""Compile guard: the main path's kernels and one full-width train step,
compiled for a described (not attached) TPU v5e chip.

Nothing runs; the TPU compiler refuses here what it would refuse on the
chip (block layouts, unsupported primitives, programs that do not fit).
The topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker given this
file loads the TPU library.
"""
import os

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import (
    flash_attention_bwd,
    flash_attention_fwd,
)
from repro.kernels.ssd_scan import ssd_scan_pallas

# (batch, seq, q heads, kv heads, head dim) of one micro-step
FLASH_WIDTHS = {
    "qwen1.5-0.5b": (4, 1024, 16, 16, 64),
    "qwen2-7b": (1, 1024, 28, 4, 128),      # GQA 7:1
}
# bf16 is what the models feed; f32 takes the full-precision contractions
DTYPES = {"bf16": jnp.bfloat16, "f32": jnp.float32}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("arch", sorted(FLASH_WIDTHS))
def test_flash_attention_fwd_compiles(arch, dtype, one_chip):
    B, S, Hq, Hkv, D = FLASH_WIDTHS[arch]
    q = _sds((B, S, Hq, D), DTYPES[dtype], one_chip)
    kv = _sds((B, S, Hkv, D), DTYPES[dtype], one_chip)
    _compile_kernel(lambda q, k, v: flash_attention_fwd(q, k, v), q, kv, kv)


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("arch", sorted(FLASH_WIDTHS))
def test_flash_attention_bwd_compiles(arch, dtype, one_chip):
    B, S, Hq, Hkv, D = FLASH_WIDTHS[arch]
    q = _sds((B, S, Hq, D), DTYPES[dtype], one_chip)
    kv = _sds((B, S, Hkv, D), DTYPES[dtype], one_chip)
    lse = _sds((B, Hq, 1, S), jnp.float32, one_chip)
    _compile_kernel(
        lambda q, k, v, o, lse, do: flash_attention_bwd(q, k, v, o, lse, do),
        q, kv, kv, q, lse, q)


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_ssd_scan_compiles(dtype, one_chip):
    # mamba2-370m: d_inner 2048 = 32 heads × 64, state 128, chunk 256
    B, S, H, P, G, N = 2, 1024, 32, 64, 1, 128
    ty = DTYPES[dtype]
    _compile_kernel(
        lambda x, dt, a, b, c: ssd_scan_pallas(x, dt, a, b, c, chunk=256)[0],
        _sds((B, S, H, P), ty, one_chip),
        _sds((B, S, H), ty, one_chip),
        _sds((H,), ty, one_chip),
        _sds((B, S, G, N), ty, one_chip),
        _sds((B, S, G, N), ty, one_chip))


def test_attention_path_follows_the_mesh(topo):
    """A mesh of one described v5e selects the flash kernel; several
    devices, a sequence off the 128 grid, or an explicit flag keep XLA."""
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.runtime.train import attention_path

    model = build_model(get_config("qwen1.5-0.5b"))
    one = make_host_mesh(topo.devices[:1])
    shape = ShapeConfig("s", 1024, 8)
    assert attention_path(model, one, shape) == "pallas"
    assert attention_path(model, make_host_mesh(topo.devices), shape) == "xla"
    assert attention_path(model, one, ShapeConfig("s", 1000, 8)) \
        == "xla"
    forced = build_model(get_config("qwen1.5-0.5b"), use_pallas=False)
    assert attention_path(forced, one, shape) == "xla"
    # 32,768 tokens + 576 patches is off the 128 grid
    vlm = build_model(get_config("phi-3-vision-4.2b"))
    assert attention_path(vlm, one, ShapeConfig("s", 32768, 1)) \
        == "xla"


def test_full_width_train_step_fits_one_chip(topo):
    """qwen1.5-0.5b at published widths, global batch 8 × 1024 in two
    micro-steps of 4, on the path a one-chip mesh selects (the flash
    kernel): arguments + temporaries stay under 15 GiB of the chip's 16."""
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig, TrainConfig
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.runtime.train import make_train_step

    model = build_model(get_config("qwen1.5-0.5b"))
    shape = ShapeConfig("smoke", 1024, 8)
    tcfg = TrainConfig(microbatch_per_device=4)
    step, state_sh, batch_sh, state_specs = make_train_step(
        model, tcfg, shape, make_host_mesh(topo.devices[:1]))
    compiled = jax.jit(step, in_shardings=(state_sh, batch_sh),
                       out_shardings=(state_sh, None),
                       donate_argnums=(0,)).lower(
        state_specs, model.input_specs(shape)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < 15 * 2**30, used / 2**30
