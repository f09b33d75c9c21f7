"""The training entry point (``launch/train.py``) at smoke size on the host.

``run_training`` is what the CLI and ``chip_smoke.py`` both call: the job
is compiled into chunk tasks and run CWSI → CWS → ``LocalExecutor`` →
jitted step, with the state in the step's own shardings on the mesh.
"""
import math
import os

import jax
import pytest
from jax.sharding import AxisType

from repro.configs import get_config
from repro.launch import train as launch_train
from repro.launch.mesh import make_host_mesh, make_mesh
from repro.launch.train import run_training

CFG = get_config("qwen1.5-0.5b", smoke=True)
SMALL = dict(batch=4, seq=32, microbatch=2, lr=5e-3)


def _chunk_tasks(out):
    return sorted((t for t in out["dag"].tasks.values()
                   if t.name == "train_chunk"), key=lambda t: t.task_id)


def test_host_mesh_axes_are_auto():
    mesh = make_host_mesh()
    assert mesh.axis_names == ("data", "model")
    assert tuple(mesh.axis_types) == (AxisType.Auto, AxisType.Auto)
    assert tuple(make_mesh((1,), ("model",)).axis_types) == (AxisType.Auto,)


def test_host_mesh_takes_a_device_subset():
    mesh = make_host_mesh(jax.devices()[:1])
    assert dict(mesh.shape) == {"data": 1, "model": 1}


def test_run_training_runs_each_chunk_once_and_loss_falls():
    lines = []
    out = run_training(CFG, steps=6, chunk=2, log=lines.append, **SMALL)
    # a CPU mesh keeps the XLA attention, and the first line says so
    assert out["attention"] == "xla"
    assert lines[0].endswith("attention=xla"), lines[0]
    tasks = _chunk_tasks(out)
    assert len(tasks) == 3
    assert all(t.state.value == "SUCCEEDED" and t.attempt == 0
               for t in tasks)
    assert out["chunk_runs"] == [1, 1, 1]
    losses = out["losses"]
    assert len(losses) == len(out["step_seconds"]) == 6
    assert all(map(math.isfinite, losses)), losses
    assert abs(losses[0] - math.log(CFG.vocab)) < 1.0, losses
    assert sum(losses[-2:]) < sum(losses[:2]), losses
    assert out["compile_seconds"] > 0


def test_run_training_state_lives_on_the_mesh():
    mesh = make_host_mesh()
    out = run_training(CFG, steps=2, chunk=2, mesh=mesh,
                       log=lambda s: None, **SMALL)
    devices = set(mesh.devices.flat)
    for leaf in jax.tree.leaves(out["state"]):
        assert leaf.sharding.device_set == devices


def test_run_training_rejects_a_ragged_microbatch():
    with pytest.raises(ValueError, match="microbatch"):
        run_training(CFG, steps=2, chunk=2, batch=4, seq=32, microbatch=3,
                     lr=5e-3, log=lambda s: None)


def test_run_training_resumes_from_its_last_checkpoint(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    first = run_training(CFG, steps=4, chunk=2, ckpt_dir=ckpt, ckpt_every=2,
                         log=lambda s: None, **SMALL)
    assert sorted(os.listdir(ckpt)) == ["step_00000002", "step_00000004"]
    again = run_training(CFG, steps=6, chunk=2, ckpt_dir=ckpt, ckpt_every=2,
                         log=lambda s: None, **SMALL)
    assert again["chunk_runs"] == [1]            # steps 4..6 only
    assert len(again["losses"]) == 2
    assert int(again["state"]["data_step"]) == 6
    # the resumed run continues the first one's data order and state
    fresh = run_training(CFG, steps=6, chunk=2, log=lambda s: None, **SMALL)
    assert fresh["losses"][:4] == pytest.approx(first["losses"], rel=1e-5)
    assert fresh["losses"][4:] == pytest.approx(again["losses"], rel=1e-3)


def test_compile_cache_follows_the_environment(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert launch_train.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = launch_train.enable_compile_cache()
        assert path == str(launch_train.REPO_ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
