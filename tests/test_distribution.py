"""Distribution-layer tests: sharding rule resolution, ZeRO state sharding,
checkpoint roundtrip (incl. bf16 + resharding), data-pipeline determinism,
optimizer math, and the continuous batcher."""
import os
import tempfile

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh, AxisType, PartitionSpec

from repro.checkpoint import (
    latest_checkpoint,
    prune_checkpoints,
    restore_checkpoint,
    save_checkpoint,
)
from repro.configs import get_config
from repro.data import DataConfig, TokenPipeline
from repro.models import build_model
from repro.optim import AdamW, warmup_cosine
from repro.runtime.sharding import base_rules, spec_for, train_rules
from repro.runtime.train import _zero1_shardings


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------
class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def test_spec_divisibility_degradation():
    mesh = _FakeMesh({"data": 16, "model": 16})
    rules = train_rules()
    # divisible vocab shards; whisper's 51865 must degrade to replicated
    s1 = spec_for((262144, 3840), ("vocab", "embed"), rules, mesh)
    assert s1 == PartitionSpec("model", None)
    s2 = spec_for((51865, 384), ("vocab", "embed"), rules, mesh)
    assert s2 == PartitionSpec(None, None)


def test_spec_no_axis_reuse():
    mesh = _FakeMesh({"data": 16, "model": 16})
    rules = dict(train_rules())
    rules["x"] = "model"
    rules["y"] = "model"
    s = spec_for((64, 64), ("x", "y"), rules, mesh)
    # "model" must be used at most once per tensor
    flat = [a for a in s if a is not None]
    assert flat == ["model"] or flat == [("model",)]


def test_moe_ff_sharding_spans_data_and_model():
    mesh = _FakeMesh({"data": 16, "model": 16})
    rules = base_rules(family="moe")
    s = spec_for((8, 6144, 16384), ("experts", "embed", "ff"), rules, mesh)
    assert s[2] == ("data", "model")


# (data, model) extents, family, parameter shape and logical axes, and the
# optimizer-state spec ZeRO-1 gives it
ZERO1_CASES = {
    "data_on_first_unsharded_dim": (
        (2, 4), "dense", (6, 8), ("embed", "ff"), ("data", "model")),
    "data_skips_an_indivisible_dim": (
        (2, 4), "dense", (3, 8, 4), ("embed", "ff", None),
        (None, "model", "data")),
    "data_on_a_vector": ((2, 4), "dense", (8,), ("embed",), ("data",)),
    "spec_already_on_data_stays": (
        (2, 4), "moe", (8, 16, 32), ("experts", "embed", "ff"),
        (None, None, ("data", "model"))),
    "no_divisible_dim_leaves_spec": (
        (2, 4), "dense", (3, 8), ("embed", "ff"), (None, "model")),
    "data_1_gives_param_spec": (
        (1, 4), "dense", (6, 8), ("embed", "ff"), (None, "model")),
}


@pytest.mark.parametrize("case", sorted(ZERO1_CASES))
def test_zero1_shardings_on_abstract_mesh(case):
    extents, family, shape, axes, want = ZERO1_CASES[case]
    mesh = AbstractMesh(extents, ("data", "model"),
                        axis_types=(AxisType.Auto,) * 2)
    rules = train_rules(family)
    specs = {"w": jax.ShapeDtypeStruct(shape, jnp.bfloat16)}
    out = _zero1_shardings(specs, {"w": axes}, rules, mesh)["w"]
    assert out.mesh == mesh
    assert out.spec == PartitionSpec(*want)
    if extents[0] == 1:
        assert out.spec == spec_for(shape, axes, rules, mesh)


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------
def _state():
    return {
        "params": {"w": jnp.arange(12, dtype=jnp.bfloat16).reshape(3, 4),
                   "b": jnp.ones((4,), jnp.float32)},
        "step": jnp.int32(7),
    }


def test_checkpoint_roundtrip_bf16():
    with tempfile.TemporaryDirectory() as d:
        state = _state()
        save_checkpoint(d, 7, state)
        ck = latest_checkpoint(d)
        assert ck and ck.endswith("step_00000007")
        restored, manifest = restore_checkpoint(ck, state)
        assert manifest["step"] == 7
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))


def test_checkpoint_prune_and_latest():
    with tempfile.TemporaryDirectory() as d:
        for s in (1, 2, 3, 4):
            save_checkpoint(d, s, _state())
        prune_checkpoints(d, keep=2)
        kept = sorted(os.listdir(d))
        assert kept == ["step_00000003", "step_00000004"]
        assert latest_checkpoint(d).endswith("step_00000004")


def test_checkpoint_detects_corruption():
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 1, _state())
        ck = latest_checkpoint(d)
        # corrupt one leaf
        target = os.path.join(ck, "params__w.npy")
        arr = np.load(target)
        arr = arr + 1
        np.save(target, arr)
        with pytest.raises(IOError):
            restore_checkpoint(ck, _state())


def test_async_checkpointer():
    from repro.checkpoint import AsyncCheckpointer
    with tempfile.TemporaryDirectory() as d:
        ac = AsyncCheckpointer(d, keep=2)
        for s in (10, 20, 30):
            ac.save(s, _state())
        written = ac.wait()
        assert len(written) == 3
        assert latest_checkpoint(d).endswith("step_00000030")


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------
def test_pipeline_deterministic_and_sharded():
    c = DataConfig(vocab=1000, seq_len=64, global_batch=8, seed=5)
    p1, p2 = TokenPipeline(c), TokenPipeline(c)
    b1, b2 = p1.batch(17), p2.batch(17)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(p1.batch(18)["tokens"], b1["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])
    # host shards partition the global batch deterministically
    sh0 = TokenPipeline(DataConfig(vocab=1000, seq_len=64, global_batch=8,
                                   seed=5, shards=2, shard_id=0)).batch(17)
    assert sh0["tokens"].shape == (4, 64)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------
def test_adamw_converges_quadratic():
    opt = AdamW(lr=lambda s: jnp.float32(0.1), weight_decay=0.0,
                grad_clip=0.0)
    params = {"x": jnp.array([5.0, -3.0])}
    state = opt.init(params)
    for _ in range(200):
        grads = {"x": 2 * state.master["x"]}    # d/dx x^2
        params, state, _ = opt.update(grads, state, params)
    assert float(jnp.abs(params["x"]).max()) < 1e-2


def test_warmup_cosine_schedule():
    lr = warmup_cosine(1e-3, warmup=10, total=100)
    assert float(lr(jnp.int32(5))) == pytest.approx(5e-4)
    assert float(lr(jnp.int32(10))) == pytest.approx(1e-3, rel=1e-2)
    assert float(lr(jnp.int32(100))) == pytest.approx(1e-4, rel=1e-2)


# ---------------------------------------------------------------------------
# serving batcher
# ---------------------------------------------------------------------------
def test_continuous_batcher_drains_and_isolates_slots():
    from repro.runtime.serve import ContinuousBatcher, Request
    cfg = get_config("qwen1.5-0.5b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b = ContinuousBatcher(model, params, batch_slots=2, max_len=48)
    rng = np.random.default_rng(1)
    reqs = [Request(f"r{i}", rng.integers(2, cfg.vocab, 5).tolist(),
                    max_new_tokens=6) for i in range(5)]
    for r in reqs:
        b.submit(r)
    b.drain()
    assert all(r.done for r in reqs)
    assert all(1 <= len(r.tokens_out) <= 6 for r in reqs)


# ---------------------------------------------------------------------------
# fault handling: watchdog, elastic plan, resume_or_init
# ---------------------------------------------------------------------------
def test_step_watchdog_flags_stragglers():
    from repro.runtime.fault import StepWatchdog
    import time as _time
    events = []
    wd = StepWatchdog(factor=2.0, min_samples=3,
                      on_straggler=lambda s, dt, med: events.append(s))
    for i in range(8):
        wd.start()
        _time.sleep(0.02 if i != 6 else 0.12)   # step 7 straggles
        flagged = wd.stop()
        assert flagged == (i == 6)
    assert events == [7]
    assert wd.stats()["stragglers"] == 1


def test_elastic_plan_batch_math():
    from repro.runtime.fault import ElasticPlan
    p = ElasticPlan(old_devices=512, new_devices=256, keep_global_batch=True)
    assert p.new_mesh_shape(model_parallel=16) == (16, 16)
    gb, per_dev = p.adjust_batch(256, dp_old=32, dp_new=16)
    assert (gb, per_dev) == (256, 16)           # trajectory preserved
    p2 = ElasticPlan(512, 256, keep_global_batch=False)
    gb2, per2 = p2.adjust_batch(256, dp_old=32, dp_new=16)
    assert (gb2, per2) == (128, 8)              # throughput preserved


def test_resume_or_init_roundtrip():
    from repro.runtime.fault import resume_or_init
    with tempfile.TemporaryDirectory() as d:
        state, step = resume_or_init(d, _state)
        assert step == 0                         # nothing to restore
        save_checkpoint(d, 42, state)
        state2, step2 = resume_or_init(d, _state)
        assert step2 == 42
        np.testing.assert_array_equal(
            np.asarray(state["params"]["w"], np.float32),
            np.asarray(state2["params"]["w"], np.float32))
