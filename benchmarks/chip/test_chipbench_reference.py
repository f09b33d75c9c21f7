"""The plain reference against ``run_training`` at smoke size on the CPU,
and the control (the reference in float8) against the limits."""
import time

import jax
import pytest

import run as R
from smoke import SMOKE, smoke_spec


@pytest.mark.parametrize("config", sorted(SMOKE))
def test_a_sound_run_is_correct(config):
    spec = smoke_spec(config)
    out = R.run(spec, 2147483659, 0.2, False, jax.devices()[:1],
                time.monotonic())
    res = out["result"]
    assert out["problems"] == []
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 2
    assert res["checks"]["data_tokens_off"]["value"] == 0
    # the step agrees with the reference far inside the limits
    for k in ("loss_gap", "grad_gap", "update_gap"):
        assert res["checks"][k]["value"] < res["checks"][k]["limit"] / 3
    assert set(res["metrics"]) == {m["name"] for m in spec["end_to_end"]}


@pytest.mark.parametrize("config", sorted(SMOKE))
def test_the_float8_control_fails_the_limits(config):
    spec = smoke_spec(config)
    ref = R.reference_run(spec, 5, 20)
    ctl = R.reference_run(spec, 5, 20, mode="fp8")
    gaps = R.compare(ctl, ref)
    limits = spec["cell"]["limits"]
    assert any(gaps[k] > limits[k] for k in gaps), gaps


def test_the_reference_draws_the_programs_parameters():
    import jax.numpy as jnp
    import numpy as np
    import reference
    from repro.models import build_model
    spec = smoke_spec("qwen1.5-0.5b")
    mine = reference.init_params(spec["config"], 2147483647)
    theirs = R._flat(build_model(spec["program_cfg"]).init(
        jax.random.PRNGKey(2147483647)))
    assert sorted(mine) == sorted(theirs)
    for k in mine:
        assert mine[k].dtype == theirs[k].dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(mine[k], np.float32),
                                      np.asarray(theirs[k], np.float32))
