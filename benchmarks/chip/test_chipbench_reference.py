"""The plain reference against ``run_training`` at smoke size on the CPU,
and the control (the reference in float8) against the limits."""
import time

import jax
import pytest

import run as R
from smoke import SMOKE, smoke_spec

DENSE = (R.BENCH / "references" / "dense.py").read_text()


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
    mine = reference.init_params(spec["reference"], spec["config"],
                                 2147483647)
    theirs = R._flat(build_model(spec["program_cfg"]).init(
        jax.random.PRNGKey(2147483647)))
    assert sorted(mine) == sorted(theirs)
    for k in mine:
        assert mine[k].dtype == theirs[k].dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(mine[k], np.float32),
                                      np.asarray(theirs[k], np.float32))


PINS = R._json(R.BENCH / "fixtures" / "reference_pins.json")


@pytest.mark.parametrize("config", sorted(PINS["configs"]))
def test_the_reference_keeps_its_recorded_numbers(config):
    """The parameters drawn from the seed and the first three losses, at
    smoke size, as the reference gave them before its models moved into
    ``references/``."""
    import numpy as np
    import reference
    pin = PINS["configs"][config]
    spec = smoke_spec(config)
    params = reference.init_params(spec["reference"], spec["config"],
                                   PINS["seed"])
    assert {k: list(v.shape) for k, v in params.items()} == \
        pin["param_shapes"]
    norms = {k: float(np.linalg.norm(np.asarray(v, np.float64).ravel()))
             for k, v in params.items()}
    assert norms == pytest.approx(pin["param_norms"], rel=1e-12)
    ref = R.reference_run(spec, PINS["seed"], PINS["total_steps"])
    assert ref["losses"] == pytest.approx(pin["losses"], rel=1e-6)
    assert ref["grad_norm"] == pytest.approx(pin["grad_norm"], rel=1e-6)


def test_a_kind_is_added_by_a_file_alone(tmp_path, monkeypatch):
    """A reference module under a new name, in a directory of its own, is
    all that a configuration of a new kind needs of the harness: the run
    (its check, its FLOP count) finds it by the configuration's name."""
    import shutil
    from pathlib import Path
    import reference
    shutil.copy(reference.REFERENCES / "dense.py", tmp_path / "plain.py")
    spec = smoke_spec("qwen1.5-0.5b")
    spec["config"]["reference"] = "plain"
    with pytest.raises(SystemExit, match="plain.py"):
        reference.load(spec["config"])           # not in references/
    monkeypatch.setattr(reference, "REFERENCES", tmp_path)
    spec["reference"] = reference.load(spec["config"])
    assert Path(spec["reference"].__file__).parent == tmp_path
    out = R.run(spec, 2147483791, 0.2, False, jax.devices()[:1],
                time.monotonic())
    assert out["problems"] == []
    assert out["result"]["correct"], out["result"]["checks"]


def _no_key(tmp_path):
    """A configuration file with no ``reference``, read for its cell."""
    import json
    bench = json.loads((R.ROOT / "BENCHMARK.json").read_text())
    conf = json.loads((R.ROOT / bench["configs"][0]["file"]).read_text())
    del conf["reference"]
    (tmp_path / "conf.json").write_text(json.dumps(conf))
    bench["configs"][0]["file"] = "conf.json"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    R.load_spec(bench["workloads"][0]["name"], root=tmp_path)


def _no_file(tmp_path):
    import reference
    reference.load({"registry": "x", "reference": "absent"})


def _no_loss(tmp_path):
    """A module without one of the names the harness calls."""
    import reference
    (tmp_path / "part.py").write_text(DENSE.replace("def block_loss(",
                                                    "def _block_loss("))
    reference.load({"registry": "x", "reference": "part"})


@pytest.mark.parametrize("case,file,says", [
    (_no_key, "conf.json", "names no 'reference'"),
    (_no_file, "absent.py", "no reference module"),
    (_no_loss, "part.py", "block_loss")], ids=["no_key", "no_file", "no_loss"])
def test_a_missing_reference_module_stops_and_names_the_file(
        case, file, says, tmp_path, monkeypatch):
    import reference
    monkeypatch.setattr(reference, "REFERENCES", tmp_path)
    with pytest.raises(SystemExit) as e:
        case(tmp_path)
    assert str(tmp_path / file) in str(e.value)
    assert says in str(e.value)
