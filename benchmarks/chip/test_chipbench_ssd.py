"""The mamba2-370m cell: its spec as ``run.py`` loads it, the SSD scope's
readers, a traced smoke-size run, and the chip readings of the program
before its decays were kept in float32."""
import time

import jax
import pytest

import run as R
from smoke import smoke_spec

CELL = "mamba2-370m.train-chunk16"
# the step's device self time by scope, median of 12 executions, as a
# traced run of the cell read it on one TPU v5e
SCOPE_S = {"ssd": 0.32292161550001297, "head_loss": 0.0024524610000009606,
           "optimizer": 0.015774324999999312,
           "(unscoped)": 0.6180714749999827}


def test_the_cell_runs_the_configuration_as_stated():
    spec = R.load_spec(CELL)
    assert R.program_mismatches(spec["config"], R.program_config(spec)) == []
    assert spec["reference"].SCOPES == ("ssd",)
    names = {m["name"] for m in spec["per_layer"]}
    assert {"train_step.ssd_ms", "train_step.ssd_roofline_pct",
            "train_step.mfu", "train_step.head_loss_ms"} <= names
    # no attention and no MLP scope in this model
    assert not names & {"train_step.attn_core_ms", "train_step.mlp_ms"}


def _rec(spans):
    spec = R.load_spec(CELL)
    trf = spec["traffic"]
    return {"config": spec["config"], "batch": trf["batch"],
            "seq": trf["seq"], "spans": spans,
            "peaks": R._json(R.BENCH / "peaks.json")["TPU v5 lite"]}


def test_the_ssd_readers_on_a_recorded_step():
    rec = _rec({"scope_s": SCOPE_S})
    assert R.reader("train_step.ssd_ms")(rec) == pytest.approx(
        SCOPE_S["ssd"] * 1e3)
    share = R.reader("train_step.ssd_roofline_pct")(rec)
    assert 0 < share <= 100
    # bound by bytes: 143.7 MB a layer and pass at 819 GB/s, 144 passes
    assert share == pytest.approx(100 * 144 * 143_654_912 / 819e9
                                  / SCOPE_S["ssd"])
    # the same work in half the time is twice the share
    rec["spans"]["scope_s"] = dict(SCOPE_S, ssd=SCOPE_S["ssd"] / 2)
    assert R.reader("train_step.ssd_roofline_pct")(rec) == pytest.approx(
        2 * share)


@pytest.mark.parametrize("spans", [None, {"scope_s": None},
                                   {"scope_s": {"head_loss": 0.03}}],
                         ids=["no-trace", "no-scope-map", "no-ssd-scope"])
def test_the_ssd_readers_read_nothing_without_the_scope(spans):
    rec = _rec(spans)
    assert R.reader("train_step.ssd_ms")(rec) is None
    assert R.reader("train_step.ssd_roofline_pct")(rec) is None


def test_a_traced_smoke_run_of_the_cell():
    """At smoke size on the CPU, with the cell's own metrics: correct, the
    host spans read, and the device-time metrics (the SSD's among them)
    read nothing, since a CPU trace has no TPU plane."""
    spec = smoke_spec("mamba2-370m")
    spec["per_layer"] = R.load_spec(CELL)["per_layer"]
    spec["cell"]["trace_steps"] = 3
    assert R.program_mismatches(spec["config"], R.program_config(spec)) == []
    out = R.run(spec, 2147483803, 0.5, True, jax.devices()[:1],
                time.monotonic())
    res = out["result"]
    assert res["correct"], res["checks"]
    got = res["metrics"]
    assert got["train_loop.input_ms"]["value"] > 0
    assert not any(m.startswith("train_step.") for m in got)


def test_the_chip_readings_of_the_bfloat16_decays_fail():
    """The program as it was before its decays were kept in float32 (the
    cumsum of dt·a, the segment sums and their exps in bfloat16), read on
    the chip at the cell's own size: every seed fails a limit."""
    limits = R._json(R.BENCH / "cells" / f"{CELL}.json")["limits"]
    seen = R._json(R.BENCH / "fixtures" / f"readings.{CELL}.json")[
        "program_bf16_decays"]
    assert len(seen) >= 3
    for r in seen:
        assert not R.verdict(r, limits, []), r
