"""A run with the timed path broken underneath comes out not correct: the
harness's look for a chip is skipped, everything else runs."""
import contextlib
import time

import jax
import pytest

import run as R
from smoke import SMOKE, smoke_spec


@contextlib.contextmanager
def broken_step(kind):
    """The program's train step, broken: ``unchanged`` returns the state
    it was given, ``half_batch`` trains on the first half of the rows and
    takes its mean over them."""
    import repro.launch.train as lt
    base = lt.make_train_step

    def patched(*a, **kw):
        step, state_sh, batch_sh, specs = base(*a, **kw)

        def bad(state, batch):
            if kind == "unchanged":
                return state, step(state, batch)[1]
            return step(state, {k: v[: v.shape[0] // 2]
                                for k, v in batch.items()})
        return bad, state_sh, batch_sh, specs

    lt.make_train_step = patched
    try:
        yield
    finally:
        lt.make_train_step = base


@pytest.mark.parametrize("config", sorted(SMOKE))
@pytest.mark.parametrize("kind", ["unchanged", "half_batch"])
def test_a_broken_step_is_not_correct(config, kind):
    spec = smoke_spec(config)
    with broken_step(kind):
        out = R.run(spec, 2147483777, 0.1, False, jax.devices()[:1],
                    time.monotonic())
    assert out["result"]["correct"] is False, out


def test_other_traffic_is_not_correct():
    spec = smoke_spec("qwen1.5-0.5b")
    spec["traffic"]["data"]["zipf_a"] = 1.3    # the program's stays 1.2
    out = R.run(spec, 11, 0.1, False, jax.devices()[:1], time.monotonic())
    assert out["result"]["correct"] is False
    assert out["result"]["checks"]["data_tokens_off"]["value"] > 0


READINGS = sorted((R.BENCH / "fixtures").glob("readings.*.json"))


def _readings(kind):
    for path in READINGS:
        rec = R._json(path)
        limits = R._json(R.BENCH / "cells" / f"{rec['workload']}.json")[
            "limits"]
        for r in rec[kind]:
            yield rec["workload"], r, limits


@pytest.mark.parametrize("kind", ["control_fp8", "fault_half_batch",
                                  "fault_unchanged"])
def test_the_chip_readings_of_the_control_and_faults_are_not_correct(kind):
    """The readings that ``calibrate.py`` took on the chip at the cell's
    own size go through the check that sets ``correct``; the data the
    reference fed matches, so the numbers alone must fail."""
    seen = 0
    for cell, r, limits in _readings(kind):
        assert not R.verdict(dict(r, data_tokens_off=0), limits, []), (cell,
                                                                       r)
        seen += 1
    assert seen >= 3


def test_the_chip_readings_of_the_program_are_correct():
    seen = 0
    for cell, r, limits in _readings("program"):
        assert R.verdict(r, limits, []), (cell, r)
        seen += 1
    assert seen >= 12
