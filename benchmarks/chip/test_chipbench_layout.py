"""The benchmark's data files hang together, and the harness refuses to
run without a TPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import run as R

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files_name_known_configurations(w):
    from repro.configs import ARCHS
    cell = json.loads((HERE / "cells" / f"{w['name']}.json").read_text())
    assert (cell["config"], cell["traffic"]) == (w["config"], w["traffic"])
    spec = R.load_spec(w["name"])
    assert spec["config"]["registry"] in ARCHS
    assert set(cell["limits"]) == {"data_tokens_off", "loss_gap",
                                   "grad_gap", "update_gap", "grad_diff",
                                   "update_diff"}
    # the program runs the configuration as its file states it
    assert R.program_mismatches(spec["config"], R.program_config(spec)) == []
    assert spec["traffic"]["batch"] % spec["traffic"]["microbatch"] == 0


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files_state_their_source_and_cuts(c):
    conf = json.loads((ROOT / c["file"]).read_text())
    assert conf["source"].startswith(c["source"])
    assert conf["reduced"] == c["reduced"]
    assert set(conf["reduced"]) <= set(conf.get("published", {}))


@pytest.mark.parametrize("m", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_has_a_reader(m):
    assert callable(R.reader(m["name"]))
    for w in m.get("workloads", []):
        assert w in {x["name"] for x in BENCH["workloads"]}


def test_peaks_name_their_source():
    peaks = json.loads((HERE / "peaks.json").read_text())
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert "Google Cloud" in peaks["TPU v5 lite"]["source"]


def _bench(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "2147483711",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_the_cpu():
    p = _bench(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_run_fails_beside_nothing_but_its_own_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert "repro" in p.stderr               # the program is not there
    assert p.stdout.strip() == ""
