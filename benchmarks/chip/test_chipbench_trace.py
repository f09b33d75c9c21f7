"""trace.py on a trace recorded on the chip (``fixtures/small.xplane.pb``,
made by ``fixtures/record.py``) and on planes built by hand."""
from pathlib import Path

import pytest

import run as R

trace = R.local("trace")
FIXTURE = Path(__file__).resolve().parent / "fixtures" / "small.xplane.pb"


def test_union_and_clip():
    assert trace.union([(3, 4), (0, 1), (0.5, 2), (4, 5)]) == [(0, 2), (3, 5)]
    assert trace.clip([(0, 2), (3, 5)], 1, 4) == [(1, 2), (3, 4)]


def test_reduction_of_planes_built_by_hand():
    planes = {
        "/device:TPU:0": {
            "XLA Modules": [("jit_step(1)", 0.0, 1.0),
                            ("jit_step(1)", 1.5, 2.5),
                            ("jit_small(2)", 2.6, 2.7)],
            "XLA Ops": [("%a = f32[2]{0} add(x)", 0.0, 0.6),
                        ("%b = f32[2]{0} mul(x)", 0.6, 1.0),
                        ("%a = f32[2]{0} add(x)", 1.5, 2.4)],
        },
        "/host:CPU": {"python3": [("bench.batch", 1.0, 1.45),
                                  ("outer", 0.0, 3.0)]},
    }
    r = trace.reduce(planes)
    assert r["step_module"] == "jit_step(1)" and r["steps"] == 2
    assert r["window_s"] == pytest.approx(2.5)
    assert r["busy_s"] == pytest.approx(1.9)
    assert r["step_device_s"] == pytest.approx([1.0, 1.0])
    top = r["breakdown"]["device_ops"]
    assert top[0][0] == "%a f32[2]" and top[0][1] == pytest.approx(1.5)
    # the longest idle gap is labelled by the host event that matches it,
    # not by the one that encloses everything
    name, secs = r["breakdown"]["idle_gaps"][0]
    assert name == "bench.batch" and secs == pytest.approx(0.5)


def test_nested_ops_count_their_own_time():
    evs = [("%while", 0.0, 10.0), ("%a", 1.0, 3.0), ("%b", 4.0, 5.0),
           ("%c", 11.0, 12.0)]
    assert dict(trace.self_times(evs)) == pytest.approx(
        {"%while": 7.0, "%a": 2.0, "%b": 1.0, "%c": 1.0})


def test_no_device_plane_reads_nothing():
    assert trace.reduce({"/host:CPU": {"python3": []}}) is None


def test_the_chip_trace():
    r = trace.reduce_file(str(FIXTURE))
    assert r["chips"] == 1 and r["steps"] == 5
    assert r["step_module"].startswith("jit_")
    assert 0 < r["busy_s"] < r["window_s"]
    # four host pauses of 10 ms between the five executions
    gaps = r["breakdown"]["idle_gaps"][:4]
    assert [g[0] for g in gaps] == ["bench.pause"] * 4
    assert all(0.009 < g[1] < 0.05 for g in gaps)
    assert r["window_s"] == pytest.approx(
        sum(r["step_device_s"]) + sum(g[1] for g in
                                      r["breakdown"]["idle_gaps"]), rel=0.05)
