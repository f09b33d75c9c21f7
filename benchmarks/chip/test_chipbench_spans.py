"""spans.py on planes built by hand, on a trace recorded on the chip
(``fixtures/scoped.xplane.pb`` and ``scoped.hlo.txt``, made by
``fixtures/record_scoped.py``), and the readers of the metrics that read
it."""
from pathlib import Path

import pytest

import run as R

spans = R.local("spans")
trace = R.local("trace")
HERE = Path(__file__).resolve().parent
SCOPED = HERE / "fixtures" / "scoped.xplane.pb"
SCOPED_HLO = HERE / "fixtures" / "scoped.hlo.txt"


def _host(*threads):
    """A host plane: one line per thread, named alike as the profiler
    names Python threads."""
    return {"/host:CPU": {("python3" if i == 0 else f"python3#{i + 1}"): t
                          for i, t in enumerate(threads)}}


def test_spans_cut_by_the_traces_edges_are_left_out():
    planes = _host([("task.body", -0.5, 1.0, {"task": "a"}),
                    ("task.body", 1.2, 2.0, {"task": "b"}),
                    ("task.body", 2.3, 3.0, {"task": "c"}),
                    ("task.body", 3.4, 4.5, {"task": "d"}),
                    ("bench.batch", 1.3, 1.4, {})])
    kept = spans.host_spans(planes, (0.0, 4.0))
    assert [sp[3]["task"] for sp in kept[0]] == ["b", "c"]
    r = spans.reduce(planes, (0.0, 4.0))
    assert r["handoff_s"] == pytest.approx([0.3])
    # with no bounds every span counts
    assert len(spans.host_spans(planes)[0]) == 4


def test_handoff_runs_between_consecutive_task_bodies_across_threads():
    # the next task's body runs on the other pool thread
    a = [("task.body", 0.0, 1.0, {"task": "t0"}),
         ("executor.finish", 1.0, 1.0004, {"task": "t0"}),
         ("task.body", 2.002, 3.0, {"task": "t2"})]
    b = [("executor.start", 1.0005, 1.0009, {"task": "t1"}),
         ("task.body", 1.001, 2.0, {"task": "t1"})]
    r = spans.reduce(_host(a, b))
    assert r["handoff_s"] == pytest.approx([0.001, 0.002])


def test_rounds_per_task_counts_rounds_between_the_first_and_last_body():
    bodies = [("task.body", k * 1.0, k * 1.0 + 0.9, {"task": f"t{k}"})
              for k in range(4)]
    # a forced round every 0.1 s on the polling thread, from before the
    # first body to after the last, and one asked for at each finish
    polls = [("cws.round", -0.35 + 0.1 * i, -0.35 + 0.1 * i + 0.001,
              {"forced": 1}) for i in range(45)]
    asked = [("cws.round", k + 0.95, k + 0.951, {"forced": 0})
             for k in range(4)]
    r = spans.reduce(_host(bodies + asked, polls))
    assert r["periods"] == 3
    # [0, 3): 30 polls, which follow the task's length and do not count,
    # and 3 asked-for rounds
    assert r["asked_rounds"] == 3
    assert R.reader("cws.rounds_per_task")({"spans": r}) == pytest.approx(1)
    # a second round asked for at each finish shows, whatever the polls
    twice = [(n, s + 0.01, e + 0.01, st) for n, s, e, st in asked]
    r = spans.reduce(_host(bodies + asked + twice, polls))
    assert R.reader("cws.rounds_per_task")({"spans": r}) == pytest.approx(2)


def test_input_time_sums_batch_and_put_of_each_step():
    th = []
    for s in range(3):
        t = s * 1.0
        th += [("train.batch", t, t + 0.004 + 0.001 * s, {"step": s}),
               ("train.put", t + 0.005, t + 0.006, {"step": s}),
               ("train.step", t + 0.006, t + 0.9, {"step": s})]
    th.append(("train.batch", 3.0, 3.004, {"step": 3}))   # no put: left out
    r = spans.reduce(_host(th))
    assert r["input_s"] == pytest.approx([0.005, 0.006, 0.007])
    assert R.reader("train_loop.input_ms")({"spans": r}) == \
        pytest.approx(6.0)


def _device(runs, ops):
    return {"/device:TPU:0": {trace.MODULES: runs, trace.OPS: ops}}


HLO = '''HloModule jit_step
ENTRY %main {
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(step)/jvp(head_loss)/exp" source_file="m.py" source_line=3}
  %fusion.2 = bf16[4,8]{1,0} fusion(%p), metadata={op_name="jit(step)/jvp()/while/body/closed_call/attn_core/dot_general"}
  %fusion.3 = bf16[4,8]{1,0} fusion(%p), metadata={op_name="jit(step)/transpose(jvp())/while/body/checkpoint/rematted_computation/mlp/mul"}
  ROOT %while.4 = (s32[]) while(%t), body=%body, metadata={op_name="jit(step)/while"}
  %copy.5 = f32[4]{0} copy(%x), metadata={op_name="batch[\\'tokens\\']"}
  %add.6 = f32[4]{0} add(%x, %y)
}
'''


def test_scope_map_reads_the_innermost_scope_of_each_instruction():
    assert spans.scope_map(HLO) == {"%fusion.1": "head_loss",
                                    "%fusion.2": "attn_core",
                                    "%fusion.3": "mlp"}


def test_scope_map_counts_a_reference_modules_scope():
    """A scope that only one kind of model has (``ssd``) is counted where
    its configuration's module names it, and is unscoped elsewhere."""
    import reference
    line = ('  %fusion.7 = f32[4]{0} fusion(%p), metadata={op_name='
            '"jit(step)/while/body/checkpoint/ssd/cumsum"}\n')
    hlo = HLO.replace("ENTRY %main {\n", "ENTRY %main {\n" + line)
    ssm = reference.load({"reference": "ssm"})
    assert "%fusion.7" not in spans.scope_map(hlo)
    got = spans.scope_map(hlo, spans.SCOPES + ssm.SCOPES)
    assert got == dict(spans.scope_map(HLO), **{"%fusion.7": "ssd"})
    runs = [("jit_step(1)", 0.0, 1.0)]
    ops = [("%fusion.7 = f32[4]{0} fusion(%p)", 0.1, 0.4),
           ("%fusion.3 = bf16[4,8]{1,0} fusion(%p)", 0.4, 0.6)]
    assert spans.reduce(_device(runs, ops), scopes=got)["scope_s"] == \
        pytest.approx({"ssd": 0.3, "mlp": 0.2})


def test_scope_self_times_sum_with_the_unscoped_to_the_step():
    runs = [("jit_step(1)", 0.0, 1.0), ("jit_step(1)", 2.0, 3.0),
            ("jit_small(2)", 3.5, 3.6)]
    ops = []
    for t in (0.0, 2.0):
        ops += [("%while.4 = (s32[]) while(%t)", t, t + 0.6),
                ("%fusion.2 = bf16[4,8]{1,0} fusion(%p)", t + 0.1, t + 0.3),
                ("%fusion.3 = bf16[4,8]{1,0} fusion(%p)", t + 0.3, t + 0.5),
                ("%fusion.1 = f32[4]{0} fusion(%p)", t + 0.6, t + 0.9),
                ("%copy.5 = f32[4]{0} copy(%x)", t + 0.9, t + 1.0)]
    ops.append(("%other.1 = f32[4]{0} add(%x)", 3.5, 3.6))   # not the step
    r = spans.reduce(_device(runs, ops), scopes=spans.scope_map(HLO))
    sc = r["scope_s"]
    assert sc == pytest.approx({"attn_core": 0.2, "mlp": 0.2,
                                "head_loss": 0.3, spans.UNSCOPED: 0.3})
    assert sum(sc.values()) == pytest.approx(
        trace.reduce(_device(runs, ops))["step_device_s"][0])
    rec = {"spans": r}
    assert R.reader("train_step.attn_core_ms")(rec) == pytest.approx(200)
    assert R.reader("train_step.head_loss_ms")(rec) == pytest.approx(300)
    # a scope no op holds reads nothing, not nought
    assert R.reader("train_step.optimizer_ms")(rec) is None
    # without a scope map (a program with no scopes) nothing is read
    assert spans.reduce(_device(runs, ops))["scope_s"] is None


def test_idle_time_goes_to_the_innermost_program_span():
    runs = [("jit_step(1)", 0.0, 1.0), ("jit_step(1)", 1.2, 2.2),
            ("jit_step(1)", 2.5, 3.5)]
    ops = [("%a = f32[2]{0} add(x)", s, e) for _, s, e in runs]
    worker = [("task.body", -0.1, 1.1, {"task": "t0"}),
              ("train.step", 0.0, 1.01, {"step": 0}),
              ("train.read", 1.01, 1.02, {"step": 0}),
              ("executor.finish", 1.1, 1.12, {"task": "t0"}),
              ("cws.round", 1.105, 1.11, {"forced": 0}),
              ("task.body", 1.15, 2.48, {"task": "t1"}),
              ("train.batch", 1.15, 1.19, {"step": 1}),
              ("train.step", 1.19, 2.2, {"step": 1}),
              ("train.batch", 2.2, 2.45, {"step": 2})]
    harness = [("bench.batch", 2.2, 2.45, {})]
    poller = [("cws.round", 2.41, 2.42, {"forced": 1})]   # not dispatching
    planes = dict(_device(runs, ops), **_host(worker + harness, poller))
    idle = spans.reduce(planes)["idle_s"]
    assert idle == pytest.approx({
        "train.step": 0.01 + 0.01, "train.read": 0.01,
        "task.body": 0.08 + 0.03, "executor.finish": 0.005 + 0.01,
        "cws.round": 0.005, "train.batch": 0.04 + 0.25,
        spans.NONE: 0.03 + 0.02})
    assert sum(idle.values()) == pytest.approx(0.5)


def test_a_trace_with_no_program_spans_reads_nothing():
    r = spans.reduce(_host([("bench.batch", 0.0, 1.0, {})]))
    rec = {"spans": r}
    for m in ("train_loop.input_ms", "executor.handoff_ms",
              "cws.rounds_per_task"):
        assert R.reader(m)(rec) is None


@pytest.mark.parametrize("metric", [
    "train_loop.input_ms", "executor.handoff_ms", "cws.rounds_per_task",
    "train_step.attn_core_ms", "train_step.mlp_ms",
    "train_step.head_loss_ms", "train_step.optimizer_ms"])
def test_each_reader_on_a_record_built_by_hand(metric):
    rec = {"spans": {"input_s": [0.004, 0.005, 0.0047],
                     "handoff_s": [0.0006, 0.0005, 0.0009],
                     "asked_rounds": 34, "periods": 10, "idle_s": {},
                     "scope_s": {"attn_core": 0.1, "mlp": 0.06,
                                 "head_loss": 0.05, "optimizer": 0.02,
                                 spans.UNSCOPED: 0.01}}}
    want = {"train_loop.input_ms": 4.7, "executor.handoff_ms": 0.6,
            "cws.rounds_per_task": 3.4, "train_step.attn_core_ms": 100.0,
            "train_step.mlp_ms": 60.0, "train_step.head_loss_ms": 50.0,
            "train_step.optimizer_ms": 20.0}[metric]
    assert R.reader(metric)(rec) == pytest.approx(want)
    # the record of a run without a trace, or of a program with no spans
    assert R.reader(metric)({"trace": None}) is None


def test_the_chip_trace_with_scopes():
    planes, bounds = spans.load(trace.profile(str(SCOPED)))
    r = spans.reduce(planes, bounds,
                     spans.scope_map(SCOPED_HLO.read_text()))
    red = trace.reduce_file(str(SCOPED))
    assert red["steps"] == 4
    # the kwargs come back as the profiler writes them
    steps = [sp[3]["step"] for th in spans.host_spans(planes, bounds)
             for sp in th if sp[0] == "train.step"]
    assert steps == [0, 1, 2, 3]
    sc = r["scope_s"]
    assert sc["attn_core"] > 0 and sc["mlp"] > 0
    # the two scopes hold nearly all of the program, and the scope sums
    # with the unscoped time make the program's device time
    step = sorted(red["step_device_s"])[1:3]
    assert sc["attn_core"] + sc["mlp"] > 0.9 * sum(sc.values())
    assert sum(sc.values()) == pytest.approx(sum(step) / 2, rel=0.05)
    # the host pauses between executions fall under the program's spans
    idle = r["idle_s"]
    assert idle["train.batch"] > 0.9 * 3 * 0.005
    assert idle.get(spans.NONE, 0.0) < 0.1 * sum(idle.values())
    assert sum(idle.values()) == pytest.approx(
        red["window_s"] - red["busy_s"])


def test_a_traced_run_hands_its_trace_to_the_span_readers():
    """``run.py`` reduces a traced run's profile with the step's scope map:
    at smoke size on the CPU the program's host spans read (the CPU trace
    has no TPU plane, so the device-time metrics read nothing)."""
    import time

    import jax
    from smoke import smoke_spec
    spec = smoke_spec("qwen1.5-0.5b")
    spec["per_layer"] = R.load_spec("qwen1.5-0.5b.train-chunk1")["per_layer"]
    spec["cell"]["trace_steps"] = 6
    out = R.run(spec, 2147483801, 0.5, True, jax.devices()[:1],
                time.monotonic())
    got = out["result"]["metrics"]
    assert out["result"]["correct"], out["result"]["checks"]
    for m in ("train_loop.input_ms", "executor.handoff_ms",
              "cws.rounds_per_task"):
        assert got[m]["value"] > 0, m
    assert not any(m.startswith("train_step.") for m in got)
