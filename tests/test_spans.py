"""The program's own spans and named scopes, read back from a profile.

``run_training`` at the smoke size of ``test_launch_train.py`` (3 chunks of
2 steps) runs under ``jax.profiler.trace``; the host spans of its step loop
and of ``LocalExecutor`` are read from the ``.xplane.pb`` with their stats,
and the named scopes from the compiled step's HLO metadata.
"""
import re
import tempfile
import time
from collections import Counter, defaultdict
from pathlib import Path

import jax
import pytest

from repro.configs import get_config
from repro.core.dag import Resources, TaskSpec, WorkflowDAG
from repro.launch.train import run_training
from repro.runtime.orchestrator import LocalRuntime

CFG = get_config("qwen1.5-0.5b", smoke=True)
SMALL = dict(batch=4, seq=32, microbatch=2, lr=5e-3)
STEPS, CHUNK = 6, 2
PROGRAM = ("train.", "task.", "executor.", "cws.")


def _profiled(fn):
    """``fn()`` under the profiler; its result and the program's spans as
    ``(name, stats)`` in the order they started."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d, profiler_options=opts):
            out = fn()
        path = sorted(Path(d).rglob("*.xplane.pb"))[-1]
        pd = jax.profiler.ProfileData.from_file(str(path))
        evs = [(e.start_ns, e.name, dict(e.stats))
               for plane in pd.planes if plane.name.startswith("/host:")
               for line in plane.lines for e in line.events
               if e.name.startswith(PROGRAM)]
    return out, [(n, st) for _, n, st in sorted(evs, key=lambda x: x[0])]


@pytest.fixture(scope="module")
def traced():
    return _profiled(lambda: run_training(
        CFG, steps=STEPS, chunk=CHUNK, log=lambda s: None, **SMALL))


def _chunk_tasks(out):
    return sorted((t for t in out["dag"].tasks.values()
                   if t.name == "train_chunk"), key=lambda t: t.task_id)


@pytest.mark.parametrize("name", ["train.batch", "train.put", "train.step",
                                  "train.read"])
def test_each_step_span_appears_once_a_step(traced, name):
    _, spans = traced
    steps = [st["step"] for n, st in spans if n == name]
    assert steps == list(range(STEPS))


def test_the_log_span_appears_once_a_chunk_at_its_last_step(traced):
    _, spans = traced
    assert [st["step"] for n, st in spans if n == "train.log"] == \
        list(range(CHUNK - 1, STEPS, CHUNK))


@pytest.mark.parametrize("name", ["executor.start", "task.body",
                                  "executor.finish", "executor.launch"])
def test_each_chunk_task_has_its_executor_spans(traced, name):
    out, spans = traced
    ids = [t.task_id for t in _chunk_tasks(out)]
    assert len(ids) == STEPS // CHUNK
    got = Counter(st["task"] for n, st in spans if n == name)
    assert all(got[i] == 1 for i in ids), (got, ids)


def test_lock_spans_say_where(traced):
    _, spans = traced
    where = Counter(st["where"] for n, st in spans if n == "executor.lock")
    assert where["start"] >= STEPS // CHUNK
    assert where["finish"] >= STEPS // CHUNK
    assert set(where) <= {"start", "finish", "poll"}


def test_step_spans_nest_in_their_task_body(traced):
    """Between a task's ``task.body`` and the next, its chunk's steps."""
    out, spans = traced
    order = [(n, st.get("task", st.get("step"))) for n, st in spans
             if n in ("task.body", "train.step")]
    ids = [t.task_id for t in _chunk_tasks(out)]
    want = []
    for k, tid in enumerate(ids):
        want.append(("task.body", tid))
        want += [("train.step", s) for s in range(k * CHUNK,
                                                  (k + 1) * CHUNK)]
    assert order == want


def test_losses_are_those_of_a_run_without_the_profiler(traced):
    out, _ = traced
    plain = run_training(CFG, steps=STEPS, chunk=CHUNK, log=lambda s: None,
                         **SMALL)
    assert plain["losses"] == out["losses"]


def test_round_spans_equal_the_engines_round_count():
    rt = LocalRuntime(n_nodes=1)
    dag = WorkflowDAG("w")
    for i in range(4):
        dag.add_task(TaskSpec(task_id=f"w.t{i}", name="p", workflow_id="w",
                              fn=lambda: time.sleep(0.03) or {"x": 1},
                              resources=Resources(cpus=1.0,
                                                  mem_bytes=1 << 20)),
                     deps=(f"w.t{i - 1}",) if i else ())
    before = rt.cws.op_counts()["rounds"]
    try:
        _, spans = _profiled(lambda: rt.run(dag, timeout_s=60))
    finally:
        rt.shutdown()
    rounds = [st["forced"] for n, st in spans if n == "cws.round"]
    assert len(rounds) == rt.cws.op_counts()["rounds"] - before
    # the poll's forced rounds and the rounds the finishes ask for
    assert 1 in rounds and 0 in rounds
    assert sum(n == "task.body" for n, _ in spans) == 4


def test_a_span_costs_little_without_a_profiler():
    from jax.profiler import TraceAnnotation
    t = time.perf_counter()
    for s in range(2000):
        with TraceAnnotation("train.step", step=s):
            pass
    assert (time.perf_counter() - t) / 2000 < 1e-4


# ---------------------------------------------------------------------------
# named scopes in the compiled step
# ---------------------------------------------------------------------------
_OP = re.compile(r'^\s*(?:ROOT\s+)?%\S+ = .*?'
                 r'metadata=\{op_name="((?:[^"\\]|\\.)*)"', re.M)


def _scoped(hlo: str):
    """scope → the passes its instructions' ``op_name`` show it in:
    ``backward`` under ``transpose(``, else ``forward``."""
    seen = defaultdict(set)
    for op in _OP.findall(hlo):
        for w in set(re.split(r"[/()]", op)):
            seen[w].add("backward" if "transpose(" in op else "forward")
    return seen


def test_the_steps_layers_are_scoped_forward_and_backward(traced):
    out, _ = traced
    seen = _scoped(out["compiled"].as_text())
    for scope in ("embed", "attn_proj", "attn_core", "mlp", "head_loss"):
        assert seen[scope] == {"forward", "backward"}, scope
    assert seen["optimizer"] == {"forward"}


def test_the_ssd_scan_is_scoped_forward_and_backward():
    from repro.configs.base import ShapeConfig, TrainConfig
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.runtime.train import make_train_step
    model = build_model(get_config("mamba2-370m", smoke=True))
    step, state_sh, batch_sh, specs = make_train_step(
        model, TrainConfig(microbatch_per_device=2),
        ShapeConfig("t", 64, 4), make_host_mesh(), total_steps=4)
    hlo = jax.jit(step).lower(
        specs, model.input_specs(ShapeConfig("t", 64, 4))
    ).compile().as_text()
    assert _scoped(hlo)["ssd"] == {"forward", "backward"}
