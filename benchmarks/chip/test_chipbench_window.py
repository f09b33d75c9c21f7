"""The window, the task-start percentiles and the metric readers, on
synthetic CWS task records."""
import statistics
from types import SimpleNamespace

import pytest

import reference
import run as R

CONFIG = {"reference": "dense", "num_hidden_layers": 1, "hidden_size": 4,
          "num_attention_heads": 1, "num_key_value_heads": 1, "head_dim": 4,
          "intermediate_size": 4, "vocab_size": 8}


def _spec(chunk=1, check=3, step_s=0.5, trace_steps=4):
    return {"traffic": {"chunk": chunk, "batch": 8, "seq": 1024},
            "cell": {"check_steps": check, "step_s": step_s,
                     "trace_steps": trace_steps},
            "config": CONFIG, "reference": reference.load(CONFIG),
            "peaks": {"chip": {"bf16_flops_per_s": 1e12}}}


def _run(n_tasks, chunk=1, step=0.3, gap=lambda k: 0.001 * (k % 7),
         offset=100.0):
    """Chunk tasks back to back on the executor's clock: each ``chunk``
    steps of ``step`` seconds plus 0.01 s of host work, separated by
    ``gap(k)``; the host clock runs ``offset`` ahead."""
    tasks, asked, t = [], {}, 0.0
    for k in range(n_tasks):
        t += gap(k) if k else 0.0
        start = t
        asked[k * chunk] = start + offset + 2e-4
        t += chunk * step + 0.01
        tasks.append(SimpleNamespace(task_id=f"j.chunk.{k:04d}",
                                     name="train_chunk", start_time=start,
                                     end_time=t))
    dag = SimpleNamespace(tasks={x.task_id: x for x in tasks})
    out = {"dag": dag, "step_seconds": [step] * (n_tasks * chunk)}
    return out, SimpleNamespace(asked=asked), tasks


def test_plan_counts_whole_chunks():
    assert R.plan(_spec(chunk=1, step_s=0.5), 40) == {
        "chunk": 1, "prefix_chunks": 4, "window_chunks": 80, "steps": 84}
    assert R.plan(_spec(chunk=16, step_s=0.8), 40) == {
        "chunk": 16, "prefix_chunks": 1, "window_chunks": 4, "steps": 80}


def test_window_runs_from_the_first_timed_start_to_the_last_end():
    spec = _spec()
    pl = R.plan(spec, 30)
    out, probe, tasks = _run(pl["steps"])
    rec = R.window_record(spec, pl, out, probe, t_start=90.0, traced=None,
                          chips=1, device_kind="chip")
    w0 = pl["prefix_chunks"]
    assert rec["window_s"] == pytest.approx(tasks[-1].end_time
                                            - tasks[w0].start_time)
    assert rec["window_steps"] == pl["window_chunks"]
    # the host clock's offset comes from the earliest batch request
    assert rec["setup_s"] == pytest.approx(tasks[w0].start_time + 100.0
                                           - 90.0, abs=3e-4)
    want = [tasks[k].start_time - tasks[k - 1].end_time
            for k in range(w0, len(tasks))]
    assert rec["gaps_s"] == pytest.approx(want)
    tok = R.reader("train_tokens_per_s")(rec)
    assert tok == pytest.approx(pl["window_chunks"] * 8192 / rec["window_s"])


def test_p90_and_median_of_task_starts():
    spec = _spec()
    pl = R.plan(spec, 60)
    out, probe, _ = _run(pl["steps"], gap=lambda k: 0.001 * (k % 10))
    rec = R.window_record(spec, pl, out, probe, 0.0, None, 1, "chip")
    assert len(rec["gaps_s"]) == pl["window_chunks"] >= 100
    p90 = R.reader("task_start_p90_ms")(rec)
    assert p90 == pytest.approx(
        statistics.quantiles(rec["gaps_s"], n=10)[8] * 1e3)
    assert 8.0 <= p90 <= 9.0
    assert R.reader("cws.handoff_ms")(rec) == pytest.approx(
        statistics.median(rec["gaps_s"]) * 1e3)
    # fewer than ten samples beyond the 90th percentile: no reading
    rec["gaps_s"] = rec["gaps_s"][:99]
    assert R.reader("task_start_p90_ms")(rec) is None


def test_host_time_per_step_and_traced_chunks_left_out():
    spec = _spec(chunk=2)
    pl = R.plan(spec, 12)
    out, probe, tasks = _run(pl["steps"] // 2, chunk=2)
    traced = range(pl["prefix_chunks"] * 2, pl["prefix_chunks"] * 2 + 4)
    rec = R.window_record(spec, pl, out, probe, 0.0, traced, 1, "chip")
    # the traced steps and the step whose batch stops the trace are in
    # the first three timed chunks
    assert len(rec["chunk_s"]) == pl["window_chunks"] - 3
    assert R.reader("train_loop.host_ms_per_step")(rec) == pytest.approx(5.0)


def test_trace_readers():
    rec = {"trace": {"steps": 4, "window_s": 2.0, "busy_s": 1.5,
                     "step_device_s": [0.4, 0.5, 0.45, 0.5]},
           "flops_per_token": 1e6, "tokens_per_step": 1000, "chips": 1,
           "peaks": {"bf16_flops_per_s": 1e10}}
    assert R.reader("train_step.device_ms")(rec) == pytest.approx(475.0)
    assert R.reader("device.idle_pct")(rec) == pytest.approx(25.0)
    assert R.reader("train_step.mfu")(rec) == pytest.approx(
        100 * 4 * 1000 * 1e6 / 2.0 / 1e10)
    rec["trace"] = None
    for m in ("train_step.device_ms", "device.idle_pct", "train_step.mfu"):
        assert R.reader(m)(rec) is None


def test_unknown_device_kind_is_an_error():
    spec = _spec()
    spec.pop("peaks")
    pl = R.plan(spec, 5)
    out, probe, _ = _run(pl["steps"])
    with pytest.raises(SystemExit):
        R.window_record(spec, pl, out, probe, 0.0, None, 1, "TPU v9 none")
