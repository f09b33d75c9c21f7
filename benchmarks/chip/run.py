"""Chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell names a configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<traffic>.json``); ``cells/<cell>.json`` holds what is the cell's
own: the step time that sizes the run, the traced steps and the limits of
the correctness check. The configuration names its reference module
(``references/<name>.py``: the plain model, its FLOP count and its named
scopes). Every metric is read by ``metrics/<metric>.py``.

The entry the run drives is the program's ``repro.launch.train.run_training``
as it stands: CWSI → CWS → ``LocalExecutor`` → chunk tasks → the jitted step.
One call, sized in whole chunks from ``--seconds``, makes the weights from
the seed, compiles (from the persistent cache after a cell's first run) and
trains. Its first chunks carry the checked steps; the window is the rest, from
the first timed chunk task's start to the last one's end as the CWS recorded
them. ``--trace 1`` profiles a few steps at the window's start and reports the
per-layer metrics.

``correct`` compares what that call produced with ``reference.py`` once the
window has closed and the program's state is freed: the batches it fed (every
one, against ``tokens.py``), the loss of its first three steps, the first
step's gradient as the optimizer received it (read from the AdamW moment
after step 1) and the parameters' change over the three steps (read from the
f32 master copy before step 4), the last two by per-leaf norms and by fixed
samples of each leaf's entries. The last lines of standard error, and the
result line's last key, give each number beside its limit.

It exits non-zero, with no result line, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import flops  # noqa: E402
import numpy as np  # noqa: E402
import reference  # noqa: E402
import tokens  # noqa: E402


def local(name: str):
    """A module of this directory by its file (``trace`` would otherwise
    be the standard library's)."""
    return reference.module_from(BENCH / f"{name}.py", f"chipbench_{name}")


# ---------------------------------------------------------------------------
# the cell, from the data files
# ---------------------------------------------------------------------------
def _json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_spec(workload: str, root: Path = ROOT) -> Dict[str, Any]:
    """The cell's entry of ``BENCHMARK.json`` with its configuration, its
    reference module, traffic, cell file and the metrics it reports."""
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    w = cells[workload]
    conf_file = root / {c["name"]: c for c in bench["configs"]}[
        w["config"]]["file"]
    conf = _json(conf_file)

    def applies(m):
        return workload in m.get("workloads", [workload])
    return {
        "name": workload,
        "chips": w["chips"],
        "config": conf,
        "reference": reference.load(conf, source=conf_file),
        "traffic": _json(BENCH / "traffic" / f"{w['traffic']}.json"),
        "cell": _json(BENCH / "cells" / f"{workload}.json"),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def reader(metric: str):
    return reference.module_from(BENCH / "metrics" / f"{metric}.py",
                                 "metric_" + metric.replace(".", "_")).read


def plan(spec: Dict[str, Any], seconds: float) -> Dict[str, int]:
    """Chunks before the window (those holding the checked steps and the
    read after the last of them) and chunks in it (about ``seconds`` at the
    cell's step time), in whole chunks."""
    chunk = spec["traffic"]["chunk"]
    check = spec["cell"]["check_steps"]
    prefix = -(-(check + 1) // chunk)
    window = max(1, math.ceil(seconds / (chunk * spec["cell"]["step_s"])))
    return {"chunk": chunk, "prefix_chunks": prefix, "window_chunks": window,
            "steps": chunk * (prefix + window)}


def program_mismatches(conf: Dict[str, Any], pcfg: Any) -> List[str]:
    """Where the program's configuration departs from the one stated."""
    out = []
    for key, attr in conf["program_fields"].items():
        val = pcfg
        for part in attr.split("."):
            val = getattr(val, part)
        if val != conf[key]:
            out.append(f"{key}: program {val!r}, stated {conf[key]!r}")
    for attr, want in conf["program_fixed"].items():
        if getattr(pcfg, attr) != want:
            out.append(f"{attr}: program {getattr(pcfg, attr)!r}, "
                       f"stated {want!r}")
    return out


# ---------------------------------------------------------------------------
# what the harness reads from the running program
# ---------------------------------------------------------------------------
def _flat(tree) -> Dict[str, Any]:
    import jax
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(getattr(k, "key", k)) for k in path): v
            for path, v in leaves}


class Probe:
    """Sees each batch the program asks its pipeline for, and the shared
    training state between steps.

    When the batch of step 1 is asked for, the state holds step 0's result:
    the first AdamW moment, ``(1 - b1)`` times the clipped gradient, gives
    the gradient's per-leaf norms. When the batch of step ``check`` is asked
    for, the f32 master copy gives the change since the seed's parameters.
    """

    def __init__(self, spec: Dict[str, Any], seed: int,
                 trace_steps: Optional[range] = None,
                 trace_dir: Optional[str] = None) -> None:
        self.spec, self.seed = spec, seed
        self.check = spec["cell"]["check_steps"]
        self.trace_steps, self.trace_dir = trace_steps, trace_dir
        self.tracing = False
        self.shared = None
        self.fed: Dict[int, Dict[str, Any]] = {}
        self.asked: Dict[int, float] = {}
        self.refed = 0
        self.grad_norms: Optional[Dict[str, float]] = None
        self.change_norms: Optional[Dict[str, float]] = None
        self.grad_sample: Optional[Dict[str, Any]] = None
        self.change_sample: Optional[Dict[str, Any]] = None
        self.problems: List[str] = []

    def _state_step(self) -> int:
        return int(self.shared.state["opt"].step)

    def before(self, step: int) -> None:
        if self.shared is None:
            return                      # the batch that the compile traces
        self.asked.setdefault(step, time.monotonic())
        if self.trace_steps is not None:
            if step == self.trace_steps.start:
                self.start_trace()
            elif step == self.trace_steps.stop:
                self.stop_trace()

    def after(self, step: int, batch: Dict[str, Any]) -> None:
        if self.shared is None:
            return
        if step in self.fed:
            self.refed += 1
        self.fed[step] = batch
        if step not in (1, self.check):
            return
        at = self._state_step()
        if at != step:
            self.problems.append(f"state after {at} steps when the batch of "
                                 f"step {step} was built")
            return
        opt = self.shared.state["opt"]
        if step == 1:
            b1 = self.spec["traffic"]["optimizer"]["b1"]
            norms, smp = _norms_and_samples(_flat(opt.m))
            self.grad_norms = {k: float(v) / (1 - b1)
                               for k, v in norms.items()}
            self.grad_sample = {k: np.asarray(v) / (1 - b1)
                                for k, v in smp.items()}
        if step == self.check:
            self.change_norms, self.change_sample = \
                reference.change_from_seed(self.spec["reference"],
                                           self.spec["config"],
                                           _flat(opt.master), self.seed)

    def readings(self, losses: List[float]) -> Dict[str, Any]:
        """What ``compare`` takes of the program's side."""
        return {"losses": losses, "grad_norms": self.grad_norms,
                "grad_sample": self.grad_sample,
                "change_norms": self.change_norms,
                "change_sample": self.change_sample}

    def start_trace(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.tracing = True

    def stop_trace(self) -> None:
        if self.tracing:
            import jax
            jax.profiler.stop_trace()
            self.tracing = False


def _norms_and_samples(tree):
    import jax
    return jax.jit(lambda t: (reference.leaf_norms(t),
                              reference.leaf_samples(t)))(tree)


@contextlib.contextmanager
def instrumented(lt, probe: Probe):
    """Run ``lt.run_training`` with the probe behind its pipeline and its
    shared state; the program's own classes do the work."""
    import jax
    base_pipe, base_shared = lt.TokenPipeline, lt.SharedState

    class Pipe(base_pipe):
        def batch(self, step):
            probe.before(step)
            with jax.profiler.TraceAnnotation("bench.batch"):
                b = super().batch(step)
            probe.after(step, b)
            return b

    class Shared(base_shared):
        def __init__(self, state):
            super().__init__(state)
            probe.shared = self

    lt.TokenPipeline, lt.SharedState = Pipe, Shared
    try:
        yield
    finally:
        lt.TokenPipeline, lt.SharedState = base_pipe, base_shared
        probe.stop_trace()


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------
def leaf_gaps(prog: Optional[Dict[str, float]], ref: Dict[str, float],
              keys: List[str]) -> Dict[str, float]:
    """Per leaf, the gap between the program's and the reference's norm,
    over the reference's norm of that leaf or of the median leaf, whichever
    is larger; a leaf the program did not report reads infinite."""
    med = statistics.median(ref[k] for k in keys)
    return {k: (abs(prog[k] - ref[k]) / max(ref[k], med)
                if prog and k in prog else math.inf) for k in keys}


def worst_leaf_gap(prog: Optional[Dict[str, float]], ref: Dict[str, float],
                   keys: List[str]) -> float:
    return max(leaf_gaps(prog, ref, keys).values())


def leaf_diffs(prog: Optional[Dict[str, Any]], ref: Dict[str, Any],
               keys: List[str]) -> Dict[str, float]:
    """Per leaf, the norm of the difference of the sampled entries over the
    reference's norm of them, or of the median leaf's, whichever is
    larger."""
    rn = {k: float(np.linalg.norm(ref[k])) for k in keys}
    med = statistics.median(rn.values())
    return {k: (float(np.linalg.norm(np.asarray(prog[k]) - ref[k]))
                / max(rn[k], med) if prog and k in prog else math.inf)
            for k in keys}


def moved_leaves(ref_grad: Dict[str, float]) -> List[str]:
    """Leaves whose reference gradient is not nought to rounding: at least
    a thousandth of the median leaf's. The others (a key's bias under
    softmax) move under Adam by round-off alone."""
    med = statistics.median(ref_grad.values())
    return sorted(k for k, v in ref_grad.items() if v >= 1e-3 * med)


def compare(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """The numbers compared, each a gap between the program and the
    reference."""
    lp, lr = prog["losses"], ref["losses"]
    loss = (max(abs(a - b) / abs(b) for a, b in zip(lp, lr))
            if len(lp) == len(lr) and all(map(math.isfinite, lp))
            else math.inf)
    keys = sorted(ref["grad_norms"])
    moved = moved_leaves(ref["grad_norms"])
    return {
        "loss_gap": loss,
        "grad_gap": worst_leaf_gap(prog["grad_norms"], ref["grad_norms"],
                                   keys),
        "update_gap": worst_leaf_gap(prog["change_norms"],
                                     ref["change_norms"], moved),
        "grad_diff": max(leaf_diffs(prog["grad_sample"], ref["grad_sample"],
                                    keys).values()),
        "update_diff": max(leaf_diffs(prog["change_sample"],
                                      ref["change_sample"], moved).values()),
    }


def verdict(gaps: Dict[str, float], limits: Dict[str, float],
            problems: List[str]) -> bool:
    """``correct``: no problem seen, and every number within its limit (a
    reading that never came, infinite or NaN, is not)."""
    return not problems and all(gaps[k] <= limits[k] for k in limits)


def data_mismatch(spec: Dict[str, Any], seed: int, probe: Probe,
                  steps: int) -> int:
    """Tokens and labels fed for steps ``0..steps-1`` that differ from the
    benchmark's own generator; a step never fed counts all of its."""
    own = tokens.traffic_for(spec["config"], spec["traffic"], seed)
    off = 0
    for s in range(steps):
        mine = own.batch(s)
        got = probe.fed.get(s)
        for k in ("tokens", "labels"):
            if got is None or got[k].shape != mine[k].shape:
                off += mine[k].size
            else:
                off += int((got[k] != mine[k]).sum())
    return off


def reference_run(spec: Dict[str, Any], seed: int, total_steps: int,
                  **kw) -> Dict[str, Any]:
    cfg, trf = spec["config"], spec["traffic"]
    own = tokens.traffic_for(cfg, trf, seed)
    batches = [own.batch(s) for s in range(spec["cell"]["check_steps"])]
    opt = dict(trf["optimizer"], lr=trf["lr"])
    kw.setdefault("rows", spec["cell"]["ref_rows"])
    return reference.train_first_steps(spec["reference"], cfg, opt, batches,
                                       seed, total_steps, **kw)


def check(spec: Dict[str, Any], seed: int, steps: int, out: Dict[str, Any],
          probe: Probe) -> Dict[str, Any]:
    """Free the program's state, follow its first steps with the
    reference, and compare: the numbers (``gaps``) with both sides'
    readings."""
    prog = probe.readings(out["losses"][:spec["cell"]["check_steps"]])
    off = data_mismatch(spec, seed, probe, steps) + probe.refed
    out.clear()
    probe.shared = None
    probe.fed.clear()
    gc.collect()
    ref = reference_run(spec, seed, steps)
    gaps = compare(prog, ref)
    gaps["data_tokens_off"] = off
    return {"gaps": gaps, "prog": prog, "ref": ref}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def program_config(spec: Dict[str, Any]):
    """The program's configuration of the cell's model (a test may hand
    the spec a smaller one under ``program_cfg``), with each field that the
    configuration file names under ``program_set`` given its stated value:
    where the program has the option, it runs the configuration as stated."""
    conf = spec["config"]
    base = spec.get("program_cfg")
    if base is None:
        from repro.configs import get_config
        base = get_config(conf["registry"])
    return dataclasses.replace(base, **{conf["program_fields"][k]: conf[k]
                                        for k in conf.get("program_set", [])})


def train(spec: Dict[str, Any], seed: int, steps: int, devices,
          probe: Probe, log=lambda line: None) -> Dict[str, Any]:
    """The program's own training entry, instrumented; ``log`` gets the
    lines it prints."""
    import repro.launch.train as lt
    from repro.launch.mesh import make_host_mesh
    trf = spec["traffic"]
    with instrumented(lt, probe):
        return lt.run_training(
            program_config(spec), steps=steps,
            chunk=trf["chunk"], batch=trf["batch"], seq=trf["seq"],
            microbatch=trf["microbatch"], lr=trf["lr"], seed=seed,
            mesh=make_host_mesh(devices), log=log)


def chunk_tasks(out: Dict[str, Any]) -> list:
    return sorted((t for t in out["dag"].tasks.values()
                   if t.name == "train_chunk"), key=lambda t: t.task_id)


def window_record(spec, pl, out, probe, t_start, traced: Optional[range],
                  chips: int, device_kind: str) -> Dict[str, Any]:
    """What the metric readers read: the window from the CWS's records of
    the chunk tasks, and the host clock's offset to the executor's; the
    configuration, the traffic's ``batch`` and ``seq`` and the device's
    peaks, from which a reader computes a kernel's roofline share."""
    tasks = chunk_tasks(out)
    chunk, w0 = pl["chunk"], pl["prefix_chunks"]
    # the executor stamps a task's start just before its body asks for the
    # batch of the chunk's first step: the smallest difference is the offset
    offset = min(probe.asked[k * chunk] - t.start_time
                 for k, t in enumerate(tasks) if k * chunk in probe.asked)
    win = tasks[w0:]
    step_s = out["step_seconds"]
    host_chunks = [k for k in range(w0, len(tasks))
                   if traced is None or not (
                       k * chunk < traced.stop + 1
                       and (k + 1) * chunk > traced.start)]
    trf = spec["traffic"]
    peaks = spec.get("peaks") or _json(BENCH / "peaks.json")
    if device_kind not in peaks:
        raise SystemExit(f"no peaks for device kind {device_kind!r} "
                         f"in peaks.json")
    return {
        "setup_s": win[0].start_time + offset - t_start,
        "window_s": win[-1].end_time - win[0].start_time,
        "window_steps": len(win) * chunk,
        "tokens_per_step": trf["batch"] * trf["seq"],
        "gaps_s": [tasks[k].start_time - tasks[k - 1].end_time
                   for k in host_chunks],
        "chunk_s": [tasks[k].end_time - tasks[k].start_time
                    for k in host_chunks],
        "chunk_step_s": [sum(step_s[k * chunk:(k + 1) * chunk])
                         for k in host_chunks],
        "chunk_steps": len(host_chunks) * chunk,
        "flops_per_token": flops.train_flops_per_token(spec["config"],
                                                       trf["seq"]),
        "peaks": peaks[device_kind],
        "config": spec["config"],
        "batch": trf["batch"],
        "seq": trf["seq"],
        "chips": chips,
        "trace": None,
    }


def run(spec: Dict[str, Any], seed: int, seconds: float, trace: bool,
        devices, t_start: float) -> Dict[str, Any]:
    pl = plan(spec, seconds)
    problems = program_mismatches(spec["config"], program_config(spec))
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    traced = None
    if trace:
        first = pl["prefix_chunks"] * pl["chunk"]
        traced = range(first, min(first + spec["cell"]["trace_steps"],
                                  pl["steps"]))
    probe = Probe(spec, seed, traced, trace_dir)
    out = train(spec, seed, pl["steps"], devices, probe)
    problems += probe.problems

    tasks = chunk_tasks(out)
    runs = out["chunk_runs"]
    failed = sum(t.state.value != "SUCCEEDED" or t.attempt != 0 or n != 1
                 for t, n in zip(tasks, runs))
    failed += max(0, len(runs) - len(tasks))
    rec = window_record(spec, pl, out, probe, t_start, traced, len(devices),
                        devices[0].device_kind)
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devices)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    breakdown = None
    if trace:
        files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
        red = None
        if files:
            tr, sp = local("trace"), local("spans")
            prof = tr.profile(str(files[-1]))      # parsed once, read twice
            red = tr.reduce(tr.load(prof))
            rec["scopes"] = sp.scope_map(
                out["compiled"].as_text(),
                sp.SCOPES + spec["reference"].SCOPES)
            rec["spans"] = sp.reduce(*sp.load(prof), rec["scopes"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        rec["trace"] = red
        if red:
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            breakdown = red["breakdown"]

    step_s = out["step_seconds"]
    # the window has closed and the peak is read
    gaps = check(spec, seed, pl["steps"], out, probe)["gaps"]
    limits = spec["cell"]["limits"]
    correct = verdict(gaps, limits, problems)

    metrics = {}
    for m in spec["per_layer" if trace else "end_to_end"]:
        v = reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": len(tasks),
              "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    # a reading that never came (inf) is printed as null, and fails
    result["checks"] = {k: {"value": gaps[k] if math.isfinite(gaps[k])
                            else None, "limit": limits[k]} for k in limits}
    w0 = pl["prefix_chunks"] * pl["chunk"]
    steps = step_s[w0:]
    slowest = w0 + max(range(len(steps)), key=steps.__getitem__)
    window = {"seconds": rec["window_s"], "steps": rec["window_steps"],
              "step_s_median": statistics.median(steps),
              "step_s_max": max(steps), "slowest_step": slowest,
              # the wall clock when its batch was asked for
              "slowest_step_at": probe.asked.get(slowest, math.nan)
              + time.time() - time.monotonic(),
              "host_ms_per_step": reader("train_loop.host_ms_per_step")(rec)}
    return {"result": result, "problems": problems, "window": window}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_spec(args.workload)

    import jax
    from repro.launch.train import enable_compile_cache
    enable_compile_cache()
    # every program of the run, small ones included, comes from the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < spec["chips"]:
        print(f"run.py: needs {spec['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr, flush=True)
        return 2
    out = run(spec, args.seed, args.seconds, bool(args.trace),
              devices[:spec["chips"]], T_START)
    res = out["result"]
    print(f"window: {out['window']}", file=sys.stderr)
    for p in out["problems"]:
        print(f"problem: {p}", file=sys.stderr)
    for k, c in res["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
