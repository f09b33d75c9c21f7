"""Reduction of a profiler trace to the program's own spans and scopes.

    python benchmarks/chip/spans.py <trace.xplane.pb> [<step.hlo.txt>
        [<configuration.json>]]

Host spans are the program's ``jax.profiler.TraceAnnotation`` events, on
the ``/host:`` planes, each with its stats (``step``, ``task``, ``where``,
``forced``) as the profiler writes them: ints as ints, strings as strings.
A span cut by the trace's start or stop is left out. The reduction gives:

- ``input_s``: per traced step, ``train.batch`` + ``train.put``;
- ``handoff_s``: from the end of one ``task.body`` to the start of the
  next, for each consecutive pair (lock waits and the thread hop
  included);
- ``asked_rounds`` and ``periods``: the ``cws.round`` spans with
  ``forced`` 0 (the rounds the scheduler's own events ask for, not the
  driver loop's timed poll) that start between the first and the last
  ``task.body`` start, and the task periods there;
- ``idle_s``: the device's idle time in the step window (as ``trace.py``
  takes it) by the innermost program span open on a dispatching thread
  (one that holds ``train.step`` spans) at that instant, ``"(none)"`` where
  none is;
- ``scope_s``: per execution of the step program, the device self time of
  its operations by the named scope in their HLO ``op_name``
  (``scope_map``: the shared ``SCOPES`` and those of the configuration's
  reference module), ``"(unscoped)"`` for the rest, medians over the
  executions; ``None`` without a scope map.
"""
from __future__ import annotations

import bisect
import importlib.util
import json
import re
import statistics
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

_spec = importlib.util.spec_from_file_location(
    "chipbench_trace", Path(__file__).resolve().parent / "trace.py")
trace = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(trace)

# the program's spans, by prefix; the harness's own (``bench.``) are not
PROGRAM = ("train.", "task.", "executor.", "cws.")
# the named scopes every model's program has; a configuration's reference
# module adds its own (``SCOPES`` there)
SCOPES = ("embed", "attn_proj", "attn_core", "mlp", "head_loss",
          "optimizer")
UNSCOPED, NONE = "(unscoped)", "(none)"

Span = Tuple[str, float, float, dict]     # (name, start_s, end_s, stats)

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?(%[^\s=]+)\s*=.*?'
                    r'metadata=\{op_name="((?:[^"\\]|\\.)*)"')


def scope_map(hlo_text: str,
              scopes: Sequence[str] = SCOPES) -> Dict[str, str]:
    """Instruction name (``%fusion.12``) → the innermost scope of
    ``scopes`` named in its ``op_name``; unscoped instructions are absent.
    A scope at the top of a differentiated function shows inside the
    transform's name (``jvp(head_loss)``), so names are split at ``/``,
    ``(`` and ``)``."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        hits = [w for w in re.split(r"[/()]", m.group(2)) if w in scopes]
        if hits:
            out[m.group(1)] = hits[-1]
    return out


def load(pd) -> Tuple[dict, Optional[Tuple[float, float]]]:
    """A parsed trace (``trace.profile``) as plain data, and its bounds in
    seconds.

    ``{plane: {line: events}}``: on ``/host:`` planes only the program's
    spans, ``(name, start_s, end_s, stats)``, with a line named twice
    (one per thread) keyed ``name#2``, ``name#3``...; on device planes the
    step's lines as ``trace.py`` reads them, ``(name, start_s, end_s)``.
    """
    planes: Dict[str, Dict[str, list]] = {}
    bounds = None
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            lines: Dict[str, list] = {}
            for line in plane.lines:
                key, n = line.name, 1
                while key in lines:
                    n += 1
                    key = f"{line.name}#{n}"
                lines[key] = [(e.name, e.start_ns * 1e-9,
                               (e.start_ns + e.duration_ns) * 1e-9,
                               dict(e.stats))
                              for e in line.events
                              if e.name.startswith(PROGRAM)]
            planes[plane.name] = lines
        elif plane.name.startswith("/device:TPU:"):
            planes[plane.name] = {
                line.name: [(e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events]
                for line in plane.lines if line.name in (trace.MODULES,
                                                         trace.OPS)}
        elif plane.name == "Task Environment":
            st = dict(plane.stats)
            if "profile_start_time" in st and "profile_stop_time" in st:
                # event times count from the session's start
                bounds = (0.0, (st["profile_stop_time"]
                                - st["profile_start_time"]) * 1e-9)
    return planes, bounds


def host_spans(planes, bounds=None) -> List[List[Span]]:
    """The program's spans per host thread, each thread's sorted by start;
    spans outside ``bounds`` (cut by the trace's start or stop) are left
    out."""
    lo, hi = bounds or (-float("inf"), float("inf"))
    out = []
    for name, lines in planes.items():
        if not name.startswith("/host:"):
            continue
        for evs in lines.values():
            keep = sorted((ev for ev in evs if ev[0].startswith(PROGRAM)
                           and lo <= ev[1] and ev[2] <= hi),
                          key=lambda ev: ev[1])
            if keep:
                out.append(keep)
    return out


def _per_step(spans: List[Span], names) -> List[float]:
    """Per ``step`` stat, the summed durations of ``names``; only steps
    that have every one of them."""
    got: Dict[int, Dict[str, float]] = defaultdict(dict)
    for n, s, e, st in spans:
        if n in names and "step" in st:
            got[st["step"]][n] = got[st["step"]].get(n, 0.0) + e - s
    return [sum(d.values()) for _, d in sorted(got.items())
            if set(d) == set(names)]


def _innermost(spans: List[Span]) -> List[Tuple[float, float, str]]:
    """The timeline of the innermost open span: ``(start, end, name)``
    pieces, the latest-started (then the first-ending) span winning where
    several are open."""
    cuts = sorted({t for _, s, e, _ in spans for t in (s, e)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        open_ = [sp for sp in spans if sp[1] <= a and sp[2] >= b]
        if open_:
            name = max(open_, key=lambda sp: (sp[1], -sp[2]))[0]
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1] = (out[-1][0], b, name)
            else:
                out.append((a, b, name))
    return out


def idle_by_span(gaps: List[Tuple[float, float]],
                 spans: List[Span]) -> Dict[str, float]:
    """Seconds of ``gaps`` under each innermost span, ``NONE`` for the
    rest."""
    pieces = _innermost(spans)
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for gs, ge in sorted(gaps):
        covered = 0.0
        while j < len(pieces) and pieces[j][1] <= gs:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < ge:
            ov = min(ge, pieces[k][1]) - max(gs, pieces[k][0])
            if ov > 0:
                out[pieces[k][2]] += ov
                covered += ov
            k += 1
        out[NONE] += (ge - gs) - covered
    return dict(out)


def _step_device(planes, scopes: Optional[Dict[str, str]]):
    """The step window's idle gaps on the first chip, and the median self
    time a step execution by scope (``None`` without a scope map)."""
    for dev in trace.device_planes(planes):
        mod = trace.step_module(planes, dev)
        if mod is not None:
            break
    else:
        return None, None
    runs = sorted((s, e) for n, s, e in planes[dev][trace.MODULES]
                  if n == mod)
    lo, hi = runs[0][0], runs[-1][1]
    every = planes[dev].get(trace.OPS, [])
    busy = trace.union(trace.clip([(s, e) for _, s, e in every], lo, hi))
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]
    if scopes is None:
        return gaps, None
    ops = [ev for ev in every if lo <= ev[1] < hi]
    per_run = [defaultdict(float) for _ in runs]
    starts = [s for s, _ in runs]
    for (text, t), (_, s, _) in zip(trace.self_times(ops), ops):
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < runs[i][1]:
            name = text.partition(" = ")[0].strip()
            per_run[i][scopes.get(name, UNSCOPED)] += t
    keys = {k for r in per_run for k in r}
    return gaps, {k: statistics.median(r.get(k, 0.0) for r in per_run)
                  for k in sorted(keys)}


def reduce(planes, bounds=None,
           scopes: Optional[Dict[str, str]] = None) -> dict:
    threads = host_spans(planes, bounds)
    spans = sorted((sp for th in threads for sp in th), key=lambda sp: sp[1])
    bodies = [sp for sp in spans if sp[0] == "task.body"]
    handoff = [b[1] - a[2] for a, b in zip(bodies, bodies[1:])]
    asked = sum(1 for sp in spans if sp[0] == "cws.round"
                and sp[3].get("forced") == 0
                and bodies and bodies[0][1] <= sp[1] < bodies[-1][1])
    dispatching = [sp for th in threads
                   if any(sp[0] == "train.step" for sp in th) for sp in th]
    gaps, scope_s = _step_device(planes, scopes)
    return {
        "input_s": _per_step(spans, ("train.batch", "train.put")),
        "handoff_s": handoff,
        "asked_rounds": asked,
        "periods": max(len(bodies) - 1, 0),
        "idle_s": idle_by_span(gaps, dispatching) if gaps is not None
        else None,
        "scope_s": scope_s,
    }


if __name__ == "__main__":
    names = SCOPES
    if len(sys.argv) > 3:
        sys.path.insert(0, str(Path(__file__).resolve().parent))
        import reference
        names += reference.load(json.loads(Path(sys.argv[3]).read_text()),
                                source=sys.argv[3]).SCOPES
    sc = scope_map(Path(sys.argv[2]).read_text(), names) \
        if len(sys.argv) > 2 else None
    print(json.dumps(reduce(*load(trace.profile(sys.argv[1])), sc),
                     indent=1))
