"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

    python benchmarks/chip/trace.py <trace.xplane.pb>   # prints the reduction

A device plane is one named ``/device:TPU:<n>``. On it, the ``XLA Modules``
line holds one event per program execution and the ``XLA Ops`` line one
event per operation. The reduction gives, per chip and averaged:

- the traced window: from the start of the first execution of the step
  program to the end of its last; the step program is the module with the
  most device time;
- busy seconds: the union of the operations' intervals inside the window;
- the step program's device duration per execution;
- ``breakdown``: the operations with the most device time of their own
  (less the operations nested in them), and the longest
  idle gaps inside the window, each labelled with the host event (a thread
  of a ``/host:`` plane) that best matches it.
"""
from __future__ import annotations

import json
import statistics
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]          # (start, end) in seconds

MODULES, OPS = "XLA Modules", "XLA Ops"
TOP = 10


def profile(path: str):
    """The trace file, parsed once for every reduction that reads it."""
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def load(pd):
    """The planes of a parsed trace (``profile``) as plain data:
    ``{plane: {line: [(name, start_s, end_s), ...]}}``."""
    out: Dict[str, Dict[str, list]] = {}
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            lines[line.name] = [(e.name, e.start_ns * 1e-9,
                                 (e.start_ns + e.duration_ns) * 1e-9)
                                for e in line.events]
        out[plane.name] = lines
    return out


def union(intervals: List[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def device_planes(planes) -> List[str]:
    return sorted(n for n, lines in planes.items()
                  if n.startswith("/device:TPU:") and MODULES in lines)


def step_module(planes, dev: str) -> Optional[str]:
    total: Dict[str, float] = defaultdict(float)
    for name, s, e in planes[dev][MODULES]:
        total[name] += e - s
    return max(total, key=total.get) if total else None


def self_times(events: List[Tuple[str, float, float]]):
    """``(name, seconds)`` of each event less the events nested in it
    (a ``while`` op holds its body's ops on the same line)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [e - s for _, s, e in events]
    stack: List[int] = []
    for i in order:
        _, s, e = events[i]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(events[i][0], own[i]) for i in range(len(events))]


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[4,1024]{...} fusion(...)`` → ``%fusion.12
    bf16[4,1024]``: the instruction and the shape it makes."""
    name, _, rest = text.partition(" = ")
    shape = rest.split("{", 1)[0].split(" ", 1)[0] if rest else ""
    return f"{name} {shape}".strip()


def _label(gap: Interval, host: List[Tuple[str, float, float]]) -> str:
    """What the host was doing while the device waited: the host event
    that best matches ``gap``, scored by its overlap with the gap times the
    share of the event inside it (so neither an enclosing event that spans
    many gaps nor a sliver inside one wins)."""
    best, score = "unattributed", 0.0
    for name, s, e in host:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > 0 and ov * ov / max(e - s, 1e-12) > score:
            best, score = name, ov * ov / max(e - s, 1e-12)
    return best


def reduce(planes) -> Optional[dict]:
    """Device busy/idle, step device time and breakdown; ``None`` where the
    trace holds no device execution."""
    devs = device_planes(planes)
    per_chip = []
    for dev in devs:
        mod = step_module(planes, dev)
        if mod is None:
            continue
        runs = sorted((s, e) for n, s, e in planes[dev][MODULES] if n == mod)
        lo, hi = runs[0][0], runs[-1][1]
        ops = planes[dev].get(OPS, [])
        busy = union(clip([(s, e) for _, s, e in ops], lo, hi))
        per_chip.append(dict(dev=dev, module=mod, runs=runs, lo=lo, hi=hi,
                             busy=busy, ops=ops))
    if not per_chip:
        return None
    c0 = per_chip[0]
    window_s = statistics.fmean(c["hi"] - c["lo"] for c in per_chip)
    busy_s = statistics.fmean(sum(e - s for s, e in c["busy"])
                              for c in per_chip)
    op_time: Dict[str, float] = defaultdict(float)
    for name, t in self_times([ev for ev in c0["ops"]
                               if c0["lo"] <= ev[1] < c0["hi"]]):
        op_time[op_name(name)] += t
    gaps = [(a[1], b[0]) for a, b in zip(c0["busy"], c0["busy"][1:])
            if b[0] > a[1]]
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    host = [ev for name, lines in planes.items() if name.startswith("/host:")
            for evs in lines.values() for ev in evs]
    return {
        "chips": len(per_chip),
        "step_module": c0["module"],
        "steps": len(c0["runs"]),
        "step_device_s": [e - s for s, e in c0["runs"]],
        "window_s": window_s,
        "busy_s": busy_s,
        "breakdown": {
            "device_ops": [[n, t] for n, t in sorted(
                op_time.items(), key=lambda kv: kv[1], reverse=True)[:TOP]],
            "idle_gaps": [[_label(g, host), g[1] - g[0]]
                          for g in gaps[:TOP]],
        },
    }


def reduce_file(path: str) -> Optional[dict]:
    return reduce(load(profile(path)))


if __name__ == "__main__":
    planes = load(profile(sys.argv[1]))
    for name, lines in planes.items():
        print(name, {ln: len(evs) for ln, evs in lines.items()},
              file=sys.stderr)
    r = reduce(planes)
    if r:
        r["step_device_s"] = r["step_device_s"][:5]
    print(json.dumps(r, indent=1))
