"""The readings that a cell's correctness limits are set from, on the chip.

    python benchmarks/chip/calibrate.py --workload <cell> --first-seed <n> \
        [--seeds 12] [--control-seeds 3]

In one process, at the cell's own sizes:

- lower readings: for each of ``--seeds`` seeds, the program's first steps
  through ``run_training`` (the chunks that a run checks, read as ``run.py``
  reads them) against the f32 reference;
- upper readings: for each of ``--control-seeds`` seeds, the control (the
  reference computed in float8, the precision below the configuration's
  bfloat16), the half-batch fault (the reference on the first half of
  each batch, its mean over those rows) and a step that returns its state
  unchanged, each against the f32 reference.

Prints one JSON line per reading and, last, the largest lower and the
smallest upper reading of each number.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import re
import statistics
import sys
import time

import run as R


def readings(spec, seed: int, devices) -> dict:
    pl = R.plan(spec, 0)
    steps = pl["prefix_chunks"] * pl["chunk"]
    probe = R.Probe(spec, seed)
    lines = []
    out = R.train(spec, seed, steps, devices, probe, log=lines.append)
    c = R.check(spec, seed, steps, out, probe)
    return {"kind": "program", "seed": seed, "problems": probe.problems,
            **c["gaps"], "losses": [c["prog"]["losses"], c["ref"]["losses"]],
            "worst": worst(c["prog"], c["ref"]),
            "clipping": clipping(spec, lines, c["prog"], c["ref"])}


def clipping(spec, lines, prog, ref) -> dict:
    """The look at a gap common to every leaf of the first gradient: the
    global norm before clipping on both sides (the program's as its log
    prints it after the first chunk), the global norm of the clipped
    gradient the program's optimizer holds, and per leaf the ratio of the
    two sides' norms before clipping, less one."""
    gn = next(filter(None, (re.search(r"gnorm ([0-9.]+)", ln)
                            for ln in lines)), None)
    clip = spec["traffic"]["optimizer"]["grad_clip"]
    prog_gn = float(gn.group(1)) if gn and spec["traffic"]["chunk"] == 1 \
        else None
    out = {"program_gnorm": prog_gn, "reference_gnorm": ref["grad_norm"],
           "program_clipped_norm": math.sqrt(sum(
               v * v for v in prog["grad_norms"].values()))}
    if prog_gn:
        def unclip(n, g):
            return n / min(1.0, clip / (g + 1e-9))
        ratio = {k: unclip(prog["grad_norms"][k], prog_gn)
                 / unclip(ref["grad_norms"][k], ref["grad_norm"]) - 1
                 for k in ref["grad_norms"] if ref["grad_norms"][k] > 0}
        top = sorted(ratio.items(), key=lambda kv: -abs(kv[1]))
        out.update(unclipped_ratio_top=top[:3],
                   unclipped_ratio_median=statistics.median(ratio.values()))
    return out


def worst(alt, ref, n: int = 3) -> dict:
    """The leaves that read the largest gaps, for the look at a tail."""
    g = R.leaf_gaps(alt["grad_norms"], ref["grad_norms"],
                    sorted(ref["grad_norms"]))
    u = R.leaf_gaps(alt["change_norms"], ref["change_norms"],
                    R.moved_leaves(ref["grad_norms"]))
    keys = sorted(ref["grad_norms"])
    gd = R.leaf_diffs(alt["grad_sample"], ref["grad_sample"], keys)
    ud = R.leaf_diffs(alt["change_sample"], ref["change_sample"],
                      R.moved_leaves(ref["grad_norms"]))
    top = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:n]  # noqa: E731
    med = lambda d: statistics.median(d.values())  # noqa: E731
    return {"grad": top(g), "update": top(u), "grad_diff": top(gd),
            "update_diff": top(ud), "median": {
                "grad_gap": med(g), "update_gap": med(u),
                "grad_diff": med(gd), "update_diff": med(ud)}}


def unchanged(spec, seed: int, steps: int) -> dict:
    """A step that returns its state unchanged: every loss is the seed's
    parameters' (the reference at learning rate 0), and the optimizer's
    moment and the parameters' change stay zero."""
    frozen = dict(spec, traffic=dict(spec["traffic"], lr=0.0))
    alt = R.reference_run(frozen, seed, steps)
    for k in ("grad_norms", "change_norms"):
        alt[k] = {leaf: 0.0 for leaf in alt[k]}
    for k in ("grad_sample", "change_sample"):
        alt[k] = {leaf: 0.0 * v for leaf, v in alt[k].items()}
    return alt


def controls(spec, seed: int) -> list:
    pl = R.plan(spec, 0)
    steps = pl["prefix_chunks"] * pl["chunk"]
    ref = R.reference_run(spec, seed, steps)
    out = []
    for kind, make in (
            ("control_fp8",
             lambda: R.reference_run(spec, seed, steps, mode="fp8")),
            ("fault_half_batch",
             lambda: R.reference_run(
                 spec, seed, steps,
                 keep_rows=spec["traffic"]["batch"] // 2)),
            ("fault_unchanged", lambda: unchanged(spec, seed, steps))):
        alt = make()
        out.append({"kind": kind, "seed": seed, **R.compare(alt, ref),
                    "worst": worst(alt, ref)})
        gc.collect()
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args()
    spec = R.load_spec(args.workload)

    import jax
    from repro.launch.train import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("calibrate.py: needs a TPU", file=sys.stderr)
        return 2
    devices = devices[:spec["chips"]]
    rows = []
    for i in range(args.seeds):
        t = time.monotonic()
        r = readings(spec, args.first_seed + i, devices)
        r["seconds"] = time.monotonic() - t
        rows.append(r)
        print(json.dumps(r), flush=True)
    for i in range(args.control_seeds):
        t = time.monotonic()
        for r in controls(spec, args.first_seed + i):
            r["seconds"] = time.monotonic() - t
            rows.append(r)
            print(json.dumps(r), flush=True)
    names = ("loss_gap", "grad_gap", "update_gap", "grad_diff",
             "update_diff")
    summary = {"workload": args.workload}
    for kind in ("program", "control_fp8", "fault_half_batch",
                 "fault_unchanged"):
        got = [r for r in rows if r["kind"] == kind]
        if got:
            pick = max if kind == "program" else min
            summary[kind] = {n: pick(r[n] for r in got) for n in names}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
