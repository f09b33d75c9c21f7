# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness — one bench per paper artifact:

  fig2_nfcore    Fig. 2: Original vs Rank(Min)RR over nine nf-core workflows
  strategies     §5 scheduling-strategy table (FIFO/Rank/HEFT/Tarema/Fair)
  predictors     §5 runtime prediction (Lotaru vs mean baselines)
  resource_pred  §5 peak-memory prediction (wastage/OOM table)
  provenance     §4 provenance store throughput/export
  sched_scale    incremental scheduling core vs legacy full scans at
                 10×500-task multi-workflow scale

Each bench returns (elapsed_s, derived-metrics dict) and the harness prints
one ``name,us_per_call,derived`` CSV line per bench.
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from . import (
        bench_fig2_nfcore,
        bench_predictors,
        bench_provenance,
        bench_resource_pred,
        bench_sched_scale,
        bench_strategies,
    )

    benches = [
        ("fig2_nfcore", bench_fig2_nfcore.run),
        ("strategies", bench_strategies.run),
        ("predictors", bench_predictors.run),
        ("resource_pred", bench_resource_pred.run),
        ("provenance", bench_provenance.run),
        ("sched_scale", bench_sched_scale.run),
    ]
    rows = []
    failed = []
    for name, fn in benches:
        print(f"== {name} ==")
        try:
            elapsed, derived = fn(verbose=True)
            rows.append((name, elapsed * 1e6,
                         ";".join(f"{k}={v:.3f}" if isinstance(v, float)
                                  else f"{k}={v}"
                                  for k, v in sorted(derived.items()))))
        except AssertionError as e:
            failed.append((name, f"claim-check failed: {e}"))
            traceback.print_exc()
        except Exception as e:  # noqa: BLE001
            failed.append((name, f"{type(e).__name__}: {e}"))
            traceback.print_exc()

    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.0f},{derived}")
    if failed:
        print(f"\nFAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
