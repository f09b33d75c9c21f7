# Tier-1 verification and benchmarks for the CWS/CWSI reproduction.
#
#   make test         the tier-1 suite (ROADMAP.md "Tier-1 verify")
#   make bench        scheduling-overhead scale benchmark (old vs new engine);
#                     writes BENCH_sched_scale.json (CI uploads it as an
#                     artifact; override the path with BENCH_JSON=...)
#   make bench-smoke  the same bench at CI scale (~30 s)
#   make bench-all    every scheduler paper-artifact benchmark, on the
#                     simulator (benchmarks/run.py)
#   make golden       regenerate tests/golden/ scheduling-trace snapshots

PYTHON ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: test bench bench-smoke bench-all golden

test:
	$(PYTHON) -m pytest -x -q

bench:
	$(PYTHON) benchmarks/bench_sched_scale.py

bench-smoke:
	BENCH_SMOKE=1 $(PYTHON) benchmarks/bench_sched_scale.py

bench-all:
	$(PYTHON) -m benchmarks.run

golden:
	REGEN_GOLDEN=1 $(PYTHON) -m pytest tests/test_golden_traces.py -q
