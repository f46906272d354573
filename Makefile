PYTHONPATH := src
export PYTHONPATH

.PHONY: test lint bench-quick bench pipeline-bench classify-bench perf-gate \
        autotune-cache serve-smoke serve-bench serve-bench-sharded chaos-test

# MODE=streaming|window|both selects the fused-chain execution plan(s)
# the pipeline benches time (default both; see kernels/stencil.py modes)
MODE ?= both

test:            ## tier-1 verify
	python -m pytest -x -q

lint:            ## ruff check + format ratchet (CI pins ruff==0.9.9)
	ruff check src/repro/kernels src/repro/core src/repro/cv src/repro/serve benchmarks tests
	ruff format --check src benchmarks tests

bench-quick:     ## quick benchmark pass (writes BENCH_results.json)
	python -m benchmarks.run --quick --mode $(MODE)

bench:           ## full benchmark pass
	python -m benchmarks.run --mode $(MODE)

pipeline-bench:  ## fused-vs-staged acceptance benchmark only
	python -m benchmarks.pipeline_bench --mode=$(MODE)

classify-bench:  ## fused classifier tail (ClassifyPlan) vs per-image staged
	python -m benchmarks.classify_bench

# MODE is passed through so a `make bench-quick MODE=window` run is gated
# against window-only history rows (like-for-like), not the both-plan ones
perf-gate:       ## fail on perf regressions vs BENCH_results.json history
	python -m benchmarks.perf_gate --mode $(MODE)

autotune-cache:  ## inspect the measured chain-mode cache
	python -m repro.core.autotune --show-cache

# FAULT_SPEC seeds the deterministic fault registry (core/faultinject.py);
# empty = fault-free.  The chaos CI cell runs both targets with every
# fault class active and requires zero unhandled exceptions.
FAULT_SPEC ?=

serve-smoke:     ## serving-engine smoke workload (honors FAULT_SPEC)
	REPRO_FAULT_SPEC="$(FAULT_SPEC)" python -m repro.serve.cv_engine --smoke

chaos-test:      ## fault suite under injection (the chaos CI cell)
	REPRO_FAULT_SPEC="$(FAULT_SPEC)" python -m pytest -x -q \
		tests/test_faultinject.py tests/test_plan_table.py tests/test_serve_cv.py

serve-bench:     ## serving throughput benchmark (appends to BENCH_results.json)
	python -m benchmarks.serve_bench

serve-bench-sharded:  ## batch-1024 multi-device fan-out rows (one process, all devices)
	python -m benchmarks.serve_bench --sharded --quick
