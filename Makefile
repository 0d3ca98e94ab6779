# Canonical entry points for verification and benchmarks.
#
#   make test             tier-1 test suite on the CPU (the CI / verify command)
#   make chip-smoke       fit -> reference -> serve on one TPU (chip_smoke.py)
#   make test-api         just the unified-API tests (fast)
#   make lint             dead-import lint (pyflakes when installed, AST fallback)
#   make ci               lint + tier-1 tests + chaos-smoke + bench-smoke
#                         artifact checks + bench-gate
#                         (what .github/workflows/ci.yml runs)
#   make bench-smoke      smoke benchmark subset (fig4_scaling, transform_fused,
#                         fit_fused, serve_engine, multiclass_batched at quick
#                         sizes) + BENCH_*.json artifact check
#   make bench-transform  fused-vs-legacy transform benchmark (BENCH_transform.json)
#   make bench-fit        fused fit-path benchmark (BENCH_fit.json)
#   make bench-serve      batched serving engine benchmark (BENCH_serve.json)
#   make bench-multiclass sequential-vs-class-batched multi-class fit benchmark
#                         (BENCH_multiclass.json)
#   make bench-streaming  out-of-core streaming fit benchmark (BENCH_streaming.json)
#   make bench-online     incremental update + continuous serving loop benchmark
#                         (BENCH_online.json)
#   make bench-resilience integrity overhead + crash-recovery benchmark
#                         (BENCH_resilience.json)
#   make bench-obs        observability overhead + sketch-fidelity benchmark
#                         (BENCH_obs.json)
#   make bench-gate       perf-regression gate: newest results/history.jsonl
#                         record vs the rolling baseline of earlier records
#   make obs-smoke        continuous loop with obs export (results/obs/trace.json,
#                         metrics.jsonl) + post-hoc obs_report render
#   make chaos-smoke      fault-injection harness (repro.launch.chaos_vi --fast):
#                         kill/resume, corrupt state, degraded activation,
#                         transient faults, poison isolation, torn shards
#   make serve-smoke      in-process CPU run of the serving CLI (repro.launch.serve_vi)
#   make continuous-smoke in-process CPU run of the ingest->refit->activate loop
#                         (repro.launch.continuous_vi)
#   make bench            full quick benchmark sweep
#   make clean            remove compiled bytecode and pytest caches
#   make dev-deps         install dev-only deps (pytest, hypothesis, pyflakes)

PYTHON ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: test test-api chip-smoke lint ci bench bench-smoke bench-transform bench-fit \
        bench-serve bench-multiclass bench-streaming bench-online \
        bench-resilience bench-obs bench-gate chaos-smoke serve-smoke \
        continuous-smoke obs-smoke clean dev-deps

test:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest -x -q

test-api:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest -q tests/test_api.py

chip-smoke:
	$(PYTHON) chip_smoke.py

lint:
	$(PYTHON) tools/lint.py src/repro benchmarks tools

ci: lint test chaos-smoke bench-smoke bench-gate

bench-smoke:
	$(PYTHON) -m benchmarks.run --only fig4_scaling,transform_fused,fit_fused,serve_engine,multiclass_batched,streaming_oavi,online_oavi,resilience_chaos,obs_overhead
	$(PYTHON) -m benchmarks.check_artifacts fit transform scaling serve multiclass streaming online resilience obs

bench-transform:
	$(PYTHON) -m benchmarks.run --only transform_fused

bench-fit:
	$(PYTHON) -m benchmarks.run --only fit_fused

bench-serve:
	$(PYTHON) -m benchmarks.run --only serve_engine

bench-multiclass:
	$(PYTHON) -m benchmarks.run --only multiclass_batched

bench-streaming:
	$(PYTHON) -m benchmarks.run --only streaming_oavi

bench-online:
	$(PYTHON) -m benchmarks.run --only online_oavi

bench-resilience:
	$(PYTHON) -m benchmarks.run --only resilience_chaos

bench-obs:
	$(PYTHON) -m benchmarks.run --only obs_overhead

bench-gate:
	$(PYTHON) -m benchmarks.history --gate

obs-smoke:
	$(PYTHON) -m repro.launch.continuous_vi --base-rows 2048 --increments 2 \
		--increment-rows 1024 --shard-rows 1024 --chunk-rows 512 \
		--min-update-rows 1024 --obs-dir results/obs
	$(PYTHON) -m repro.launch.obs_report --obs-dir results/obs

chaos-smoke:
	$(PYTHON) -m repro.launch.chaos_vi --fast

continuous-smoke:
	$(PYTHON) -m repro.launch.continuous_vi --base-rows 4096 --increments 4 \
		--increment-rows 1024 --shard-rows 1024 --chunk-rows 512

serve-smoke:
	$(PYTHON) -m repro.launch.serve_vi --fit-m 1500 --requests 96 --mean-rows 64 \
		--concurrency 8 --min-bucket 32 --max-bucket 4096

bench:
	$(PYTHON) -m benchmarks.run

clean:
	find . -name __pycache__ -type d -prune -exec rm -rf {} +
	rm -rf .pytest_cache

dev-deps:
	$(PYTHON) -m pip install -r requirements-dev.txt
