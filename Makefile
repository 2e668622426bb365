PY ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: test test-multidevice bench bench-smoke bench-check bench-ft \
        bench-batched bench-init bench-serve bench-dist quickstart docs \
        docs-check lint typecheck analysis static test-fast

test:            ## tier-1 suite
	$(PY) -m pytest -x -q

test-multidevice: ## 8-virtual-device subprocess suites only (slow)
	$(PY) -m pytest -q -m multidevice

lint:            ## ruff (config in pyproject.toml)
	ruff check src tests benchmarks examples

typecheck:       ## mypy, strict on repro.api / repro.kernels.ops / repro.analysis
	mypy

analysis:        ## repo-specific static passes: contracts, lint, recompile
	$(PY) -m repro.analysis --check

static: lint typecheck analysis  ## every static gate CI runs before the tests

test-fast:       ## API + kmeans + kernels only (quick signal)
	$(PY) -m pytest -q tests/test_api.py tests/test_kmeans.py tests/test_kernels.py

bench:           ## all paper-figure benchmark modules
	$(PY) -m benchmarks.run

bench-smoke:     ## Fig. 7 ladder at tiny shapes (all rungs compiled)
	$(PY) -m benchmarks.bench_stepwise --smoke --model --json BENCH_stepwise.json

bench-check:     ## regen smoke artifacts, gate vs committed baselines (>25% = fail)
	git show HEAD:BENCH_stepwise.json > /tmp/bench_stepwise_baseline.json
	git show HEAD:BENCH_init.json > /tmp/bench_init_baseline.json
	git show HEAD:BENCH_serve.json > /tmp/bench_serve_baseline.json
	git show HEAD:BENCH_dist.json > /tmp/bench_dist_baseline.json
	$(MAKE) bench-smoke
	$(MAKE) bench-init
	$(MAKE) bench-serve
	$(MAKE) bench-dist
	$(PY) -m benchmarks.check_regression /tmp/bench_stepwise_baseline.json \
	    BENCH_stepwise.json --rung fig7_v5_onepass \
	    --rung fig7_v7_ft_onepass --rung fig7_v8_batched \
	    --rung fig7_v9_pruned --rung fig7_v6_smallk \
	    --rung fig7_v10_int8 --rung fig7_v11_dbuf \
	    --rung fig7_v12_aot_predict --max-ratio 1.25
	$(PY) -m benchmarks.check_regression /tmp/bench_init_baseline.json \
	    BENCH_init.json --rung init_fused_vs_vmapped --max-ratio 1.25
	$(PY) -m benchmarks.check_regression /tmp/bench_serve_baseline.json \
	    BENCH_serve.json --rung serve_microbatch_vs_naive --max-ratio 1.25
	$(PY) -m benchmarks.check_regression /tmp/bench_dist_baseline.json \
	    BENCH_dist.json --rung dist_hier_vs_flat --max-ratio 1.25

bench-init:      ## fused k-means++ seeding vs vmapped baseline (B=64 small problems)
	$(PY) -m benchmarks.bench_init --json BENCH_init.json

bench-serve:     ## serving layer: AOT cells, micro-batch vs naive, latency sim
	$(PY) -m benchmarks.bench_serve --json BENCH_serve.json

bench-dist:      ## hierarchical vs flat vs compressed reduce (8 virtual devices)
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	    $(PY) -m benchmarks.bench_dist --json BENCH_dist.json

bench-ft:        ## Fig. 15/16 FT overhead (incl. one-pass FT vs unprotected)
	$(PY) -m benchmarks.bench_ft_overhead

bench-batched:   ## batched many-problem fit vs vmapped vs loop-of-fits
	$(PY) -m benchmarks.bench_batched

quickstart:
	$(PY) examples/quickstart.py

docs:            ## regenerate the auto-generated docs (backend matrix)
	$(PY) -m repro.api.registry --markdown docs/backends.md

docs-check:      ## CI doc gates: matrix freshness + executable docs
	$(PY) -m repro.api.registry --check docs/backends.md
	$(PY) -m pytest -q tests/test_docs.py
	$(PY) examples/quickstart.py --smoke
