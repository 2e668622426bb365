PY ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: test test-multidevice quickstart docs docs-check lint typecheck \
        analysis static test-fast

test:            ## tier-1 suite
	$(PY) -m pytest -x -q

test-multidevice: ## 8-virtual-device subprocess suites only (slow)
	$(PY) -m pytest -q -m multidevice

lint:            ## ruff (config in pyproject.toml)
	ruff check src tests examples

typecheck:       ## mypy, strict on repro.api / repro.kernels.ops / repro.analysis
	mypy

analysis:        ## repo-specific static passes: contracts, lint, recompile
	$(PY) -m repro.analysis --check

static: lint typecheck analysis  ## every static gate CI runs before the tests

test-fast:       ## API + kmeans + kernels only (quick signal)
	$(PY) -m pytest -q tests/test_api.py tests/test_kmeans.py tests/test_kernels.py

quickstart:
	$(PY) examples/quickstart.py

docs:            ## regenerate the auto-generated docs (backend matrix)
	$(PY) -m repro.api.registry --markdown docs/backends.md

docs-check:      ## CI doc gates: matrix freshness + executable docs
	$(PY) -m repro.api.registry --check docs/backends.md
	$(PY) -m pytest -q tests/test_docs.py
	$(PY) examples/quickstart.py --smoke
