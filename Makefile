# Developer entry points.  All targets force 8 logical host devices so
# the mesh-sharded serving tests exercise a real data x model layout on
# any machine (tests/conftest.py applies the same default under bare
# pytest); override with XLA_HOST_DEVICES=1 to pin single-device.
XLA_HOST_DEVICES ?= 8
# merge (not replace) any XLA flags already in the developer's shell
export XLA_FLAGS := $(XLA_FLAGS) --xla_force_host_platform_device_count=$(XLA_HOST_DEVICES)
export PYTHONPATH := src

PYTEST ?= python -m pytest

.PHONY: smoke full chaos fleet lint

# sub-minute loop: everything not marked slow (includes the equivalence
# smoke subset — sharded serve, pallas packed, paged serve with radix
# reuse — plus the paging property tests and the fast protocol
# cross-task-batching scenario)
smoke:
	$(PYTEST) -q -m "not slow"

# the whole suite, including the cross-backend equivalence grid
full:
	$(PYTEST) -q

# deterministic chaos acceptance runs: seeded fault injection through the
# full resilience stack (FaultyClient -> ResilientClient -> ProtocolRunner),
# asserting bit-identical reruns and zero sibling aborts
chaos:
	$(PYTEST) -q -m chaos

# fleet gateway battery: queue/routing property tests, LRU response
# cache, backpressure, replica-kill chaos, plus the EnginePool
# determinism cells against real engines
fleet:
	$(PYTEST) -q tests/test_fleet.py
	$(PYTEST) -q tests/test_equivalence.py -k fleet

# static analysis: repro-lint rules R1-R9 over the library (exit 1 on
# any unbaselined finding; see lint_baseline.json + repro-lint.toml),
# an R1/R3-only determinism pass over the entry points (benchmarks/,
# examples/ — key minting at the entry point is allowlisted), plus ruff
# style lint when installed (CI installs it; local runs skip gracefully)
lint:
	python -m repro.analysis.lint src/repro
	python -m repro.analysis.lint benchmarks examples --rules R1,R3
	@if command -v ruff >/dev/null 2>&1; then ruff check src tests; \
	else echo "ruff not installed; skipping style lint"; fi
