# Tier-1 verification. The multi-device tests (test_multidevice,
# test_elastic) spawn subprocesses that force 8 fake CPU devices via
# XLA_FLAGS before jax initializes; exporting the flag here too keeps
# the top-level process consistent on CPU-only hosts and makes the run
# reproducible regardless of the caller's environment.
XLA_DEVICES ?= 8

.PHONY: verify test test-fast ci analyze dryrun-smoke bench

verify: test

test:
	XLA_DEVICES=$(XLA_DEVICES) scripts/verify.sh

# skip the multi-minute subprocess tests (inner loop) — routed through
# scripts/verify.sh so it runs under the SAME fake-device XLA_FLAGS and
# path setup as the full suite (a bare `pytest` invocation here used to
# diverge from what CI enforces)
test-fast:
	XLA_DEVICES=$(XLA_DEVICES) scripts/verify.sh -m "not slow"

# the full CI pipeline locally: analysis gate + tier-1 suite + the
# bench schema gate — exactly what .github/workflows/ci.yml runs (as
# separate jobs)
ci: analyze test bench

# static contract checker + sanitizer (src/repro/analysis/README.md):
# capability lattice vs the kernels README matrix, pallas block/index
# maps, the sharding-contract prover, the jaxpr dataflow audit, the
# serve transfer/retrace contract, and the AST lint — exits nonzero on
# any finding, and writes the machine-readable findings document (the
# CI artifact). Same offline fake-device env as the tests.
analyze:
	mkdir -p experiments/analysis
	XLA_FLAGS="--xla_force_host_platform_device_count=$(XLA_DEVICES)" \
	    PYTHONPATH=src python -m repro.analysis \
	    --out experiments/analysis/findings.json

# kernel_bench (oracle match, VMEM working sets, HBM density) under
# the same 8-fake-device env as the tests; fails if the tracked
# BENCH_autotune.json or the regenerated (gitignored)
# experiments/benchmarks/kernel_bench.json is missing or
# schema-invalid (benchmarks/schema.py). Speed is measured on the chip
# by bench/run.py (BENCHMARK.json), not here.
bench:
	XLA_FLAGS="--xla_force_host_platform_device_count=$(XLA_DEVICES)" \
	    PYTHONPATH=src python -m benchmarks.run --fast

# one dry-run cell as a launcher smoke check (compiles a 256-chip train
# step against ShapeDtypeStructs; no allocation)
dryrun-smoke:
	PYTHONPATH=src python -m repro.launch.dryrun --arch internlm2-1.8b \
	    --shape train_4k
