# Developer entry points.  PYTHONPATH=src everywhere (src-layout, no install).

.PHONY: verify test lint analyze bench bench-engine bench-smoke \
	bench-serve-smoke bench-mutate-smoke bench-chaos-smoke \
	bench-recovery-smoke bench-autotune-smoke

# Fast tier: every push. Hard wall-clock timeout so a hung jit/compile
# fails loudly instead of wedging CI.
verify:
	PYTHONPATH=src timeout 420 python -m pytest -x -q -m "not slow"

# Full tier (the tier-1 command): everything, including slow markers.
test:
	PYTHONPATH=src python -m pytest -x -q

# Lint tier: ruff's default rule set (pyflakes + pycodestyle errors), see
# ruff.toml.  CI runs this as its own fast job.
lint:
	ruff check .

# Static-analysis tier: the repo-specific invariant checkers of DESIGN.md
# §13 (lock discipline, trace safety, cache-key hygiene, failpoint sync,
# fail-open).  --strict fails on any unsuppressed finding; the JSON report
# is written even when findings fail the run, so CI can upload it.
analyze:
	@mkdir -p .cache
	PYTHONPATH=src python -m repro.analysis --strict \
		--json .cache/repolint.json

bench:
	PYTHONPATH=src python -m benchmarks.run

bench-engine:
	PYTHONPATH=src python -m benchmarks.run --only engine

# CI tier: tiny-n engine benchmarks in interpret mode so the benchmark
# entrypoints (and the BENCH_engine.json writer) can't silently rot.
# Results go to .cache/, never to the committed trajectory file.
bench-smoke:
	BENCH_SMOKE=1 BENCH_Q=32 PYTHONPATH=src timeout 420 \
		python -m benchmarks.run --only engine

# CI tier: tiny ragged trace through the serving frontend (both backends)
# so bucket warmup, the zero-recompile invariant, and the telemetry digest
# stay exercised per-PR.  Results go to .cache/, never to BENCH_serve.json.
bench-serve-smoke:
	XLA_FLAGS=--xla_force_host_platform_device_count=4 \
		BENCH_SMOKE=1 BENCH_Q=32 PYTHONPATH=src timeout 420 \
		python -m benchmarks.run --only serve

# CI tier: shrunk two-phase shifting trace through the autotuned frontend —
# screen/probe/decide/pre-warm-then-switch all exercised per-PR with the
# zero-recompile invariant asserted.  Results go to .cache/, never to
# BENCH_autotune.json.
bench-autotune-smoke:
	BENCH_SMOKE=1 BENCH_Q=32 PYTHONPATH=src timeout 420 \
		python -m benchmarks.run --only autotune

# CI tier: tiny streaming insert+delete trace through the mutable index
# behind the frontend, spanning a background merge — keeps the delta +
# tombstone + swap machinery and its zero-recompile invariant exercised
# per-PR.  Results go to .cache/, never to BENCH_mutate.json.
bench-mutate-smoke:
	BENCH_SMOKE=1 BENCH_Q=32 PYTHONPATH=src timeout 420 \
		python -m benchmarks.run --only mutate

# CI tier: seeded fault schedule through the frontend over a sharded
# mutable index — availability (every admitted request resolves), partial
# results with shards_failed set, merge retry/quarantine recovery, all
# exercised per-PR.  Results go to .cache/, never to BENCH_chaos.json.
bench-chaos-smoke:
	BENCH_SMOKE=1 BENCH_Q=32 PYTHONPATH=src timeout 420 \
		python -m benchmarks.run --only chaos

# CI tier: tiny WAL ingest / crash-recover / kill-at-every-site sweep so
# the durability stack (fsync ack point, checkpoint rotation, replay) and
# its zero-acked-loss guarantee stay exercised per-PR.  Results go to
# .cache/, never to BENCH_recovery.json.
bench-recovery-smoke:
	BENCH_SMOKE=1 BENCH_Q=32 PYTHONPATH=src timeout 420 \
		python -m benchmarks.run --only recovery
