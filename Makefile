# Single entry point for tests, benchmarks and doc checks (see README.md).
#
#   make verify      pre-merge umbrella: test-fast + docs-check
#   make test-fast   tier-1 suite (excludes @slow; the CI / pre-merge gate)
#   make test-all    everything, including multi-device + million-point tests
#   make bench       benchmark driver (paper tables) + batched-engine bench
#   make bench-serve serving throughput sweep (wave size x mesh shape)
#   make bench-diff  re-run the batched bench and flag >20% throughput
#                    regressions vs the committed BENCH_batched.json snapshot
#   make serve-smoke serve CLI one round on a unit mesh, then diff a quick
#                    serve_bench run against the committed
#                    BENCH_serving.json (deterministic rejection/deadline
#                    counters compare exactly; timings at a loose 50%)
#   make scale-smoke quick dense-vs-matrix-free scale_bench run diffed
#                    against the committed BENCH_scale.json (analytic
#                    peak_bytes compare exactly; timings at a loose 50%)
#   make stream-smoke quick offline-vs-streaming stream_bench run diffed
#                    against the committed BENCH_streaming.json (oracle
#                    eval counts compare exactly; timings at a loose 50%)
#   make chaos-smoke quick chaos_bench run (fault injection: retry,
#                    quarantine, breaker fallback, crash-restore) diffed
#                    against the committed BENCH_resilience.json (the
#                    *_total resilience counters compare exactly; timings
#                    at a loose 50%)
#   make docs-check  execute the code blocks in README.md and docs/*.md,
#                    and assert the README coverage matrix matches the
#                    registries (tools/gen_matrix.py --check)
#   make shims-check assert no internal caller uses the deprecated entry
#                    points (maximize/batched_maximize/legacy submit) —
#                    everything internal routes through SelectionSpec/solve
#   make lint        repro-lint: the rule-registry static-analysis pass
#                    (AST rules + jaxpr audit + registry drift; see
#                    docs/linting.md) — suppress with
#                    `# lint: ok(RULE-ID): reason`

PYTHON ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: verify test-fast test-all bench bench-batched bench-serve bench-diff serve-smoke scale-smoke stream-smoke chaos-smoke docs-check shims-check lint

verify: test-fast docs-check shims-check lint serve-smoke scale-smoke stream-smoke chaos-smoke

test-fast:
	$(PYTHON) -m pytest -x -q

test-all:
	$(PYTHON) -m pytest -q -m ""

# benchmarks.run already includes batched_bench; bench-batched runs it alone
bench:
	$(PYTHON) -m benchmarks.run

bench-batched:
	$(PYTHON) -m benchmarks.batched_bench

# own process: it must set --xla_force_host_platform_device_count pre-import
bench-serve:
	$(PYTHON) -m benchmarks.serve_bench

# fresh snapshot to /tmp, then diff against the committed baseline
bench-diff:
	$(PYTHON) -m benchmarks.batched_bench --json /tmp/BENCH_batched_new.json >/dev/null
	$(PYTHON) tools/bench_diff.py benchmarks/BENCH_batched.json /tmp/BENCH_batched_new.json

# serving smoke: one CLI round on a unit mesh (the sharded engine with live
# collectives reduced to one device), then a quick serve_bench diffed
# against the committed snapshot.  The quick cells are a subset of the full
# sweep; rejection/deadline counters are deterministic and compare exactly,
# timings use a loose 50% threshold (shared boxes are noisy).
serve-smoke:
	XLA_FLAGS=--xla_force_host_platform_device_count=1 JAX_PLATFORMS=cpu \
	  $(PYTHON) -m repro.launch.serve --requests 8 --rounds 1 --mesh 1x1 --metrics
	$(PYTHON) -m benchmarks.serve_bench --quick --json /tmp/BENCH_serving_new.json >/dev/null
	$(PYTHON) tools/bench_diff.py benchmarks/BENCH_serving.json /tmp/BENCH_serving_new.json --threshold 0.5

# scale smoke: the quick dense-vs-matrix-free cells (a subset of the full
# sweep) diffed against the committed snapshot.  The analytic peak_bytes
# columns are machine-independent and compare exactly; wall-clock uses the
# same loose 50% threshold as serve-smoke.
scale-smoke:
	$(PYTHON) -m benchmarks.scale_bench --quick --json /tmp/BENCH_scale_new.json >/dev/null
	$(PYTHON) tools/bench_diff.py benchmarks/BENCH_scale.json /tmp/BENCH_scale_new.json --threshold 0.5

# streaming smoke: the quick offline-vs-streaming cells (a subset of the
# full sweep) diffed against the committed snapshot.  The n_evals oracle
# counters are deterministic and compare exactly; wall-clock uses the same
# loose 50% threshold as the other smokes.
stream-smoke:
	$(PYTHON) -m benchmarks.stream_bench --quick --json /tmp/BENCH_streaming_new.json >/dev/null
	$(PYTHON) tools/bench_diff.py benchmarks/BENCH_streaming.json /tmp/BENCH_streaming_new.json --threshold 0.5

# chaos smoke: the quick fault-injection cells (a subset of the full sweep)
# diffed against the committed snapshot.  The *_total resilience counters
# come from seeded fault plans against a sync server, so they are
# deterministic and compare exactly; recovery_ms / degraded_qps wall clock
# uses the same loose 50% threshold as the other smokes.
chaos-smoke:
	$(PYTHON) -m benchmarks.chaos_bench --quick --json /tmp/BENCH_resilience_new.json >/dev/null
	$(PYTHON) tools/bench_diff.py benchmarks/BENCH_resilience.json /tmp/BENCH_resilience_new.json --threshold 0.5

docs-check:
	$(PYTHON) tools/check_docs.py

shims-check:
	$(PYTHON) tools/check_shims.py

lint:
	JAX_PLATFORMS=cpu $(PYTHON) -m tools.lint
