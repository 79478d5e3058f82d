# Tier-1 verify — exactly as ROADMAP.md specifies.
PY ?= python

.PHONY: verify lint bench bench-serve

verify:
	PYTHONPATH=src $(PY) -m pytest -x -q

# repro-lint (DESIGN.md §20): AST invariant passes over src/ — trace
# purity, readback budget, replay determinism, accounting completeness,
# donation safety. Exits nonzero on any finding not justified in
# tools/lint_baseline.txt. Runs in CI before the test suite.
lint:
	PYTHONPATH=src $(PY) tools/repro_lint.py --baseline tools/lint_baseline.txt

bench:
	PYTHONPATH=src $(PY) -m benchmarks.run

# reproduces ALL serve bench artifacts: BENCH_serve.json (fused vs
# host-loop reference), BENCH_quant.json (bf16 vs int8 fast path),
# BENCH_serve_paged.json (dense vs paged+prefix-cache on shared prefixes),
# BENCH_serve_spec.json (plain paged vs speculative multi-token decode),
# BENCH_serve_longctx.json (paged flash-prefill kernel: fragmented vs
# contiguous layouts vs the chunked whole-table-gather baseline),
# BENCH_serve_faults.json (chaos tier: one seeded fault arm per kind vs
# the fault-free baseline, DESIGN.md §17), and BENCH_serve_cow.json
# (n-best COW forks vs the duplicate-KV baseline, DESIGN.md §18)
bench-serve:
	PYTHONPATH=src $(PY) benchmarks/serve_bench.py
	PYTHONPATH=src $(PY) benchmarks/serve_bench.py --quant int8
	PYTHONPATH=src $(PY) benchmarks/serve_bench.py --paged
	PYTHONPATH=src $(PY) benchmarks/serve_bench.py --paged --spec-k 4
	PYTHONPATH=src $(PY) benchmarks/serve_bench.py --paged --nbest 4
	PYTHONPATH=src $(PY) benchmarks/serve_bench.py --paged --long-context
	PYTHONPATH=src $(PY) benchmarks/serve_bench.py --chaos
