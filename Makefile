.PHONY: test bench bench-smoke demo

# Tier-1 verify (ROADMAP.md): must stay green.
test:
	./scripts/test.sh

# Memento's host-side claims (B1-B5, B12); serving is measured on the chip
# (BENCHMARK.json, PERF.md).
bench:
	PYTHONPATH=src python benchmarks/run.py

# B1-B5 at their smoke sizes, as CI runs them.
bench-smoke:
	PYTHONPATH=src python benchmarks/run.py --smoke

demo:
	PYTHONPATH=src python examples/serve_demo.py
