.PHONY: check test bench-scaling bench-txn bench-migration bench-crdt bench-slo bench-watchdog bench-gate

check:
	bash scripts/check.sh

test:
	PYTHONPATH=src python -m pytest -x -q

bench-scaling:
	PYTHONPATH=src python -m benchmarks.fig_scaling

bench-txn:
	PYTHONPATH=src python -m benchmarks.fig_txn

bench-migration:
	PYTHONPATH=src python -m benchmarks.fig_migration

bench-crdt:
	PYTHONPATH=src python -m benchmarks.fig_crdt

bench-slo:
	PYTHONPATH=src python -m benchmarks.fig_slo

bench-watchdog:
	PYTHONPATH=src python -m benchmarks.fig_watchdog

bench-gate:
	python scripts/bench_gate.py
