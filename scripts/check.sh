#!/usr/bin/env bash
# Tier-1 gate + benchmark wiring check.
#
#   scripts/check.sh            # full tier-1 tests + benchmark smokes
#   scripts/check.sh -m 'not slow'   # extra pytest args pass through
#
# The smoke runs use tiny op counts: they validate that the sharded,
# transaction, and live-migration benchmarks still run end-to-end
# (fig_scaling stays monotonic; fig_txn keeps its crash-atomicity,
# single-shard fast-path, fan-out-beats-sequential, and
# wound/wait-cuts-aborts assertions; fig_migration keeps its
# zero-lost-writes, strict-linearizability,
# untouched-slot fast-ratio, slot-route parity, and rebalance-beats-static
# assertions; fig_crdt keeps the merge-lattice separation — hot-counter
# INCR fast-frac >=0.95 vs plain SET <=0.2 at skew 1.0 — the 16x16
# matrix/scalar and record-kernel/oracle bit-exact parity checks, and the
# merge-aware strict-linearizability assertion on every scenario; fig_slo
# keeps its armor assertions — bounded admission queue, >=5x goodput over
# the naked 2x-overload baseline, AIMD adaptive bound not regressing the
# static one, heartbeat-detected failover with zero lost acked writes, and
# strict-checked migration/crash storm companions; fig_obs keeps the flight
# recorder honest — every storm exports a Perfetto-loadable trace with zero
# leaked spans and resolvable parents, and registry/sampled-tracing
# overhead on the device fast path stays bounded; fig_watchdog proves the
# protocol watchdog non-vacuous — every ChaosConfig switch trips exactly
# its monitor within a bounded event count, clean overload/crash/migration
# storms trip nothing, breach replay is bit-identical, the windowed
# linearizability checker agrees with the strict one, and watched goodput
# stays >=95% of unwatched on the overload ramp), not the measured
# numbers.  bench_gate then reads the recorded BENCH_curp.json deltas:
# soft perf regressions (>10%) report without failing, hard ones (>20%)
# fail the run.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

python -m pytest -x -q "$@"
python -m benchmarks.fig_scaling --smoke
python -m benchmarks.fig_txn --smoke
python -m benchmarks.fig_migration --smoke
python -m benchmarks.fig_crdt --smoke
python -m benchmarks.fig_slo --smoke
python -m benchmarks.fig_obs --smoke
python -m benchmarks.fig_watchdog --smoke

# Perf-regression gate over recorded BENCH_curp.json deltas: report-only
# for soft moves, blocking for >20% regressions (--ci).
python scripts/bench_gate.py --ci

# Observability discipline: production layers report through the metrics
# registry / tracer, never bare print() (benchmarks and scripts may print).
if grep -rnE '^[[:space:]]*print\(' src/repro/core src/repro/sim; then
    echo "check.sh: bare print() in src/repro/{core,sim} — use telemetry" >&2
    exit 1
fi
echo "check.sh: all green"
