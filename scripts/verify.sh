#!/usr/bin/env bash
# Tier-1 verification: test suite + parity/fault gates.
#
# Usage: scripts/verify.sh [--fast] [--units|--gates]
#   --fast    deselect @slow tests
#   --units   only the unit/property test pass (gate files excluded —
#             they run once, in the gates phase, not twice)
#   --gates   only the explicit CI gates (dispatch/experiment/parallel/
#             launcher suites + the parity and fault-injection scripts)
# Default (no phase flag) runs both phases in order. The CI matrix
# (.github/workflows/ci.yml) runs the phases as parallel jobs.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

MODE=all
MARK=()
for arg in "$@"; do
    case "$arg" in
        --fast) MARK=(-m "not slow") ;;
        --units|--gates) MODE="${arg#--}" ;;
        *) echo "unknown argument: $arg" >&2; exit 2 ;;
    esac
done

# Files re-run explicitly by the gates phase; the units pass excludes
# them so a full verify.sh executes every test file exactly once.
GATE_FILES=(
    tests/test_dispatch_gate.py
    tests/test_experiment.py
    tests/test_parallel_sweep.py
    tests/test_golden_tables.py
    tests/test_launcher.py
)

if [[ "$MODE" == "all" || "$MODE" == "units" ]]; then
    IGNORES=()
    for f in "${GATE_FILES[@]}"; do IGNORES+=("--ignore=$f"); done
    python -m pytest -x -q "${MARK[@]}" "${IGNORES[@]}"
fi

if [[ "$MODE" == "all" || "$MODE" == "gates" ]]; then
    # dispatch-count regression gate (O(1) jitted dispatches per window)
    # + experiment-API gate (SweepSpec preset == legacy grid, JSON
    # round-trip)
    python -m pytest -q "${MARK[@]}" tests/test_dispatch_gate.py \
        tests/test_experiment.py
    # parallel-sweep + hosts-launcher gates: partitioner/backend/golden
    # suites and the launcher retry/crash suite (slow members — clean
    # hosts parity, slurm bash-sim, fake-device subprocess — run here
    # too unless --fast, matching the old full-suite coverage)
    python -m pytest -q "${MARK[@]}" tests/test_parallel_sweep.py \
        tests/test_golden_tables.py tests/test_launcher.py
    # sharded-run parity under 8 fake CPU devices: a parallel run must
    # reproduce the sequential SweepResult bitwise (the flag must precede
    # jax init, so the gate owns its process; DESIGN.md §7)
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
        python scripts/parallel_parity.py --preset smoke --windows 4 \
        --expect-devices 8 --backends devices:n=8,processes:n=2
    # multi-host launcher parity, clean AND with one local worker
    # SIGKILLed mid-shard on its first attempt (DESIGN.md §8)
    python scripts/hosts_parity.py --preset smoke --windows 3 \
        --spec "hosts:channel=local,n=2,retries=1" --inject-failures
    # sweep-service parity: sweeps submitted over HTTP stream per-shard
    # NDJSON and merge client-side — bitwise-identical to sequential,
    # clean, with one worker SIGKILLed mid-shard, and served from the
    # exact result cache (DESIGN.md §12). --statsd-e2e additionally
    # validates every emitted UDP datagram against the DogStatsD grammar
    python scripts/service_parity.py --preset smoke --windows 3 \
        --spec "hosts:channel=local,n=2,retries=1" --inject-failures \
        --statsd-e2e
    python scripts/service_parity.py --preset transport_grid --windows 3 \
        --spec "hosts:channel=inline,n=2,retries=1" --statsd-e2e
    # scan-engine parity: the scan-over-windows engine's SweepResult JSON
    # must be byte-identical to the sequential fleet engine (DESIGN.md §10)
    python scripts/scan_parity.py --preset smoke --windows 4
    python scripts/scan_parity.py --preset transport_grid --windows 5
    # city-smoke: the 10^5-DC city preset on 8 fake CPU devices, peak
    # memory flat in the window count (DESIGN.md §10)
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
        python scripts/city_smoke.py --fleet-size 100000 --windows 6 \
        --baseline-windows 2 --expect-devices 8
    # churn-smoke: battery-driven DC churn degrades gracefully — depleted
    # mules stop accruing ledger events, F1 stays finite, scan==fleet
    # bitwise under churn (DESIGN.md §13)
    python scripts/churn_smoke.py --windows 6 --battery-mj 25
    # pareto-smoke: successive-halving search recovers the exhaustive
    # frontier exactly, and the frontier metrics are bitwise a plain
    # SweepSpec.run of the frontier configs (DESIGN.md §14)
    python scripts/pareto_smoke.py --windows 6 --seeds 1
fi
