#!/usr/bin/env bash
# CI entry point. Two modes:
#
#   bash scripts/ci.sh              # main: docs-drift + tier-1 tests
#                                   # (+ coverage when pytest-cov is
#                                   # installed) + benchmark smoke +
#                                   # benchmark-regression guard
#   bash scripts/ci.sh conformance  # deflake audit (fast tier under a
#                                   # deterministic shuffled order) +
#                                   # budgeted config-space differential
#                                   # fuzz (repro.conformance.fuzz);
#                                   # violation artifacts land in
#                                   # conformance-artifacts/ for upload
#
# Knobs:
#   BENCH_GUARD=hard|soft|off   benchmark guard mode (default hard)
#   PYTEST_ORDER_SEED=<n>       shuffled-order seed for the deflake leg
#                               (conformance mode; default 1, CI passes
#                               the run id so every run tries a fresh
#                               order that stays replayable from logs)
#   CONF_FUZZ_SEEDS=<n>         fuzz budget in sampled configs (def 10)
#   REPRO_COV_FLOOR / REPRO_COV_HARD   see scripts/coverage_floor.py
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}
# 8 virtual CPU devices so the sharded flat-engine tests exercise a real
# (data, model) mesh (tests/test_flat.py needs8 cases + `sharded` bench)
export XLA_FLAGS="${XLA_FLAGS:---xla_force_host_platform_device_count=8}"

MODE="${1:-main}"

if [ "$MODE" = "conformance" ]; then
    # deflake audit: the fast tier must pass in a shuffled order too —
    # any difference vs the default order is an inter-test dependency
    PYTEST_ORDER_SEED="${PYTEST_ORDER_SEED:-1}" \
        python -m pytest -x -q -m "not slow"
    # budgeted differential fuzz over the conformance config space; the
    # regression corpus (seeds 0..21 + pinned) already ran in tier-1
    # above, so start the budget past it for fresh configs
    python -m repro.conformance.fuzz \
        --start 1000 --seeds "${CONF_FUZZ_SEEDS:-10}" \
        --out conformance-artifacts
    exit 0
fi

# docs drift: the scenario table in docs/SCENARIOS.md and the metric
# table in docs/TELEMETRY.md are generated from the SCENARIOS /
# telemetry.schema registries — regenerate and fail on any diff
python scripts/gen_docs.py
git diff --exit-code -- docs/

# coverage rides along when pytest-cov is installed (CI installs it;
# the dev container may not have it — the tier runs identically bare)
COV_ARGS=()
if python -c "import pytest_cov" 2>/dev/null; then
    COV_ARGS=(--cov=repro --cov-report=json:coverage.json
              --cov-report=term:skip-covered)
fi

# fast tier first (-m "not slow"), then the slow tail — a broken fast
# test fails CI before the multi-round/mesh-heavy tests even start
python -m pytest -x -q -m "not slow" "${COV_ARGS[@]}"
python -m pytest -x -q -m slow
if [ "${#COV_ARGS[@]}" -gt 0 ]; then
    python scripts/coverage_floor.py coverage.json
fi
python -m benchmarks.run \
    --only kernels,sharded,scenarios,compression,faults,rounds_fused,fleet,telemetry,serving \
    --quick
python -m benchmarks.compare bench_results.csv benchmarks/baseline.json \
    --mode "${BENCH_GUARD:-hard}"
