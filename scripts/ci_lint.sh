#!/usr/bin/env bash
# CI lint gate (tier-1: tests/test_lint.py::test_ci_lint_script).
#
# Five legs, all of which must hold or the gate fails:
#   1. self-analysis  — hvd-lint --self: every rule (HVD2xx + HVD3xx +
#      the interprocedural HVD4xx + the simulated HVD5xx + the perf
#      HVD6xx) over horovod_tpu/ itself plus the knob-registry and
#      metric-registry docs cross-checks (HVD306/HVD307), failing on
#      warnings.
#   2. dogfood sweep  — hvd-lint verify over examples/, failing on
#      warnings: the shipped entry points stay clean (the schedule
#      simulator included — zero HVD5xx).
#   3. canary corpus  — the fixture corpus must still TRIP every rule
#      family (a gate that stopped seeing its fixtures has rotted),
#      including the simulator's proven HVD501/502 and the new
#      protocol-order HVD704/705, and its findings are emitted as
#      lint.sarif for the CI artifact/code-scanning upload.
#   4. perf canary    — hvd-lint perf stays zero-false-positive over
#      examples/ at fail-on-warning, while the perf fixture
#      corpus (with its checked-in calibration table) still trips
#      every HVD6xx rule; findings land in perf.sarif.
#   5. model check    — hvd-model explores the bounded state space of
#      all three control-plane protocols (HA terms, fleet leases, KV
#      migration) with crash/loss/dup/reorder injection inside a hard
#      wall-clock budget: the shipped specs must come back complete
#      with zero counterexamples (model.sarif), and the seeded
#      mutations (lease actuate_before_ledger, migration
#      double_import) must each still produce a minimized HVD701
#      counterexample — a checker that stopped seeing its mutants has
#      rotted.
#
# Every SARIF artifact is structurally gated by ONE shared validator
# (python -m horovod_tpu.analysis.sarif) instead of per-leg ad-hoc
# scripts. Each leg reports its analysis wall time; within one
# hvd-lint invocation the AST, verify, simulate, and cost-model layers
# share one parsed corpus and one call-graph fixpoint
# (analysis/ast_lint.py parse_cached), so the gate's cost is one
# corpus build per leg, not one per layer.
#
# Env: LINT_SARIF_OUT / PERF_SARIF_OUT / MODEL_SARIF_OUT override the
# artifact paths (defaults: lint.sarif / perf.sarif / model.sarif in
# the repo root). HVDTPU_LINT_BASELINE is honored by hvd-lint itself
# (see docs/lint.md "Baselines").
set -euo pipefail
cd "$(dirname "$0")/.."

sarif_out="${LINT_SARIF_OUT:-lint.sarif}"
perf_sarif_out="${PERF_SARIF_OUT:-perf.sarif}"
model_sarif_out="${MODEL_SARIF_OUT:-model.sarif}"
python="${PYTHON:-python3}"
command -v "${python}" >/dev/null 2>&1 || python=python
run_lint() { "${python}" -m horovod_tpu.analysis.cli "$@"; }
run_model() { "${python}" -m horovod_tpu.analysis.protocol.cli "$@"; }
check_sarif() { "${python}" -m horovod_tpu.analysis.sarif "$@"; }
leg_t0=0
leg_start() { leg_t0=${SECONDS}; }
leg_done() { echo "-- leg wall time: $((SECONDS - leg_t0))s"; }

echo "== hvd-lint: self-analysis (HVD2xx/3xx/4xx/5xx + knob/metric docs) =="
leg_start
run_lint --self --check-knobs --check-metrics
leg_done

echo "== hvd-lint verify: examples/ (fail on warnings) =="
leg_start
run_lint verify examples --fail-on warning
leg_done

echo "== hvd-lint verify: fixture corpus -> ${sarif_out} =="
# --fail-on never: the corpus is SUPPOSED to be full of findings; the
# validator below asserts they are all still being caught. Proven
# HVD501/502 findings must ship their counterexample — one threadFlow
# per symbolic rank.
leg_start
run_lint verify tests/lint_fixtures --format sarif --fail-on never \
    > "${sarif_out}"
leg_done
check_sarif "${sarif_out}" \
    --require-family HVD2 --require-family HVD3 \
    --require-family HVD4 --require-family HVD5 \
    --require-rule HVD210 --require-rule HVD211 \
    --require-rule HVD212 --require-rule HVD213 \
    --require-rule HVD401 --require-rule HVD402 \
    --require-rule HVD403 --require-rule HVD404 \
    --require-rule HVD405 --require-rule HVD501 \
    --require-rule HVD502 --require-rule HVD503 \
    --require-rule HVD704 --require-rule HVD705 \
    --require-flows HVD501:2 --require-flows HVD502:2

echo "== hvd-lint perf: examples/ (zero HVD6xx FPs) =="
leg_start
run_lint perf examples --fail-on warning
leg_done

echo "== hvd-lint perf: fixture corpus -> ${perf_sarif_out} =="
# --fail-on never: the perf corpus is SUPPOSED to trip HVD6xx; the
# validator asserts every rule in the family is still caught and that
# the clean/suppressed fixtures stayed quiet.
leg_start
run_lint perf tests/lint_fixtures/perf \
    --table tests/lint_fixtures/perf/costmodel_table.json \
    --format sarif --fail-on never > "${perf_sarif_out}"
leg_done
check_sarif "${perf_sarif_out}" \
    --require-rule HVD601 --require-rule HVD602 \
    --require-rule HVD603 --forbid-location good_perf

echo "== hvd-model: protocol state spaces (HA/lease/migration) -> ${model_sarif_out} =="
# The shipped specs must explore to completion with zero
# counterexamples inside the budget; an incomplete exploration emits
# HVD703 (a warning) and hvd-model exits 1 at the default
# --fail-on warning, so a budget overrun fails the gate loudly.
leg_start
run_model --protocol all --budget-s 25 --format sarif \
    > "${model_sarif_out}"
check_sarif "${model_sarif_out}" --expect-none
# Mutation canaries: each seeded historical bug must still produce a
# minimized safety counterexample (HVD701) — run them into throwaway
# artifacts and assert the violation IS found (exit 1) with the right
# rule in the output.
mutant_sarif="$(mktemp)"
trap 'rm -f "${mutant_sarif}"' EXIT
if run_model --protocol lease --seed-bug actuate_before_ledger \
        --format sarif > "${mutant_sarif}"; then
    echo "ci_lint: seeded lease bug produced no counterexample" >&2
    exit 1
fi
check_sarif "${mutant_sarif}" --require-rule HVD701
if run_model --protocol migration --seed-bug double_import \
        --format sarif > "${mutant_sarif}"; then
    echo "ci_lint: seeded migration bug produced no counterexample" >&2
    exit 1
fi
check_sarif "${mutant_sarif}" --require-rule HVD701
leg_done

echo "ci_lint: all gates green (artifacts: ${sarif_out}, ${perf_sarif_out}, ${model_sarif_out})"
