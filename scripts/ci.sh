#!/usr/bin/env bash
# CI entry point.
#
# Stage 1 — lint gate (seconds, before anything imports jax-heavy code):
#   * `python -m repro.lint` must exit 0 on the repo (the standing
#     architectural rules as AST checks — see docs/lint.md);
#   * it must exit 1 on the seeded violation fixtures, proving every rule
#     still fires (a linter that stopped firing would pass CI silently);
#   * no __pycache__/.pyc path may be git-tracked.
#
# Stage 2 — fail-fast import gate: `pytest --collect-only` imports every
# test module in seconds, so a collection-time ImportError (bad import,
# missing dep, jax API drift not absorbed by repro/compat.py) fails
# immediately instead of after the ~8-minute tier-1 suite. The gate also
# covers the non-pytest trees: `benchmarks/` is imported for real (its
# modules are import-safe), `examples/` is byte-compiled only (example
# scripts run work at module level, so importing them would launch sims).
#
# Stage 3 — the tier-1 suite itself (ROADMAP "Tier-1 verify").
#
# Stage 4 — obs smoke: runs a tiny *instrumented* simulation that
# emits a RunRecord JSONL + Chrome trace into a mktemp dir (OBS_SMOKE_DIR —
# never under runs/, so CI can't clobber real run records), then invokes
# `python -m repro.obs.report` on the emitted file; the report CLI exits
# non-zero on any RunRecord schema violation.
#
# Stage 5 — sharded smoke: forces 8 host devices (XLA_FLAGS, which must be
# set before the JAX import — hence a fresh interpreter) and asserts the
# client-mesh placement matches one device on all six methods over a real
# 4-device ("clients",) mesh.
#
# Stage 6 — HLO invariants: `python -m repro.lint.hlo` lowers + compiles a
# round block for all six methods on both placements (fused and sharded)
# and checks the compiled artifacts (no host callbacks, donated carry,
# rounds scanned inside, collectives ride the scan at while-depth <= 1
# with one peer gather per round, no f64 under x64-off).
#
# Tests are offline by policy: the property tests run on the vendored
# deterministic engine (src/repro/testing) unless a real `hypothesis`
# happens to be installed.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
# pin the backend: every test in this suite targets host devices (unpinned,
# jax tries the installed libtpu and falls back to the CPU silently)
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

echo "== stage 1/6: lint gate (source rules + fixtures + tracked-pyc) =="
python -m repro.lint
if python -m repro.lint tests/fixtures/lint > /dev/null 2>&1; then
    echo "== lint gate FAILED: the violation fixtures no longer fire =="
    exit 2
fi
if git ls-files | grep -E '(__pycache__|\.pyc$)' ; then
    echo "== lint gate FAILED: __pycache__/.pyc paths are git-tracked =="
    exit 2
fi

echo "== stage 2/6: import gate (tests collect, benchmarks import, examples compile) =="
# quiet on success (the full collected-test list is noise), but surface
# pytest's collection errors when the gate trips
gate_log="$(mktemp)"
trap 'rm -f "$gate_log"' EXIT
if ! python -m pytest --collect-only -q tests/ > "$gate_log" 2>&1; then
    cat "$gate_log"
    echo "== import gate FAILED: fix collection errors above =="
    exit 2
fi
python -c "import benchmarks.run"   # pulls in every registered benchmark
python -m py_compile examples/*.py  # examples execute on import: compile only

rm -f "$gate_log"
trap - EXIT

echo "== stage 3/6: tier-1 suite =="
python -m pytest -x -q "$@"

echo "== stage 4/6: obs smoke (instrumented run + RunRecord report) =="
OBS_SMOKE_DIR="$(mktemp -d)"
export OBS_SMOKE_DIR
trap 'rm -rf "$OBS_SMOKE_DIR"' EXIT
python -m benchmarks.run --only obs_smoke
python -m repro.obs.report "$OBS_SMOKE_DIR/obs_smoke.jsonl"

echo "== stage 5/6: sharded smoke (client mesh on forced host devices) =="
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m benchmarks.run --only fedsim_sharded_smoke

echo "== stage 6/6: HLO invariants (six methods x fused/sharded) =="
python -m repro.lint.hlo --engine both --devices 4
