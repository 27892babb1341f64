#!/usr/bin/env bash
# Tier-1 verification: run the FULL test suite. The seed_known_failure set
# (tests/conftest.py) is empty, so that marker filter deselects nothing. The
# suite is not fully green on the installed JAX (0.9.0): the three
# small-mesh tests of tests/test_launch.py fail (see tests/conftest.py).
#
#   scripts/tier1.sh            full tier-1 suite (the PR gate)
#   scripts/tier1.sh --fast     developer loop: deselect the `slow`-marked
#                               multi-minute association/launch tests
#
# Marker hygiene (tests/_marker_hygiene.py): tier-1 exports
# TIER1_SLOW_MARKER_LIMIT_S (default 30) so any unmarked test that crosses
# the limit FAILS — the fast tier stays fast as the suite grows. Unknown
# markers fail collection via --strict-markers, and --durations prints the
# slowest tests so creep is visible before it crosses the limit. Override
# the limit (or disable with 0) by exporting the variable yourself.
#
# Extra pytest arguments pass through, e.g.
# `scripts/tier1.sh tests/test_assoc_fast.py`.
set -euo pipefail
cd "$(dirname "$0")/.."
MARKER="not seed_known_failure"
if [[ "${1:-}" == "--fast" ]]; then
    MARKER="$MARKER and not slow"
    shift
fi
export TIER1_SLOW_MARKER_LIMIT_S="${TIER1_SLOW_MARKER_LIMIT_S:-30}"
# hfellint gate: the static-analysis pass (scripts/lint.py, rules in
# src/repro/analysis/rules.py) must report zero findings beyond
# lint_baseline.json before any tests run — in --fast mode too. It is
# jax-free and finishes in ~2s; see experiments/lint_rules.md.
python scripts/lint.py --check
# Pin a fixed host-device count so the shard_map sweep tests
# (tests/test_assoc_sharded.py) see a deterministic 4-device mesh on this
# CPU container; must be set before jax first imports.
export XLA_FLAGS="--xla_force_host_platform_device_count=4${XLA_FLAGS:+ $XLA_FLAGS}"
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m pytest -q -m "$MARKER" --strict-markers --durations=15 "$@"
