#!/usr/bin/env bash
# tpudl CI gate: static analysis + (optional) ruff + the fast test tier.
#
#   scripts/ci_check.sh            # everything
#   scripts/ci_check.sh --lint-only
#
# Exit nonzero on: new (unbaselined) lint_tpudl findings, ruff
# error-tier findings (when ruff is installed — see [tool.ruff] in
# pyproject.toml), or a fast-tier test failure.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== scripts/lint_tpudl.py (ratcheted static analysis)"
python scripts/lint_tpudl.py

if command -v ruff >/dev/null 2>&1; then
    echo "== ruff check"
    ruff check .
else
    echo "== ruff not installed; skipping (config lives in pyproject.toml)"
fi

if [[ "${1:-}" == "--lint-only" ]]; then
    exit 0
fi

echo "== chaos smoke (serving fault injection: migration, failover, drains)"
JAX_PLATFORMS=${JAX_PLATFORMS:-cpu} python -m pytest tests/ -q -m 'chaos and not slow' \
    -p no:cacheprovider

echo "== fast test tier (tier-1: not slow)"
JAX_PLATFORMS=${JAX_PLATFORMS:-cpu} python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider
