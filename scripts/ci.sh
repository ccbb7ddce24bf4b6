#!/usr/bin/env bash
# Tier-1 CI: the exact commands the roadmap gates on.
#   1. quantlint — AST rules + jaxpr dtype-flow invariants over src/ (blocking)
#   2. pytest    — the tier-1 test suite
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}
python -m repro.analysis src
python -m pytest -x -q "$@"
