#!/usr/bin/env bash
# CI entrypoint: the tier-1 tests.  Speed is measured on the chip by
# bench/ (BENCHMARK.json), never here.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}

python -m pytest -x -q
