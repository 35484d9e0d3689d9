#!/usr/bin/env bash
# Repo lint gate: tpulint over the source tree with the committed baseline.
# Exits non-zero on any NEW finding (existing debt lives in the baseline).
# Usage: scripts/lint.sh [extra tpulint args...]
set -euo pipefail

cd "$(dirname "$0")/.."

python -m tools.tpulint \
    deepspeed_tpu/ tools/ scripts/ tests/ \
    --baseline .tpulint-baseline.json "$@"

# metric-name <-> docs drift gate: every literal registry.counter/gauge/
# histogram name in the tree must appear in docs/observability.md's metric
# table (tools/tpulint/metricsdoc.py)
python -m tools.tpulint.metricsdoc
