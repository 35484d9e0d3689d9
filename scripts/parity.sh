#!/usr/bin/env bash
# Serving kernel-path parity gate: the Pallas paged-attention kernels
# (interpret mode) against the jnp references, plus the served-tokens vs
# plain-forward acceptance smoke — run under the tier-1 marker set so CI's
# gate trio covers the serving hot path even when the full suite is not in
# the loop. Usage: scripts/parity.sh [extra pytest args...]
set -euo pipefail

cd "$(dirname "$0")/.."

JAX_PLATFORMS=cpu python -m pytest \
    tests/kernels/test_paged_attention.py \
    "tests/unit/test_serving.py::TestPagedReadOracle" \
    -q -m 'not slow' -p no:cacheprovider "$@"
