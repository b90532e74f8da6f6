#!/usr/bin/env bash
# Regenerate BENCH_serving.json (serving throughput + prefix-cache
# benchmark) on whatever backend JAX finds (set JAX_PLATFORMS=cpu for
# the CPU); run from anywhere.
set -euo pipefail
cd "$(dirname "$0")/.."
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.serving "$@"
