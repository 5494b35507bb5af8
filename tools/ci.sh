#!/usr/bin/env bash
# CI entry point (reference analog: the reference repo's CI pipelines under
# tools/ + paddle_build.sh test stages, with testslist.csv-style run tiers).
#
# Usage:
#   tools/ci.sh quick     per-commit tier: import hygiene + fast unit subset
#                         (-m "not slow"), <3 min on the CI host
#   tools/ci.sh           full gate: everything below
#   tools/ci.sh nightly   full gate + 200-step loss-curve parity vs torch
#
# Stages (full):
#   1. import hygiene: importing paddle_tpu must NOT initialize the XLA
#      backend (jax.distributed would break)
#   1c. tuning plane: block-size resolver precedence/provenance, the JSON
#      tuning cache and the autotuner end to end
#   2. unit suite on the virtual 8-device CPU mesh
#   3. driver multichip gate: 8-device dryrun of the full sharded train step
# Speed is not CI's to judge: the driver measures every PR on the chip
# (BENCHMARK.json, benchmark/run.py, PERF_LEDGER.jsonl).
set -euo pipefail
cd "$(dirname "$0")/.."

TIER="${1:-full}"

echo "== [1] import hygiene =="
python - <<'EOF'
import jax, paddle_tpu
from jax._src import xla_bridge
assert not xla_bridge._backends, "import paddle_tpu initialized the XLA backend"
print("ok: lazy backend")
EOF

echo "== [1b] observability plane (not slow) =="
# the instrument every other gate reads from is verified FIRST: metrics
# registry exposition, trace-id propagation, step telemetry, event journal
python -m pytest tests/test_observability.py -q -m "not slow"

echo "== [1c] tuning plane (not slow) =="
# the block resolver feeds every kernel the later stages compile: its
# precedence, the tuning cache's stale-schema rejection and the autotuner
# are verified first
python -m pytest tests/test_tuning.py -q -m "not slow"

if [ "$TIER" = "quick" ]; then
  echo "== [2] unit suite (quick tier) =="
  # [1b]/[1c] already ran the observability + tuning modules; don't pay
  # their XLA compiles twice per CI run
  python -m pytest tests/ -q -m "not slow" --ignore=tests/test_observability.py --ignore=tests/test_tuning.py
  echo "CI QUICK TIER PASSED"
  exit 0
fi

echo "== [2] unit suite (full) =="
python -m pytest tests/ -q --ignore=tests/test_observability.py --ignore=tests/test_tuning.py

echo "== [3] multichip gate =="
python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

if [ "$TIER" = "nightly" ]; then
  echo "== [5] loss-curve parity (200 steps, fp32 + bf16, vs torch) =="
  PARITY_STEPS=200 PARITY_BF16=1 python -m pytest tests/test_loss_parity.py -q
  echo "== [6] parallel-mode loss parity (200 steps, dp/mp/pp/zero2) =="
  PARALLEL_PARITY_STEPS=200 python -m pytest tests/test_parallel_parity.py -q
fi

echo "CI PASSED"
