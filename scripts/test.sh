#!/usr/bin/env bash
# Tier-1 gate (the exact command from ROADMAP.md), with an explicit
# collection pass first so import regressions (like the jax shard_map move)
# fail loudly on their own, before any test runs.
#
# The bare full run executes as TWO concurrent file batches: the two
# heaviest files (test_decode ~8 min; test_parallel_2d's 4-device
# subprocess equivalence suite) anchor batch A while every other file runs
# alongside in batch B — roughly halving wall clock without oversubscribing
# the box. Any explicit pytest args fall back to a single serial
# invocation.
#
# Usage:
#   scripts/test.sh              # full tier-1 suite, 2 concurrent batches
#   scripts/test.sh --quick      # tier-0 quick gate (seconds-scale subset)
#   scripts/test.sh -m tier1     # just the tier1-marked core subset
#   scripts/test.sh tests/test_kernels.py -k gbn   # any pytest args
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
# the suite runs on the CPU: its concurrent pytest batches (and the test
# children they start) must never contend for an attached accelerator
export JAX_PLATFORMS=cpu

args=()
for a in "$@"; do
  if [[ "$a" == "--quick" ]]; then
    args+=(-m tier0)
  else
    args+=("$a")
  fi
done

echo "== collect =="
python -m pytest --collect-only -q >/dev/null

echo "== run =="
if [[ ${#args[@]} -eq 0 ]]; then
  # test_analysis rides batch A: its repo-wide gates (lint + kernel
  # contracts + trace audit) compile the hot entry points, which overlaps
  # the decode suite's long pole instead of stretching batch B
  batch_a=(tests/test_decode.py tests/test_parallel_2d.py tests/test_serving_continuous.py tests/test_analysis.py tests/test_fused_kernels.py)
  # batch C: the multi-process jax.distributed tests, under a hard wall
  # clock — a hung coordinator handshake must fail the suite loudly, not
  # wedge it (the in-test subprocess waits have their own timeouts; this
  # is the outer belt-and-braces bound)
  batch_c=(tests/test_distributed.py)
  batch_c_timeout=900
  batch_b=()
  for f in tests/test_*.py; do
    case " ${batch_a[*]} ${batch_c[*]} " in
      *" $f "*) ;;
      *) batch_b+=("$f") ;;
    esac
  done
  log_a=$(mktemp) log_b=$(mktemp) log_c=$(mktemp)
  trap 'rm -f "$log_a" "$log_b" "$log_c"' EXIT
  # repro.obs.trace --label wraps each batch and prints its wall time
  python -m repro.obs --label "batch A" -- \
    python -m pytest -x -q "${batch_a[@]}" >"$log_a" 2>&1 &
  pid_a=$!
  python -m repro.obs --label "batch B" -- \
    python -m pytest -x -q "${batch_b[@]}" >"$log_b" 2>&1 &
  pid_b=$!
  timeout --signal=TERM --kill-after=30 "$batch_c_timeout" \
    python -m repro.obs --label "batch C" -- \
    python -m pytest -x -q "${batch_c[@]}" >"$log_c" 2>&1 &
  pid_c=$!
  rc=0
  wait "$pid_a" || rc=$?
  wait "$pid_b" || rc=$?
  rc_c=0
  wait "$pid_c" || rc_c=$?
  if [[ "$rc_c" -ne 0 ]]; then
    rc=${rc_c}
    if [[ "${rc_c}" -ge 124 ]]; then
      echo "batch C exceeded ${batch_c_timeout}s (distributed init hang?)" >>"$log_c"
    fi
  fi
  echo "== batch A (${batch_a[*]}) =="
  cat "$log_a"
  echo "== batch B (${#batch_b[@]} files) =="
  cat "$log_b"
  echo "== batch C (${batch_c[*]}) =="
  cat "$log_c"
  exit "$rc"
fi
exec python -m pytest -x -q "${args[@]}"
