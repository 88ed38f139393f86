#!/usr/bin/env bash
# Pre-snapshot gate: everything here must pass before an end-of-round commit.
# (Round-2 postmortem: the snapshot was committed with a failing test and a
# kernel that could not lower on TPU — this script makes that impossible.)
#
# Usage: scripts/gate.sh [--full]
#   default: raylint + full pytest (slow tests too) + 8-device multichip
#            dryrun + one benchmark cell rehearsed on the CPU
#   --full:  additionally runs chip_smoke.py (needs a TPU; a few minutes)

set -uo pipefail
cd "$(dirname "$0")/.."
FAIL=0

step() {
  echo "=== gate: $1"
  shift
  if ! "$@"; then
    echo "!!! gate FAILED: $1"
    FAIL=1
  fi
}

# Incremental raylint: per-file results cached under .raylint_cache/
# keyed by content hash (an absent or stale cache is the cold-run
# fallback — same findings, just slower). The cold leg runs against a
# THROWAWAY cache dir so the printed cold/warm ratio is honest on every
# gate, not only the first (the persistent cache would otherwise make
# both legs warm); --timings keeps a slow rule visible before it bloats
# this step. The unused-suppression audit rides along so a stale
# `# raylint: disable=` comment fails the gate too.
step "raylint (incremental + suppression audit)" bash -c '
  coldcache=$(mktemp -d)
  t0=$(date +%s%N)
  python -m ray_tpu.analysis ray_tpu/ --incremental --cache-dir "$coldcache" \
      --timings --report-unused-suppressions || exit 1
  t1=$(date +%s%N)
  python -m ray_tpu.analysis ray_tpu/ --incremental --cache-dir "$coldcache" \
      || exit 1
  t2=$(date +%s%N)
  rm -rf "$coldcache"
  # Refresh the persistent cache too (steady-state warm for local runs).
  python -m ray_tpu.analysis ray_tpu/ --incremental >/dev/null 2>&1
  cold_ms=$(( (t1 - t0) / 1000000 )); warm_ms=$(( (t2 - t1) / 1000000 ))
  ratio=$(( warm_ms * 100 / (cold_ms > 0 ? cold_ms : 1) ))
  echo "raylint wall: cold ${cold_ms}ms, warm ${warm_ms}ms (${ratio}% of cold)"
  # Acceptance bound: the warm incremental run (per-file results cached,
  # project rules re-joined over cached summaries — now including the
  # RL020-RL024 dataflow extracts) must stay under 25% of cold.
  if (( ratio >= 25 )); then
    echo "raylint warm run is ${ratio}% of cold (must be <25%)"
    exit 1
  fi
'
step "pytest tests/" python -m pytest tests/ -q
step "multichip dryrun (8 virtual devices)" \
  env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python __graft_entry__.py 8
# One cell of the benchmark end to end at rehearsal sizes, on the CPU: the
# harness, a builder, the check against the reference and the result line.
# It prints no metric value; numbers come from a chip (PERF.md §1).
step "benchmark rehearsal (one cell, CPU, no numbers)" \
  python3 benchmarks/run.py --workload train_gpt2m_1chip --seed 1 \
  --seconds 2 --rehearsal
if [[ "${1:-}" == "--full" ]]; then
  # The train and serve main paths on the chip: it asserts by itself that
  # every attention call compiled to the Pallas kernels, that they agree
  # with the XLA reference on the device, and that no leg ran on the CPU.
  step "chip_smoke.py (real chip)" python chip_smoke.py
fi

if [[ $FAIL -ne 0 ]]; then
  echo "GATE: FAILED"
  exit 1
fi
echo "GATE: OK"
