#!/usr/bin/env bash
# Known-good CPU environment for repro runs (the CI smoke solves), so runs
# are reproducible across machines and CI:
#
#   scripts/run.sh -m repro.launch.solve --mesh 48 48 32
#   scripts/run.sh -m repro.launch.cfd --scenario cavity --n 24 --backend spmd
#
# The chip benchmark is `python3 bench/run.py --workload <cell>` (PERF.md).
#
# Pins: tcmalloc (when installed) — thread-friendly malloc, matters for
# interpret-mode Pallas; quiet TF/XLA logging; a fixed fake-device
# count so shard_map fabrics are reproducible; PYTHONPATH=src.  Set
# REPRO_X64=1 to enable float64 (the f64 policy path); REPRO_DEVICES to
# change the host-platform device count (default 8: the 2x2x2 test fabric).
set -euo pipefail
cd "$(dirname "$0")/.."

for so in /usr/lib/x86_64-linux-gnu/libtcmalloc.so.4 \
          /usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4; do
  if [[ -e "$so" ]]; then
    export LD_PRELOAD="$so"  # faster malloc
    break
  fi
done

export TF_CPP_MIN_LOG_LEVEL="${TF_CPP_MIN_LOG_LEVEL:-4}"
export XLA_FLAGS="${XLA_FLAGS:---xla_force_host_platform_device_count=${REPRO_DEVICES:-8}}"
if [[ "${REPRO_X64:-0}" == "1" ]]; then
  export JAX_ENABLE_X64=1
fi
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

exec python "$@"
