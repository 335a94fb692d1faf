#!/usr/bin/env bash
# Production launcher for the coreset server: process-level runtime hygiene
# that cannot be set from inside Python, then exec the real entrypoint.
#
#   scripts/run.sh [serve_coresets args...]
#
# What it sets (all overridable from the caller's environment):
#
#   LD_PRELOAD=libtcmalloc          glibc malloc fragments badly under the
#                                   allocate-free churn of per-request numpy
#                                   buffers; tcmalloc's thread caches also
#                                   cut lock contention in the worker pool.
#                                   Skipped with a notice when absent.
#   TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD
#                                   raise the report threshold so routine
#                                   multi-GB SAT allocations do not spam
#                                   stderr on every large signal.
#   TF_CPP_MIN_LOG_LEVEL=4          silence the XLA/TSL C++ banner noise on
#                                   every worker boot.
#   JAX_COMPILATION_CACHE_DIR       persistent jit cache across restarts;
#                                   defaults to .jax_cache in the checkout
#                                   (a fixed path: it is part of the key).
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export TF_CPP_MIN_LOG_LEVEL="${TF_CPP_MIN_LOG_LEVEL:-4}"
export TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD="${TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD:-17179869184}"
export JAX_COMPILATION_CACHE_DIR="${JAX_COMPILATION_CACHE_DIR:-$PWD/.jax_cache}"

if [ -z "${LD_PRELOAD:-}" ]; then
  for so in /usr/lib/x86_64-linux-gnu/libtcmalloc.so.4 \
            /usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4 \
            /usr/lib/libtcmalloc.so.4 \
            /opt/conda/lib/libtcmalloc.so.4; do
    if [ -e "$so" ]; then
      export LD_PRELOAD="$so"
      break
    fi
  done
  if [ -z "${LD_PRELOAD:-}" ]; then
    echo "[run.sh] tcmalloc not found: serving with glibc malloc" >&2
  fi
fi

exec python -m repro.launch.serve_coresets "$@"
