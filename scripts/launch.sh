#!/usr/bin/env bash
# Launch a triton_dist_tpu program on TPU hardware or a virtual CPU mesh.
#
# TPU-native re-design of the reference's launcher
# (ref: scripts/launch.sh — torchrun + NVSHMEM env hygiene: UID bootstrap
# :137-139, CUDA_DEVICE_MAX_CONNECTIONS=1 :128, symmetric heap size :133,
# sanitizer hook :160-163). On TPU there is no per-process rendezvous for
# a single slice: one controller process drives every chip. Multi-host
# slices rendezvous through jax.distributed, driven here by env vars
# (runtime/init.py:_maybe_init_multihost reads them).
#
# Usage:
#   ./scripts/launch.sh prog.py [args...]              # real TPU
#   TDT_VIRTUAL_DEVICES=8 ./scripts/launch.sh prog.py  # CPU mesh (dev)
#
# Multi-host (run on every host of the slice/pod):
#   TDT_COORDINATOR=host0:8476 TDT_NUM_PROCESSES=4 TDT_PROCESS_ID=$i \
#     ./scripts/launch.sh prog.py
set -euo pipefail

REPO_ROOT="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
export PYTHONPATH="${REPO_ROOT}${PYTHONPATH:+:}${PYTHONPATH:-}"

# --- env hygiene (the CUDA_DEVICE_MAX_CONNECTIONS / NVSHMEM_* analog) ---
# one compilation cache across runs, placed by the same rule as
# runtime.enable_compile_cache: the caller's JAX_COMPILATION_CACHE_DIR
# if set, else the fixed git-ignored directory inside the checkout
export JAX_COMPILATION_CACHE_DIR="${JAX_COMPILATION_CACHE_DIR:-${REPO_ROOT}/.jax_cache}"
# deterministic kernel math unless the caller overrides
export XLA_FLAGS="${XLA_FLAGS:-} --xla_tpu_enable_latency_hiding_scheduler=true"

# --- virtual CPU mesh for development without a slice ---
if [[ -n "${TDT_VIRTUAL_DEVICES:-}" ]]; then
  # +4 spares: interpret-mode kernels block executor threads (conftest.py)
  export XLA_FLAGS="${XLA_FLAGS} --xla_force_host_platform_device_count=$((TDT_VIRTUAL_DEVICES + 4))"
  export JAX_PLATFORMS=cpu
fi

# --- multi-host rendezvous (read by runtime/init.py) ---
if [[ -n "${TDT_COORDINATOR:-}" ]]; then
  export JAX_COORDINATOR_ADDRESS="${TDT_COORDINATOR}"
  export JAX_NUM_PROCESSES="${TDT_NUM_PROCESSES:?set TDT_NUM_PROCESSES}"
  export JAX_PROCESS_ID="${TDT_PROCESS_ID:?set TDT_PROCESS_ID}"
fi

exec python "$@"
