"""triton_dist_tpu — a TPU-native distributed overlapping-kernel framework.

A from-scratch JAX/XLA/Pallas re-design of the capabilities of
Triton-distributed (ByteDance Seed): device-side communication primitives
(wait/notify/put/get/signal over semaphores + async remote DMA on ICI),
a library of computation-communication overlapping kernels (AG+GEMM,
GEMM+RS, AllReduce, GEMM+AR, low-latency MoE AllToAll, EP dispatch/combine,
sequence-parallel AG attention, distributed flash-decode), TP/SP/EP/PP model
layers, an end-to-end LLM inference engine, a single-persistent-kernel
"megakernel" scheduler, contextual autotuning and AOT export.

Layer map (mirrors reference SURVEY.md table; reference = Triton-distributed):
  runtime/   - host runtime: mesh init, symmetric buffers, profiling
               (ref: python/triton_dist/utils.py)
  lang/      - device-side primitive layer usable inside Pallas kernels
               (ref: python/triton_dist/language/, libshmem_device)
  kernels/   - overlapping collective + compute kernels
               (ref: python/triton_dist/kernels/nvidia/)
  trace/     - in-kernel event tracing, stall attribution, Perfetto
               export (ref: the intra-kernel profiler hooks;
               docs/observability.md)
  obs/       - always-on telemetry: metrics registry, O(1) in-kernel
               stat rows, flight recorder, SLO health, exporters
               (docs/observability.md)
Subpackages under construction land here as they are built (layers/,
models/, megakernel/, tools/, csrc/ in the reference's inventory).
"""

__version__ = "0.1.0"

from triton_dist_tpu.runtime import (  # noqa: F401
    initialize_distributed,
    get_default_mesh,
    set_default_mesh,
    finalize_distributed,
)
