"""Core Pallas helpers: backend-aware pallas_call, tiling utilities.

This is the foundation of the device-side language layer
(ref: python/triton_dist/language/core.py). Every kernel in the framework is
built through `tpu_call`, which compiles natively on TPU and transparently
switches to Pallas TPU interpret mode on CPU so the full kernel library —
including inter-chip remote DMA — runs on a virtual
`--xla_force_host_platform_device_count` mesh for testing.
"""

from __future__ import annotations

import collections
import functools
import math
import os
import re
import zlib
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_FORCE_INTERPRET = os.environ.get("TDT_FORCE_INTERPRET", "") == "1"


@functools.lru_cache(maxsize=None)
def backend_device():
    """The device every backend-dependent decision is read from: which
    platform kernels compile for (backend_platform), which chip the
    perf model prices (perf_model.detect_chip), how many TensorCores
    the megakernel may use (mega.kernel.physical_core_count)."""
    return jax.devices()[0]


@functools.lru_cache(maxsize=None)
def backend_platform() -> str:
    return backend_device().platform


def use_interpret() -> bool:
    """True when Pallas TPU kernels must run in interpreter mode (CPU mesh)."""
    return _FORCE_INTERPRET or backend_platform() != "tpu"


# Counts every Pallas kernel constructed through tpu_call. Lets tests and
# the driver dryrun assert the real protocol kernels were traced rather
# than silently rerouted to XLA fallbacks (a fail-open here previously made
# the whole fused-vs-ref suite vacuous).
_PALLAS_CALLS = 0


def pallas_call_count() -> int:
    return _PALLAS_CALLS


_HLO_KERNEL = re.compile(
    r'%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*'
    r'custom_call_target="tpu_custom_call"')


def pallas_kernels_in(hlo_text: str) -> dict:
    """{kernel name: count} of the Mosaic kernels in a COMPILED
    program's text (`compiled.as_text()`): XLA names each
    `tpu_custom_call` instruction after the call's innermost name
    scope, which `tpu_call` sets to the kernel's name. Counting traces
    (pallas_call_count) says a kernel was BUILT; this says it is in the
    program the device runs — what separates a kernel run from a route
    that gave way to XLA after tracing."""
    return dict(sorted(collections.Counter(
        _HLO_KERNEL.findall(hlo_text)).items()))


# Conformance-recording hook (verify/conform.py installs this at import;
# lang stays free of any verify import). With no recording active the
# hook returns None and tpu_call takes its unmodified path — the
# zero-cost-off contract the conform tests pin.
_CONFORM_INSTRUMENT = None


def tpu_call(kernel, **kwargs):
    """pl.pallas_call with automatic interpret-mode fallback off-TPU.

    Every kernel is NAMED — `name=` or, by default, its body function's
    own name (functools.partial unwrapped), so a name found in a
    compiled program or a profiler trace greps straight to its `def`.
    The name scopes the call's op metadata, which is how chip_smoke.py
    and tests/test_chip_compile.py tell a kernel that ran from a route
    that quietly gave way to XLA (pallas_kernels_in)."""
    global _PALLAS_CALLS
    _PALLAS_CALLS += 1
    if "name" not in kwargs:
        body = kernel
        while isinstance(body, functools.partial):
            body = body.func
        kwargs["name"] = body.__name__
    if use_interpret() and "interpret" not in kwargs:
        kwargs["interpret"] = pltpu.InterpretParams()
    if _CONFORM_INSTRUMENT is not None:
        instrumented = _CONFORM_INSTRUMENT(kernel, kwargs)
        if instrumented is not None:
            return instrumented
    return pl.pallas_call(kernel, **kwargs)


def interpret_no_headroom() -> bool:
    """True when interpret-mode Pallas kernels that block across devices
    must not be used because the host has no spare executor threads.

    XLA:CPU sizes its thunk-executor pool by the virtual device count, and
    interpret-mode kernels block pool threads inside callbacks (semaphore
    waits; operand materialization). When the surrounding mesh occupies
    every virtual device, those blocked callbacks exhaust the pool, pending
    compute starves, and cross-device-blocking kernels deadlock. Kernels
    consult this to route to their XLA-collective fallback instead — the
    result is identical, only the overlap protocol is skipped. This is what
    keeps `__graft_entry__.dryrun_multichip` (driver sets device count ==
    mesh size) deadlock-free while the test suite (12 virtual devices,
    8-device meshes) still exercises the real protocols.
    """
    if not use_interpret():
        return False
    m = jax.sharding.get_abstract_mesh()
    if m is not None and m.shape:
        mesh_total = math.prod(m.shape.values())
        return mesh_total >= len(jax.devices())
    # Unknown mesh under interpret mode: the safe default is the
    # non-blocking XLA path (a wrong False here deadlocks; a wrong True
    # only skips the overlap protocol).
    return True


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


def fit_tile(tile: int, dim: int) -> int:
    """Largest divisor of dim that is <= tile, preferring lane multiples
    (shared tile-fitting rule of the blocked GEMM kernels). The search
    walks lane multiples only: a request that is not one itself (the
    silu_pair half-tile 3200 // 2 = 1600) must not step through 1472,
    ..., 192 and hand Mosaic a block whose last dim is not a multiple
    of 128."""
    t = min(tile, dim)
    if t > 128 and dim % t:
        t -= t % 128
    while t > 128 and dim % t:
        t -= 128
    while dim % t:
        t //= 2
    return max(t, 1)


def min_tile(dtype) -> tuple:
    """Minimum (sublane, lane) tile for a dtype on TPU."""
    d = jnp.dtype(dtype)
    if d.itemsize == 4:
        return (8, 128)
    if d.itemsize == 2:
        return (16, 128)
    return (32, 128)


def compute_vmem_bytes(*shaped) -> int:
    """Sum byte sizes of (shape, dtype) pairs or arrays, for vmem_limit."""
    total = 0
    for s in shaped:
        if hasattr(s, "shape") and hasattr(s, "dtype"):
            shape, dtype = s.shape, s.dtype
        else:
            shape, dtype = s
        total += math.prod(shape) * jnp.dtype(dtype).itemsize
    return total


_COLLECTIVE_IDS: dict = {}


def next_collective_id(name: str) -> int:
    """Stable collective_id per kernel name.

    Mosaic requires every collective pallas_call to carry an id agreed on by
    all devices; ids key the shared barrier semaphore. The id is derived
    from the *name alone* (crc32), never from call order, so multi-controller
    processes that trace extra rank-local programs still agree. Cross-name
    collisions are detected per process and are a hard error (two distinct
    collectives sharing a barrier semaphore could race if XLA overlaps
    them)."""
    if name not in _COLLECTIVE_IDS:
        # int16 space: the Pallas interpreter stores collective ids as int16.
        cid = zlib.crc32(name.encode()) & 0x7FFF
        for other, oid in _COLLECTIVE_IDS.items():
            if oid == cid:
                raise RuntimeError(
                    f"collective_id collision: {name!r} and {other!r} both "
                    f"hash to {cid}; rename one kernel"
                )
        _COLLECTIVE_IDS[name] = cid
    return _COLLECTIVE_IDS[name]


def cost_estimate(flops: int = 0, bytes_accessed: int = 0,
                  remote_bytes: int = 0) -> "pl.CostEstimate":
    """Kernel cost metadata — the reference's `launch_metadata` flops/
    bytes reporting (ref: allgather_gemm.py:145-155) — consumed by the
    XLA scheduler and surfaced in profiles."""
    return pl.CostEstimate(
        flops=int(flops), bytes_accessed=int(bytes_accessed),
        transcendentals=0, remote_bytes_transferred=int(remote_bytes),
    )


def compiler_params(
    has_side_effects: bool = False,
    collective_id: Optional[int] = None,
    vmem_limit_bytes: Optional[int] = None,
    **kw: Any,
):
    args: dict = dict(kw)
    if has_side_effects:
        args["has_side_effects"] = True
    if collective_id is not None:
        args["collective_id"] = collective_id
    if vmem_limit_bytes is not None:
        args["vmem_limit_bytes"] = vmem_limit_bytes
    return pltpu.CompilerParams(**args)
