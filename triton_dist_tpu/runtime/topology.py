"""Topology and bandwidth discovery.

TPU-native re-design of the reference's topology queries
(ref: python/triton_dist/utils.py topology helpers +
kernels/nvidia/comm_perf_model.py:51-93, which probe NVLink/NUMA/NIC
through pynvml). On TPU the static topology is fully determined by the
chip generation (ICI link count/bandwidth — `perf_model.CHIPS`) and the
mesh shape; what remains worth *measuring* is the achieved collective
bandwidth per mesh axis, which this module probes with the chain timer
(link contention, dispatch overhead, and XLA scheduling all land in the
measurement, exactly like the reference's measured-NIC path).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.perf_model import (
    ChipSpec,
    detect_chip,
    estimate_ag_ms,
)
from triton_dist_tpu.runtime.utils import chain_timer


@dataclasses.dataclass
class AxisBandwidth:
    size: int
    measured_gbps: Optional[float]  # None when size == 1 (nothing to move)
    model_gbps: float


@dataclasses.dataclass
class Topology:
    chip: ChipSpec
    process_count: int
    devices_per_process: int
    axes: Dict[str, AxisBandwidth]


def measure_axis_bandwidth(
    mesh, axis: str, nbytes: int = 4 << 20, k_hi: int = 11
) -> float:
    """Achieved all-gather algorithm bandwidth (GB/s per device) over one
    mesh axis: bytes received per device / measured time."""
    n = int(mesh.shape[axis])
    assert n > 1
    rows = max(8, nbytes // (128 * 4))
    x = jnp.ones((n * rows, 128), jnp.float32)

    def build(k):
        def per_rank(x):
            def body(_, x):
                g = jax.lax.all_gather(x, axis, tiled=True)
                return (x * (1.0 + 0.0 * g[0, 0])).astype(x.dtype)

            out = jax.lax.fori_loop(0, k, body, x)
            return jnp.sum(out).reshape(1)

        return jax.jit(jax.shard_map(
            per_rank, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
            check_vma=False,
        ))

    ms, _ = chain_timer(build, (x,), k_hi=k_hi, pairs=3, warmup=1)
    recv_bytes = (n - 1) * rows * 128 * 4
    return recv_bytes / (ms * 1e-3) / 1e9


def discover_topology(
    mesh=None, measure: bool = True, nbytes: int = 4 << 20
) -> Topology:
    """The reference's init-time topology report, TPU edition: chip table
    + mesh axes, optionally with measured per-axis bandwidth."""
    chip = detect_chip()
    axes: Dict[str, AxisBandwidth] = {}
    if mesh is not None:
        for name in mesh.axis_names:
            n = int(mesh.shape[name])
            model_ms = estimate_ag_ms(nbytes, n, chip)
            model_gbps = (
                (n - 1) * nbytes / (model_ms * 1e-3) / 1e9
                if n > 1 else 0.0
            )
            measured = None
            if measure and n > 1:
                measured = measure_axis_bandwidth(mesh, name, nbytes)
            axes[name] = AxisBandwidth(n, measured, model_gbps)
    return Topology(
        chip=chip,
        process_count=jax.process_count(),
        devices_per_process=len(jax.local_devices()),
        axes=axes,
    )
