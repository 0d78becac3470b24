"""Distributed bring-up and mesh management.

TPU-native re-design of the reference's `initialize_distributed`
(ref: python/triton_dist/utils.py:182-205): where the reference bootstraps
torch.distributed + NVSHMEM symmetric heaps over NCCL/gloo, on TPU the
"transport" is the ICI/DCN fabric already owned by the XLA runtime, so
bring-up reduces to (a) optional multi-host jax.distributed init and
(b) constructing a named `jax.sharding.Mesh` whose axes play the role of
NVSHMEM teams (ref: language/extra/libshmem_device.py:326-340 teams ->
mesh axes).
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

# Canonical axis names. NVSHMEM teams map to mesh axes
# (ref: SURVEY.md "Teams map to mesh axes").
TP_AXIS = "tp"
EP_AXIS = "ep"
SP_AXIS = "sp"
PP_AXIS = "pp"
DP_AXIS = "dp"

_DEFAULT_MESH: Optional[Mesh] = None
_INITIALIZED = False

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Place JAX's persistent compilation cache; call before the first
    compile. `JAX_COMPILATION_CACHE_DIR`, when set, is the place (JAX
    reads it itself) and nothing else is touched. Otherwise one fixed,
    git-ignored directory inside the checkout — the path is part of
    the cache key's world, so never a temporary name, a pid or a time.
    Returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def _maybe_init_multihost() -> None:
    """Initialize jax.distributed when launched multi-process.

    The reference reads RANK/LOCAL_RANK/WORLD_SIZE from torchrun env
    (ref: utils.py:182-188). The JAX equivalent: coordinator env vars; we
    only call jax.distributed.initialize when they are present so
    single-host usage needs no env.
    """
    if os.environ.get("JAX_COORDINATOR_ADDRESS") or os.environ.get(
        "COORDINATOR_ADDRESS"
    ):
        coord = os.environ.get("JAX_COORDINATOR_ADDRESS") or os.environ.get(
            "COORDINATOR_ADDRESS"
        )
        num_procs = int(os.environ.get("JAX_NUM_PROCESSES", os.environ.get("WORLD_SIZE", "1")))
        proc_id = int(os.environ.get("JAX_PROCESS_ID", os.environ.get("RANK", "0")))
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=num_procs,
            process_id=proc_id,
        )


def make_mesh(
    mesh_shape: Optional[Sequence[int]] = None,
    axis_names: Sequence[str] = (TP_AXIS,),
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a named device mesh.

    Defaults to a 1-D mesh over all devices on axis "tp" — the analog of the
    reference's world-spanning TP group (ref: utils.py:198-201).

    On TPU the first n devices are laid out by
    `mesh_utils.create_device_mesh`, so that neighbours along a mesh
    axis are ICI neighbours: enumeration order on a v5e 2x2 is
    (0,0) (1,0) (0,1) (1,1), whose 1-D ring 0->1->2->3->0 crosses the
    diagonal twice; the ring kernels' hop is then two links, not one.
    Other platforms have no topology and keep enumeration order.
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if mesh_shape is None:
        mesh_shape = (len(devices),) + (1,) * (len(axis_names) - 1)
    n = int(np.prod(mesh_shape))
    if n > len(devices):
        raise ValueError(
            f"mesh shape {tuple(mesh_shape)} needs {n} devices, have "
            f"{len(devices)}"
        )
    if n > 1 and devices[0].platform == "tpu":
        grid = mesh_utils.create_device_mesh(tuple(mesh_shape),
                                             devices=devices[:n])
    else:
        grid = np.asarray(devices[:n], dtype=object).reshape(mesh_shape)
    return Mesh(grid, tuple(axis_names))


def split_mesh(mesh: Mesh, axis: str, sizes: Sequence[int],
               names: Sequence[str]) -> Mesh:
    """Split one mesh axis into sub-axes (teams): the TPU analog of
    NVSHMEM team_split_strided (ref: shmem teams, libshmem_device.py:
    326-340; test/nvidia/test_team_split.py). A (8,)-"tp" mesh split by
    (2, 4) into ("pp", "tp") yields 2 pipeline groups of 4-way TP; kernels
    address either team by its axis name."""
    import math

    if math.prod(sizes) != mesh.shape[axis]:
        raise ValueError(
            f"split sizes {tuple(sizes)} do not cover axis {axis} "
            f"(size {mesh.shape[axis]})"
        )
    idx = mesh.axis_names.index(axis)
    new_shape = []
    new_names = []
    for i, name in enumerate(mesh.axis_names):
        if i == idx:
            new_shape.extend(sizes)
            new_names.extend(names)
        else:
            new_shape.append(mesh.devices.shape[i])
            new_names.append(name)
    return Mesh(mesh.devices.reshape(new_shape), tuple(new_names))


def initialize_distributed(
    mesh_shape: Optional[Sequence[int]] = None,
    axis_names: Sequence[str] = (TP_AXIS,),
    seed: int = 42,
) -> Mesh:
    """Bring up the distributed runtime and install the default mesh.

    Mirrors the reference's single entry point (ref: utils.py:182-205):
    process-group init -> seeds -> symmetric-heap transport init. On TPU the
    symmetric heap is implicit (every kernel's comm buffers live in each
    chip's HBM and are addressed by mesh coordinates), so step three is free.
    """
    global _INITIALIZED
    if not _INITIALIZED:
        _maybe_init_multihost()
        _INITIALIZED = True
    init_seed(seed)
    mesh = make_mesh(mesh_shape, axis_names)
    set_default_mesh(mesh)
    return mesh


def finalize_distributed() -> None:
    """Tear down (ref: utils.py finalize_distributed analog)."""
    global _DEFAULT_MESH, _INITIALIZED
    _DEFAULT_MESH = None
    _INITIALIZED = False


def set_default_mesh(mesh: Mesh) -> None:
    global _DEFAULT_MESH
    _DEFAULT_MESH = mesh


def get_default_mesh() -> Mesh:
    if _DEFAULT_MESH is None:
        raise RuntimeError(
            "No default mesh; call initialize_distributed() or set_default_mesh()."
        )
    return _DEFAULT_MESH


def rank(mesh: Optional[Mesh] = None, axis: str = TP_AXIS) -> int:
    """Host-side rank of this process's first local device along `axis`.

    Looks up the mesh coordinate of the first addressable device, so it is
    correct for multi-host meshes regardless of process/device layout.
    Device-side rank (inside kernels) is lang.my_pe / lax.axis_index
    (ref: distributed_ops.py:57-111 rank()).
    """
    mesh = mesh or get_default_mesh()
    first_local = jax.local_devices()[0]
    axis_pos = mesh.axis_names.index(axis)
    coords = np.argwhere(mesh.devices == first_local)
    if coords.size == 0:
        raise ValueError(f"first local device {first_local} not in mesh {mesh}")
    return int(coords[0][axis_pos])


def num_ranks(mesh: Optional[Mesh] = None, axis: str = TP_AXIS) -> int:
    mesh = mesh or get_default_mesh()
    return int(mesh.shape[axis])


_SEED = 42


def init_seed(seed: int = 42) -> None:
    """Deterministic seeding (ref: utils.py:77-96 init_seed)."""
    global _SEED
    _SEED = seed
    np.random.seed(seed)


def get_prng_key(salt: int = 0) -> jax.Array:
    return jax.random.PRNGKey(_SEED + salt)
