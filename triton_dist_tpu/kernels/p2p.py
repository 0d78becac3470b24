"""Point-to-point transfer kernels — the pipeline-parallel building block.

TPU-native analog of the reference's p2p kernels
(ref: python/triton_dist/kernels/nvidia/p2p.py:31-54 `p2p_copy_kernel` /
remote-to-local via symm_at), which back the PP CommOp layer
(ref: layers/nvidia/p2p.py:43-140: `read` remote pull, set_signal/wait_signal
via cuStreamWriteValue/cuStreamWaitValue).

ICI RDMA is push-based, so the canonical op is `send`/`recv` as one
matched collective kernel: the sender pushes into the receiver's output
buffer and signals; the stream-memop signal/wait pair becomes the DMA
delivery semaphore. A `p2p_read` (pull) is provided for API parity by
running the matched kernel in the reverse direction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.lang import shmem
from triton_dist_tpu.lang.core import (
    tpu_call,
    compiler_params,
    next_collective_id,
    interpret_no_headroom,
)
from triton_dist_tpu.runtime.init import PP_AXIS


def _p2p_kernel(axis: str, n: int, src_rank: int, dst_rank: int,
                x_ref, o_ref, cp_sem, send_sem, recv_sem):
    """Matched on all ranks of the axis: rank src pushes x to rank dst's
    output; everyone else forwards its own x to its own output (identity),
    so the kernel is a pure SPMD program with no divergent control flow
    hazards."""
    me = jax.lax.axis_index(axis)
    # Full barrier: src/dst may be arbitrary ranks, and the put must not
    # land while dst is still in a previous kernel using these semaphores.
    shmem.barrier_all(axis)

    if src_rank == dst_rank or n == 1:
        cp = pltpu.make_async_copy(x_ref, o_ref, cp_sem)
        cp.start()
        cp.wait()
        return

    # Local identity copy for every rank EXCEPT dst: dst's output is written
    # only by the incoming put. Nothing orders a local copy against the
    # remote DMA's arrival, so dst writing o_ref itself would race the put
    # (the put could land first and be overwritten after wait_recv).
    @pl.when(me != dst_rank)
    def _():
        cp = pltpu.make_async_copy(x_ref, o_ref, cp_sem)
        cp.start()
        cp.wait()

    rdma = pltpu.make_async_remote_copy(
        src_ref=x_ref,
        dst_ref=o_ref,
        send_sem=send_sem,
        recv_sem=recv_sem,
        device_id={axis: jnp.int32(dst_rank)},
        device_id_type=pltpu.DeviceIdType.MESH,
    )

    @pl.when(me == src_rank)
    def _():
        rdma.start()
        rdma.wait_send()

    @pl.when(me == dst_rank)
    def _():
        rdma.wait_recv()


def p2p_send(x: jax.Array, src_rank: int, dst_rank: int,
             axis: str = PP_AXIS) -> jax.Array:
    """Send rank src's `x` to rank dst; all other ranks pass through their
    own `x`. Per-device function inside shard_map — every rank must call it
    (matched collective), mirroring the reference's symmetric-buffer p2p
    contract (ref: kernels/nvidia/p2p.py:31-54)."""
    n = jax.lax.axis_size(axis)
    if n == 1:
        return x
    if interpret_no_headroom():
        me = jax.lax.axis_index(axis)
        shifted = jax.lax.ppermute(x, axis, [(src_rank, dst_rank)])
        return jnp.where(me == dst_rank, shifted, x)
    return tpu_call(
        functools.partial(_p2p_kernel, axis, n, src_rank, dst_rank),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=compiler_params(
            has_side_effects=True,
            collective_id=next_collective_id(f"p2p_{axis}"),
        ),
    )(x)


def p2p_read(x: jax.Array, reader_rank: int, owner_rank: int,
             axis: str = PP_AXIS) -> jax.Array:
    """Pull owner's buffer into reader (ref CommOp.read, layers/nvidia/
    p2p.py:43-140). Push-based under the hood."""
    return p2p_send(x, owner_rank, reader_rank, axis)


def ring_shift(x: jax.Array, shift: int = 1, axis: str = PP_AXIS) -> jax.Array:
    """Every rank sends its buffer `shift` hops right; the PP stage-to-stage
    microbatch handoff (all stages transfer simultaneously)."""
    n = jax.lax.axis_size(axis)
    if n == 1:
        return x
    if interpret_no_headroom():
        perm = [(i, (i + shift) % n) for i in range(n)]
        return jax.lax.ppermute(x, axis, perm)

    def kernel(x_ref, o_ref, send_sem, recv_sem):
        me = jax.lax.axis_index(axis)
        if abs(shift) == 1:
            shmem.neighbor_barrier(axis, me, n)
        else:
            shmem.barrier_all(axis)
        shmem.putmem_nbi(o_ref, x_ref, send_sem, recv_sem,
                         jnp.mod(me + shift, n), axis).wait()

    return tpu_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA],
        compiler_params=compiler_params(
            has_side_effects=True,
            collective_id=next_collective_id(f"ring_shift_{axis}"),
        ),
    )(x)


# -- protocol models (static verifier, triton_dist_tpu.verify) ---------------

from triton_dist_tpu import verify as _v  # noqa: E402


@_v.protocol("broadcast", grid=({"root": 0}, {"root": 1}),
             doc="team broadcast (lang/shmem.broadcast): root-guarded "
                 "fan-out, non-root single delivery wait")
def _broadcast_protocol(n, root=0):
    """Exercises the rank-divergent guard machinery (capture `when`):
    only the root records the fan-out puts, only non-roots the delivery
    wait — the same divergence the real kernel expresses with pl.when.
    The entry barrier is the documented caller precondition."""
    src, dst = _v.ref("src"), _v.ref("dst")
    send, recv = _v.sem("send_sem"), _v.sem("recv_sem")
    shmem.barrier_all(PP_AXIS)
    shmem.broadcast(dst, src, send.at(), recv.at(), root, PP_AXIS, n)
    _v.read(dst.at())  # every rank consumes the broadcast payload


@_v.protocol("ring_shift", grid=({"shift": 1}, {"shift": 3}),
             doc="PP stage handoff: every rank puts `shift` hops right")
def _ring_shift_protocol(n, shift=1):
    me = shmem.my_pe(PP_AXIS)
    x, o = _v.ref("x"), _v.ref("o")
    send, recv = _v.sem("send_sem"), _v.sem("recv_sem")
    if abs(shift) == 1:
        shmem.neighbor_barrier(PP_AXIS, me, n)
    else:
        shmem.barrier_all(PP_AXIS)
    h = shmem.putmem_nbi(o.at(), x.at(), send.at(), recv.at(),
                         (me + shift) % n, PP_AXIS)
    h.wait()
    _v.read(o.at())


# -- conformance runners (verify.conform) -------------------------------------

from jax.sharding import PartitionSpec as _P  # noqa: E402

from triton_dist_tpu.verify import conform as _conform  # noqa: E402


@_conform.conforms(
    "ring_shift",
    grids=((4, {"shift": 1}), (4, {"shift": 3})),
    doc="neighbor-barriered ring rotation on the interpret mesh")
def _ring_shift_conform(n, shift=1):
    mesh = _conform.team_mesh(n, (PP_AXIS,))
    if isinstance(mesh, _conform.Skip):
        return mesh
    x = jnp.ones((8, 128), jnp.float32)
    return _conform.collect_streams(
        mesh, PP_AXIS, lambda v: ring_shift(v, shift, PP_AXIS),
        in_specs=_P(), args=(x,))


@_conform.conforms(
    "broadcast",
    grids=((4, {"root": 0}), (4, {"root": 1})),
    doc="root-guarded fan-out (rank-divergent; see skip reason)")
def _broadcast_conform(n, root=0):
    return _conform.Skip(
        "rank-divergent protocol (root-guarded fan-out): no runner "
        "records its kernel stream yet (tests/test_shmem_ext.py "
        "executes shmem.broadcast itself)")
