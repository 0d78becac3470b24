"""Low-latency AllToAll — the MoE dispatch/combine transport.

TPU-native re-design of the reference's DeepEP-style A2A
(ref: python/triton_dist/kernels/nvidia/low_latency_all_to_all.py:36-118
`all_to_all_kernel`: one block per peer does putmem_nbi_block of the token
segment + putmem_signal of scales, fence, signal_op, then
signal_wait_until on its own incoming segment; 137 µs on 32 ranks,
README.md:93). On TPU the whole exchange is one Pallas kernel issuing n-1
concurrent remote DMAs — segment i of the send buffer lands in peer i's
slot `me` — with DMA delivery semaphores playing the role of the
putmem_signal flags. Segment sizes are static (max tokens per peer, as jit
requires); actual counts travel in the same kernel as a second, tiny
`splits` transfer, mirroring the reference's split-metadata exchange
(ref: ep_a2a.py:244-309 splits AG + recv-offset calc).

The reference double-buffers by call parity so back-to-back layer calls
don't collide (low_latency_all_to_all.py:36-118 `call_count % 2`); here
every call's semaphores are kernel-local scratch, so calls are re-entrant
by construction and no parity state exists.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu import verify as _v
from triton_dist_tpu.faults import guard as _guard
from triton_dist_tpu.faults import plan as _fplan
from triton_dist_tpu.lang import shmem
from triton_dist_tpu.lang.core import (
    tpu_call,
    compiler_params,
    next_collective_id,
    interpret_no_headroom,
)
from triton_dist_tpu.runtime.init import EP_AXIS
from triton_dist_tpu.trace import events as trace_ev


def _a2a_kernel(axis: str, n: int, x_ref, s_ref, o_ref, os_ref,
                cp_sem, send_sem, recv_sem, meta_send_sem, meta_recv_sem):
    me = jax.lax.axis_index(axis)
    shmem.barrier_all(axis)

    # Local segment: x[me] -> out[me]; splits likewise.
    cp = pltpu.make_async_copy(x_ref.at[me], o_ref.at[me], cp_sem)
    cp.start()
    cps = pltpu.make_async_copy(s_ref.at[me], os_ref.at[me], cp_sem)

    handles = []
    for i in range(1, n):
        peer = jnp.mod(me + i, n)
        handles.append(shmem.putmem_nbi(
            o_ref.at[me], x_ref.at[peer], send_sem, recv_sem, peer, axis))
        handles.append(shmem.putmem_nbi(
            os_ref.at[me], s_ref.at[peer], meta_send_sem, meta_recv_sem,
            peer, axis))
    cp.wait()
    cps.start()
    cps.wait()
    for h in handles:
        h.wait()


def all_to_all(
    x: jax.Array,
    splits: jax.Array,
    axis: str = EP_AXIS,
) -> Tuple[jax.Array, jax.Array]:
    """Exchange per-peer segments: out[j] = peer j's x[me]. Per-device
    function inside shard_map (ref host entry:
    low_latency_all_to_all.py:198 `fast_all_to_all`).

    x: (n, m, hidden) send buffer — segment i goes to rank i.
    splits: (n,) or (n, S) int32 — per-segment metadata rows travelling
    alongside (the classic case is the single valid-token count; the
    chunk-pipelined EP dispatch rides its per-expert counts here too).
    Returns (out, out_splits): out[j] holds rank j's segment for us, with
    rank j's metadata row in out_splits[j] (same shape as splits).
    """
    n = jax.lax.axis_size(axis)
    if x.shape[0] != n:
        raise ValueError(f"x leading dim {x.shape[0]} != axis size {n}")
    if n == 1:
        return x, splits.astype(jnp.int32)
    if interpret_no_headroom():
        return all_to_all_ref(x, splits, axis)
    splits2d = splits.reshape(n, -1).astype(jnp.int32)
    out, out_splits = tpu_call(
        functools.partial(_a2a_kernel, axis, n),
        out_shape=(
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(splits2d.shape, jnp.int32),
        ),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=compiler_params(
            has_side_effects=True,
            collective_id=next_collective_id(f"a2a_{axis}"),
        ),
    )(x, splits2d)
    return out, out_splits.reshape(splits.shape)


def fast_all_to_all(x, splits, axis: str = EP_AXIS):
    """Alias matching the reference's public name
    (ref: kernels/nvidia/__init__.py fast_all_to_all)."""
    return all_to_all(x, splits, axis)


def all_to_all_ref(x: jax.Array, splits: jax.Array, axis: str = EP_AXIS):
    """XLA reference path (lax.all_to_all over the leading dim).
    splits may be (n,) or (n, S); the output matches its shape."""
    out = jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0, tiled=False)
    n = x.shape[0]
    out_splits = jax.lax.all_to_all(
        splits.reshape(n, -1), axis, split_axis=0, concat_axis=0, tiled=True
    ).reshape(splits.shape)
    return out, out_splits


# -- chunked transport (the EP MoE pipeline's arrival-granular A2A) ----------


def _a2a_chunked_kernel(axis, n, q, rows, straggler, build, gbuild,
                        *refs):
    """Chunk-granular A2A: segment payloads travel as `q` row-chunks, and
    chunk (step i, c) lands on its OWN delivery semaphore slot
    recv_sems[i, c] — the TPU analog of the reference's per-peer
    putmem_signal + signal_wait_until (low_latency_all_to_all.py:36-118):
    a consumer can wait on chunk c of every source while chunks c+1..q-1
    are still in flight.

    Semaphore slots are indexed by RING STEP i (source offset me-i), not
    absolute source rank: every rank's descriptor for step (i, c) then
    names the same static slot — the DMA's delivery semaphore lives on
    the destination chip, so sender and receiver must agree on it.

    `build` (trace.events.TraceBuild or None) gates the event records:
    instants per chunk send, spans per delivery wait, and the straggle
    instant every rank emits (payload = this rank's injected delay, 0
    off-rank — uniform record sequences keep cross-rank seq aligned for
    the delivery replay, trace/attribution.a2a_step_waits)."""
    refs = list(refs)
    x_ref, s_ref, o_ref, os_ref = refs[:4]
    del refs[:4]
    tbuf = refs.pop(0) if build is not None else None
    gbuf = refs.pop(0) if gbuild is not None else None
    gcur = refs.pop() if gbuild is not None else None
    tcur = refs.pop() if build is not None else None
    (cp_sem, send_sem, recv_sems, meta_send_sem, meta_recv_sem) = refs
    me = jax.lax.axis_index(axis)
    tctx = trace_ev.make_ctx(build, tbuf, tcur)
    trace_ev.init_ctx(tctx, rank=me)
    R = trace_ev.REGIONS
    gctx = _guard.make_ctx(gbuild, gbuf, gcur, tctx=tctx)
    _guard.init_ctx(gctx, rank=me)
    with _guard.attached(gctx):
        shmem.barrier_all(axis)
        if straggler is not None:
            # race provocation: stall one rank between entering the
            # kernel and issuing its sends, so its peers' per-chunk
            # waits really wait (pattern of the megakernel AR skew
            # stress)
            trace_ev.instant(
                tctx, R["straggle"],
                payload=jnp.where(me == straggler[0], straggler[1], 0))
            shmem.straggler_delay(axis, straggler[0], straggler[1])

        # Local segment: chunk-granular local copies, each on its own
        # slot (recv_sems row 0 — ring step 0 is "self", so the slot
        # space is uniform: slot [i, c] == chunk c from source offset
        # i). A shared local semaphore would let chunk c's wait be
        # satisfied by chunk c+1's completion (waits are byte-counted,
        # not tagged), silently voiding the chunk-major arrival
        # guarantee.
        local = []
        for c in range(q):
            sl = pl.ds(c * rows, rows)
            cp = pltpu.make_async_copy(x_ref.at[me, sl],
                                       o_ref.at[me, sl],
                                       recv_sems.at[0, c])
            cp.start()
            local.append(cp)
        cps = pltpu.make_async_copy(s_ref.at[me], os_ref.at[me], cp_sem)

        handles = {}
        meta_handles = []
        for i in range(1, n):
            peer = jnp.mod(me + i, n)
            for c in range(q):
                sl = pl.ds(c * rows, rows)
                trace_ev.instant(tctx, R["a2a.send"], payload=i, aux=c)
                handles[(i, c)] = shmem.putmem_nbi(
                    o_ref.at[me, sl], x_ref.at[peer, sl], send_sem,
                    recv_sems.at[i, c], peer, axis,
                )
            meta_handles.append(shmem.putmem_nbi(
                os_ref.at[me], s_ref.at[peer], meta_send_sem,
                meta_recv_sem, peer, axis,
            ))

        # Chunk-major consumption: after iteration c the output rows of
        # chunk c are complete FROM EVERY SOURCE while chunks c+1.. are
        # still in flight — the wait order a fused consumer interleaves
        # compute into.
        for c in range(q):
            shmem.guard_progress(c)
            with trace_ev.span(tctx, R["a2a.local"], payload=c):
                local[c].wait()
            for i in range(1, n):
                with trace_ev.span(tctx, R["a2a.wait"], payload=i,
                                   aux=c):
                    handles[(i, c)].wait_send()
                    handles[(i, c)].wait_recv(slot=i)
        cps.start()
        cps.wait()
        for i, h in enumerate(meta_handles):
            with trace_ev.span(tctx, R["a2a.meta"], payload=i + 1):
                h.wait()


def all_to_all_chunked(
    x: jax.Array,
    splits: jax.Array,
    axis: str = EP_AXIS,
    n_chunks: int = 1,
    straggler: Optional[Tuple[int, int]] = None,
) -> Tuple[jax.Array, jax.Array]:
    """all_to_all with per-chunk delivery semaphores: each segment's rows
    travel as `n_chunks` independently-signalled chunks (see
    _a2a_chunked_kernel). Byte-identical output to `all_to_all`; what
    changes is the ARRIVAL protocol — chunk c of every source can be
    consumed while later chunks stream, which is what the chunk-pipelined
    EP MoE dispatch builds on (kernels/ep_a2a.py).

    x: (n, C, hidden) with C % n_chunks == 0; splits: (n,) or (n, S).
    straggler: optional (rank, nanos) skew injection for stress tests.

    Tracing (trace.building active): returns an extra trailing output —
    the per-rank device trace buffer — on every path (fallbacks hand
    back an empty buffer), so callers' output trees are build-stable.
    Guarding (faults.guard.building active): one more trailing output,
    the guard buffer (after the trace buffer when both are active).
    """
    n = jax.lax.axis_size(axis)
    if x.shape[0] != n:
        raise ValueError(f"x leading dim {x.shape[0]} != axis size {n}")
    q = int(n_chunks)
    if q < 1 or x.shape[1] % q:
        raise ValueError(
            f"n_chunks={q} must be >= 1 and divide the capacity dim "
            f"{x.shape[1]}"
        )
    build = trace_ev.active_build()
    gbuild = _guard.active_build()
    straggler = _fplan.scheduled_straggler("all_to_all_chunked",
                                           straggler)

    def with_both(res, tbuf=None, gbuf=None):
        return _guard.with_guard(
            gbuild, trace_ev.with_trace(build, res, tbuf), gbuf)

    if n == 1:
        return with_both((x, splits.astype(jnp.int32)))
    if interpret_no_headroom():
        return with_both(all_to_all_ref(x, splits, axis))
    rows = x.shape[1] // q
    splits2d = splits.reshape(n, -1).astype(jnp.int32)
    out_shape = (
        jax.ShapeDtypeStruct(x.shape, x.dtype),
        jax.ShapeDtypeStruct(splits2d.shape, jnp.int32),
    )
    out_specs = (
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    )
    scratch = [
        pltpu.SemaphoreType.DMA,
        pltpu.SemaphoreType.DMA,
        pltpu.SemaphoreType.DMA((n, q)),
        pltpu.SemaphoreType.DMA,
        pltpu.SemaphoreType.DMA,
    ]
    if build is not None:
        out_shape += (trace_ev.out_shape(build),)
        out_specs += (trace_ev.out_spec(),)
        scratch.append(trace_ev.cursor_scratch())
    if gbuild is not None:
        out_shape += (_guard.out_shape(gbuild),)
        out_specs += (_guard.out_spec(),)
        scratch.append(_guard.cursor_scratch())
    res = tpu_call(
        functools.partial(_a2a_chunked_kernel, axis, n, q, rows,
                          straggler, build, gbuild),
        out_shape=out_shape,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=out_specs,
        scratch_shapes=scratch,
        compiler_params=compiler_params(
            has_side_effects=True,
            collective_id=next_collective_id(f"a2a_chunk{q}_{axis}"),
        ),
    )(x, splits2d)
    out, out_splits = res[:2]
    k = 2
    tbuf = res[k] if build is not None else None
    k += 1 if build is not None else 0
    gbuf = res[k] if gbuild is not None else None
    return with_both((out, out_splits.reshape(splits.shape)), tbuf, gbuf)


# -- protocol models (static verifier, triton_dist_tpu.verify) ---------------
#
# Each model replays its kernel's cross-rank communication skeleton
# through the shmem primitives under verify.capturing(): same barrier,
# same DMA slot/semaphore indexing, same wait order, with the consumer
# contract spelled as read annotations. scripts/verify_kernels.py proves
# them deadlock-free / race-free / semaphore-balanced at n = 2/4/8.


@_v.protocol("all_to_all",
             doc="single-shot segment exchange (_a2a_kernel)")
def _a2a_protocol(n):
    me = shmem.my_pe(EP_AXIS)
    x, s = _v.ref("x"), _v.ref("splits")
    o, os_ = _v.ref("out"), _v.ref("out_splits")
    cp = _v.sem("cp_sem")
    send, recv = _v.sem("send_sem"), _v.sem("recv_sem")
    msend, mrecv = _v.sem("meta_send_sem"), _v.sem("meta_recv_sem")
    shmem.barrier_all(EP_AXIS)
    lc = _v.copy(o.at(me), x.at(me), cp.at())
    handles = []
    for i in range(1, n):
        peer = (me + i) % n
        handles.append(shmem.putmem_nbi(
            o.at(me), x.at(peer), send.at(), recv.at(), peer, EP_AXIS))
        handles.append(shmem.putmem_nbi(
            os_.at(me), s.at(peer), msend.at(), mrecv.at(), peer,
            EP_AXIS))
    lc.wait()
    lcs = _v.copy(os_.at(me), s.at(me), cp.at())
    lcs.wait()
    for h in handles:
        h.wait()
    # consumer contract: the caller reads every segment after the kernel
    for j in range(n):
        _v.read(o.at(j))
        _v.read(os_.at(j))


@_v.protocol("all_to_all_chunked",
             grid=({"q": 1}, {"q": 2}, {"q": 4}),
             doc="per-(step, chunk) delivery slots (_a2a_chunked_kernel)")
def _a2a_chunked_protocol(n, q=2):
    """Slots indexed by RING STEP (source offset), never absolute rank —
    the exact invariant the verifier's deadlock check proves (the
    absolute-rank mutant in tests/_mutants.py is the counterexample).
    Chunk-major consumer reads model the fused EP pipeline: chunk c of
    every source is read while chunks c+1.. are still in flight."""
    me = shmem.my_pe(EP_AXIS)
    x, o = _v.ref("x"), _v.ref("out")
    s, os_ = _v.ref("splits"), _v.ref("out_splits")
    cp = _v.sem("cp_sem")
    send, recv = _v.sem("send_sem"), _v.sem("recv_sems")
    msend, mrecv = _v.sem("meta_send_sem"), _v.sem("meta_recv_sem")
    shmem.barrier_all(EP_AXIS)
    local = [_v.copy(o.at(me, c), x.at(me, c), recv.at(0, c))
             for c in range(q)]
    handles = {}
    metas = []
    for i in range(1, n):
        peer = (me + i) % n
        for c in range(q):
            with _v.tag(step=i, chunk=c):
                handles[(i, c)] = shmem.putmem_nbi(
                    o.at(me, c), x.at(peer, c), send.at(), recv.at(i, c),
                    peer, EP_AXIS)
        metas.append(shmem.putmem_nbi(
            os_.at(me), s.at(peer), msend.at(), mrecv.at(), peer,
            EP_AXIS))
    for c in range(q):
        local[c].wait()
        for i in range(1, n):
            with _v.tag(step=i, chunk=c):
                handles[(i, c)].wait()
        for j in range(n):
            _v.read(o.at(j, c))  # chunk-major consumer (EP FFN)
    lcs = _v.copy(os_.at(me), s.at(me), cp.at())
    lcs.wait()
    for m in metas:
        m.wait()
    for j in range(n):
        _v.read(os_.at(j))


# -- conformance runners (verify.conform: recorded kernel vs model) -----------

from jax.sharding import PartitionSpec as _P  # noqa: E402

from triton_dist_tpu.verify import conform as _conform  # noqa: E402


@_conform.conforms(
    "all_to_all", grids=((4, {}),),
    doc="single-shot segment exchange on the interpret mesh")
def _a2a_conform(n):
    mesh = _conform.team_mesh(n, (EP_AXIS,))
    if isinstance(mesh, _conform.Skip):
        return mesh
    x = jnp.ones((n * n, 8, 128), jnp.float32)
    sp = jnp.ones((n * n,), jnp.int32)
    return _conform.collect_streams(
        mesh, EP_AXIS, lambda v, s: all_to_all(v, s, EP_AXIS),
        in_specs=(_P(EP_AXIS), _P(EP_AXIS)), args=(x, sp))


@_conform.conforms(
    "all_to_all_chunked",
    grids=((4, {"q": 1}), (4, {"q": 2}), (4, {"q": 4})),
    doc="chunk-granular A2A: per-(step, chunk) delivery slots")
def _a2a_chunked_conform(n, q=2):
    mesh = _conform.team_mesh(n, (EP_AXIS,))
    if isinstance(mesh, _conform.Skip):
        return mesh
    x = jnp.ones((n * n, 8, 128), jnp.float32)
    sp = jnp.ones((n * n,), jnp.int32)
    return _conform.collect_streams(
        mesh, EP_AXIS,
        lambda v, s: all_to_all_chunked(v, s, EP_AXIS, n_chunks=q),
        in_specs=(_P(EP_AXIS), _P(EP_AXIS)), args=(x, sp))
