"""Low-latency allgather for small messages — barrier-free steady state.

TPU-native re-design of the reference's LL fast allgather
(ref: python/triton_dist/kernels/nvidia/low_latency_allgather.py:530-607
`_pack_ll_block`/`_recv_ll_block` — LAMPORT-style 8-byte flag-in-data
packing so the receiver validates payload arrival without a separate
signal round-trip; context `FastAllGatherContext` :781).

On TPU the DMA delivery semaphore IS the flag: it is updated by the same
hardware transaction that writes the payload, so flag-in-data packing is
obviated. What the LL design still contributes — and what this kernel
keeps — is the *barrier-free steady state* via double buffering:

  - the destination is a persistent (2, n, ...) context buffer; call k
    uses slot parity k%2;
  - each parity has its own recv semaphore (recv_sems[parity]): a
    semaphore increment can never be attributed to the wrong call,
    because call k+2 (same parity) on any peer is gated behind that
    peer's call k+1 wait, which is gated behind OUR call-k consume —
    exactly the flag-validation ordering of the LL protocol, carried by
    semaphore counting instead of flag words (the `call_count % 2`
    double buffer of the reference, low_latency_all_to_all.py:36-118);
  - only the FIRST call on a fresh context barriers the team (the
    reference syncs at context creation).

Use for latency-class payloads (flash-decode partials, splits metadata).
Bandwidth-class payloads want the ring/2-axis kernels in allgather.py.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.faults import guard as _guard
from triton_dist_tpu.lang import shmem
from triton_dist_tpu.obs import stats as _obs
from triton_dist_tpu.verify import capture as _vcap
from triton_dist_tpu.lang.core import (
    compiler_params,
    interpret_no_headroom,
    next_collective_id,
    tpu_call,
)
from triton_dist_tpu.runtime.init import TP_AXIS
from triton_dist_tpu.wire import codec as wcodec


def create_ll_ag_buffer(x_shape, dtype, n: int,
                        wire_format=None) -> jax.Array:
    """Persistent per-device context buffer (2 parities × n slots), the
    FastAllGatherContext analog. Thread it through calls (it is donated /
    aliased by the kernel). With a quantized wire_format the context
    holds the int8 wire image per slot (the parity protocol is
    format-invariant — only the slot byte shape changes)."""
    fmt = wcodec.resolve(wire_format)
    if not wcodec.is_native(fmt):
        import math

        rows = x_shape[0]
        kw = wcodec.wire_cols(math.prod(x_shape[1:]), fmt)
        return jnp.zeros((2, n, rows, kw), jnp.int8)
    return jnp.zeros((2, n) + tuple(x_shape), dtype)


def _ll_ag_kernel(axis: str, n: int, gbuild, obuild, fmtc, flags_ref,
                  x_ref, buf_in, buf_out, *refs):
    refs = list(refs)
    # outputs precede scratch: gbuf/obuf follow buf_out, the obs/guard
    # cursors are the trailing scratch entries
    gbuf = refs.pop(0) if gbuild is not None else None
    obuf = refs.pop(0) if obuild is not None else None
    ocur = refs.pop() if obuild is not None else None
    gcur = refs.pop() if gbuild is not None else None
    send_sem, recv_sems, local_sem = refs
    parity = flags_ref[0]
    first = flags_ref[1]
    del buf_in  # aliased: access through buf_out

    me = shmem.my_pe(axis)
    octx = _obs.make_ctx(obuild, obuf, ocur)
    _obs.init_ctx(octx, rank=me, fmt=fmtc)
    gctx = _guard.make_ctx(gbuild, gbuf, gcur, octx=octx)
    _guard.init_ctx(gctx, rank=me)
    with _guard.attached(gctx), _obs.attached(octx):
        @pl.when(first == 1)
        def _():
            # fresh context: peers must be inside the kernel before the
            # first puts land (afterwards the parity protocol orders
            # everything)
            shmem.barrier_all(axis)

        shmem.fault_delay(axis, "low_latency_allgather")
        shmem.fcollect_slots(
            lambda pe: buf_out.at[parity, pe], x_ref,
            local_sem, send_sem, recv_sems.at[parity], axis, n,
        )


def ll_all_gather(
    x: jax.Array,
    buf: jax.Array,
    call_count,
    axis: str = TP_AXIS,
    first=None,
    wire_format=None,
) -> Tuple[jax.Array, jax.Array]:
    """Small-message AG: returns (gathered (n,)+x.shape, new buf).

    Per-device inside shard_map. `call_count` is the 0-based call index
    on this context buffer (python int or traced scalar); the FIRST call
    on a fresh context performs the one-time entry barrier — by default
    call 0, overridable via `first` (bool/scalar) when the caller manages
    context lifetime separately from the call counter (ll_all_gather_op).
    The context must not be shared by two in-flight collectives.

    wire_format: quantized formats push the block-scaled wire image
    through the SAME parity protocol (the context must have been created
    with the same format — create_ll_ag_buffer(wire_format=...)); every
    slot including the rank's own passes the codec, so the gathered
    result is the pack/unpack roundtrip of the shards.

    Guarding (faults.guard.building active): one extra trailing output —
    the kernel's guard buffer (bounded-watchdog trip rows; empty stream
    on the fallback paths) — which the caller feeds to guard.check."""
    n = jax.lax.axis_size(axis)
    fmt = wcodec.resolve(wire_format)
    wire = not wcodec.is_native(fmt)
    gbuild = _guard.active_build()
    obuild = _obs.active_build()

    def with_builds(res, gbuf=None, obuf=None):
        if obuild is not None and obuf is None:
            obuf = _obs.new_stream(obuild, fmt=_obs.fmt_code(fmt))
        return _obs.with_stats(
            obuild, _guard.with_guard(gbuild, res, gbuf), obuf)

    def decode(slots):
        # (n, rows, kw) wire slots -> (n,) + x.shape in x.dtype
        if not wire:
            return slots
        flat = slots.reshape(n * slots.shape[1], slots.shape[2])
        return wcodec.unpack(flat, x.shape[1:], fmt, x.dtype).reshape(
            (n,) + x.shape)

    if n == 1:
        out = wcodec.roundtrip(x, fmt)[None] if wire else x[None]
        return with_builds((out, buf))
    xw = wcodec.pack(x, fmt)
    if interpret_no_headroom():
        return with_builds((decode(jax.lax.all_gather(xw, axis)), buf))

    call_count = jnp.asarray(call_count, jnp.int32)
    if first is None:
        first = call_count == 0
    flags = jnp.stack([
        jnp.asarray(call_count % 2, jnp.int32),
        jnp.asarray(first, jnp.int32),
    ])
    res = _ll_ag_call(flags, xw, buf, call_count % 2, axis, n, gbuild,
                      obuild, _obs.fmt_code(fmt))
    out, buf = res[:2]
    k_res = 2
    gbuf = res[k_res] if gbuild is not None else None
    k_res += 1 if gbuild is not None else 0
    obuf = res[k_res] if obuild is not None else None
    if gbuild is not None and wire and fmt.checksum:
        # detect-and-record consume edge: a corrupted slot becomes a
        # wire guard row the host raises on (WireIntegrityError via
        # guard.check) instead of dequantizing garbage silently
        import math as _math

        flat = out.reshape(n * out.shape[1], out.shape[2])
        ok = jnp.all(wcodec.verify_rows(
            flat, _math.prod(x.shape[1:]), fmt))
        gbuf = _guard.stream_trip(gbuf, ok)
    return with_builds((decode(out), buf), gbuf, obuf)


def _ll_ag_call(flags, x, buf, parity, axis, n, gbuild=None,
                obuild=None, fmtc=0):
    kernel = functools.partial(_ll_ag_kernel, axis, n, gbuild, obuild,
                               fmtc)
    out_shape = (jax.ShapeDtypeStruct(buf.shape, buf.dtype),)
    out_specs = (pl.BlockSpec(memory_space=pl.ANY),)
    scratch = [
        pltpu.SemaphoreType.DMA,
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA,
    ]
    if gbuild is not None:
        # explicit block shape: PrefetchScalarGridSpec does not accept
        # the shapeless SMEM spec the gridless kernels use
        out_shape += (_guard.out_shape(gbuild),)
        out_specs += (pl.BlockSpec(
            (1 + gbuild.cap, _guard.GUARD_WORDS),
            lambda i, *_: (0, 0),  # *_: the scalar-prefetch operand
            memory_space=pltpu.SMEM),)
        scratch.append(_guard.cursor_scratch())
    if obuild is not None:
        out_shape += (_obs.out_shape(obuild),)
        out_specs += (pl.BlockSpec(
            (1, _obs.STAT_WORDS),
            lambda i, *_: (0, 0),
            memory_space=pltpu.SMEM),)
        scratch.append(_obs.cursor_scratch())
    single = len(out_shape) == 1
    res = tpu_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
            out_specs=out_specs[0] if single else out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape[0] if single else out_shape,
        input_output_aliases={2: 0},
        compiler_params=compiler_params(
            has_side_effects=True,
            collective_id=next_collective_id(f"ll_ag_{axis}"),
        ),
    )(flags, x, buf)
    res = res if isinstance(res, tuple) else (res,)
    buf = res[0]
    out = jax.lax.dynamic_index_in_dim(buf, parity, 0, keepdims=False)
    return (out, buf) + tuple(res[1:])


@functools.lru_cache(maxsize=None)
def _ll_op_fn(mesh, axis: str, fmt=None, gbuild=None,
              metered: bool = False):
    """Cached jitted executable per (mesh, axis, wire format, guard
    build): call_count and the fresh-context flag ride as traced
    arguments, so every decode step replays one compiled program (a
    fresh closure per call would retrace — the opposite of
    low-latency). An active guard build is part of the cache key — its
    executable has a different output tree (the trailing guard buffer)
    and must never be served to unguarded callers (or vice versa)."""
    from jax.sharding import PartitionSpec as P

    def per_device(x_shard, buf_shard, cc, first):
        with _guard.building(gbuild.cap, gbuild.deadline) if gbuild \
                else contextlib.nullcontext(), \
                _obs.building() if metered else contextlib.nullcontext():
            res = ll_all_gather(x_shard, buf_shard[0], cc, axis,
                                first=first, wire_format=fmt)
        out, new_buf = res[:2]
        return (out, new_buf[None]) + tuple(b[None] for b in res[2:])

    out_specs = (P(None, axis), P(axis))
    out_specs += (P(axis),) * ((gbuild is not None) + bool(metered))
    return jax.jit(
        jax.shard_map(
            per_device, mesh=mesh,
            in_specs=(P(axis), P(axis), P(), P()),
            out_specs=out_specs,
            check_vma=False,
        ),
        donate_argnums=(1,),
    )


@functools.lru_cache(maxsize=None)
def _ll_xla_fn(mesh, axis: str, fmt=None):
    """The degraded route: plain XLA all_gather of the (packed) shard —
    identical output contract (and wire fidelity) to the LL kernel,
    no Pallas protocol to hang. Collective entry points route here
    once a guard trip degraded the protocol (fallback="xla")."""
    from jax.sharding import PartitionSpec as P

    f = wcodec.resolve(fmt)

    def per_device(x_shard):
        xw = wcodec.pack(x_shard, f)
        g = jax.lax.all_gather(xw, axis)
        if wcodec.is_native(f):
            return g
        n = jax.lax.axis_size(axis)
        flat = g.reshape(n * g.shape[1], g.shape[2])
        return wcodec.unpack(flat, x_shard.shape[1:], f,
                             x_shard.dtype).reshape((n,) + x_shard.shape)

    return jax.jit(
        jax.shard_map(per_device, mesh=mesh, in_specs=P(axis),
                      out_specs=P(None, axis), check_vma=False))


PROTOCOL_NAME = "low_latency_allgather"  # degradation-registry key


def ll_all_gather_op(
    x: jax.Array,
    workspace,
    call_count: int,
    mesh,
    axis: str = TP_AXIS,
    name: str = "ll_ag",
    wire_format=None,
    fallback=None,
):
    """Host-level LL allgather over a SymmetricWorkspace-owned context
    (the reference's FastAllGatherContext held by a layer context and
    reused across calls, low_latency_allgather.py:781 +
    runtime/symm_mem.SymmetricWorkspace). x is a GLOBAL array sharded
    P(axis); the context buffer persists inside `workspace` between jit
    invocations (donated in, aliased out, stored back via update()).
    wire_format: quantized contexts are namespaced per format (a
    format switch is a fresh context, with its entry barrier).

    fallback="xla" is the guard-tripped degradation route
    (docs/robustness.md): under an active guard build
    (faults.guard.building), a watchdog trip inside the kernel marks
    the protocol degraded and this call — and every later one — returns
    the plain XLA all_gather result instead of raising, so a degraded
    step completes rather than dies. Without fallback, a trip raises
    DeadlineExceeded with the decoded guard rows."""
    n = int(mesh.shape[axis])
    loc_rows = x.shape[0] // n
    fmt = wcodec.resolve(wire_format)
    if fallback not in (None, "xla"):
        raise ValueError(f"unknown fallback {fallback!r} (None or 'xla')")
    if fallback == "xla" and _guard.is_degraded(PROTOCOL_NAME):
        return _ll_xla_fn(mesh, axis, fmt)(x)
    if wcodec.is_native(fmt):
        local_shape = (2, n, loc_rows) + tuple(x.shape[1:])
        buf_dtype = x.dtype
    else:
        import math

        kw = wcodec.wire_cols(math.prod(x.shape[1:]), fmt)
        local_shape = (2, n, loc_rows, kw)
        buf_dtype = jnp.int8
        name = f"{name}.{fmt.kind}{fmt.block or ''}"
    # the entry barrier keys off CONTEXT creation, not call_count: a new
    # shape/name at a nonzero count still needs the one-time team sync
    fresh = not workspace.contains(name, local_shape, buf_dtype)
    buf = workspace.get(name, local_shape, buf_dtype)
    gbuild = _guard.active_build()
    obuild = _obs.active_build()
    res = _ll_op_fn(mesh, axis, fmt, gbuild, obuild is not None)(
        x, buf, jnp.asarray(call_count, jnp.int32),
        jnp.asarray(fresh, jnp.int32),
    )
    out, new_buf = res[:2]
    workspace.update(name, new_buf)
    if gbuild is None and obuild is None:
        return out
    import numpy as np

    if obuild is not None:
        _obs.consume_rows(res[-1], kernel=PROTOCOL_NAME)
    if gbuild is None:
        return out
    gout = res[2]
    trips = _guard.decode(
        np.asarray(gout).reshape(n, -1, _guard.GUARD_WORDS))
    if trips:
        if fallback == "xla":
            _guard.degrade(PROTOCOL_NAME)
            return _ll_xla_fn(mesh, axis, fmt)(x)
        _guard.check(np.asarray(gout).reshape(
            n, -1, _guard.GUARD_WORDS), context=PROTOCOL_NAME)
    return out


# -- per-segment-signalled producer (exposed delivery semaphores) ------------


def segment_collect_start(dst_slot_at, srcs, send_sem, seg_sem_at,
                          axis: str, n: int, on_send=None):
    """Full-mesh segment push with EXPOSED per-segment delivery
    semaphores — the LL-AG producer discipline opened up for in-kernel
    consumers (kernels/flash_prefill.py): where `fcollect_slots` counts
    every arrival on one shared semaphore (consumable only by a full
    wait), here each (tensor, source-offset) pair gets its OWN slot, so
    a consumer can gate on exactly one segment's arrival while later
    segments are still in flight — the per-segment barrier of the
    reference's SP-AG attention (sp_ag_attention_intra_node.py:105-427)
    carried by semaphore counting, exactly as the parity slots of
    `_ll_ag_kernel` carry the LL flag-validation ordering.

    dst_slot_at(t, i): the symmetric destination slot ref for tensor t,
    source-offset i (1..n-1) — every rank's descriptor for offset i
    names the same static slot (the delivery semaphore lives on the
    destination chip — the PR-2 slot rule). seg_sem_at(t, i): that slot's delivery semaphore.
    srcs: the local tensors to push (each goes to every peer).
    on_send(i): optional per-offset hook (trace instants).

    Returns {offset: [PutHandle per tensor]}; the consumer pairs each
    offset's `wait_recv()`s (delivery gate) with a trailing
    `wait_send()` drain (semaphore balance). Caller must barrier the
    team first (same precondition as fcollect). Works under
    verify.capturing() — the flash-prefill protocol model replays this
    exact producer."""
    me = shmem.my_pe(axis)
    sym = _vcap.active() is not None
    handles = {}
    for i in range(1, n):
        peer = (me + i) % n if sym else jnp.mod(me + i, n)
        if on_send is not None:
            on_send(i)
        ctx = _vcap.tag(step=i) if sym else contextlib.nullcontext()
        with ctx:
            handles[i] = [
                shmem.putmem_nbi(dst_slot_at(t, i), src, send_sem,
                                 seg_sem_at(t, i), peer, axis)
                for t, src in enumerate(srcs)
            ]
    return handles


# -- protocol model (static verifier, triton_dist_tpu.verify) ----------------

from triton_dist_tpu import verify as _v  # noqa: E402


@_v.protocol("low_latency_allgather",
             grid=({"calls": 1}, {"calls": 3},
                   {"calls": 3, "fmt": "fp8"}),
             doc="parity double-buffered LL AG: entry barrier on call 0 "
                 "only; calls=3 exercises the same-parity slot reuse "
                 "(call k+2) the parity counting protocol protects; "
                 "fmt != native pushes the wire image on the same slots")
def _ll_ag_protocol(n, calls=3, fmt="native"):
    """Back-to-back _ll_ag_kernel calls on one context buffer. The
    barrier-free steady state is the point: call k+2 reuses parity
    k%2's slots and semaphores, and its safety rests on the counting
    chain (my call-k+1 waits consumed every peer's call-k+1 delivery,
    which is program-ordered after their call-k consumption) — the HB
    argument the verifier replays, not a barrier."""
    x, buf = _v.ref("x"), _v.ref("buf")
    lsem = _v.sem("local_sem")
    send, recv = _v.sem("send_sem"), _v.sem("recv_sems")
    for k in range(calls):
        parity = k % 2
        if fmt != "native":
            # send edge: pack the shard into the wire image
            _v.read(x.at())
            _v.write(x.at())
        if k == 0:
            shmem.barrier_all(TP_AXIS)  # fresh-context entry barrier
        shmem.fcollect_slots(
            lambda pe: buf.at(parity, pe), x,
            lsem.at(), send.at(), recv.at(parity), TP_AXIS, n,
        )
        for j in range(n):
            _v.read(buf.at(parity, j))  # consume (wire: per-slot decode)


# -- conformance runner (verify.conform) --------------------------------------

from jax.sharding import PartitionSpec as _P  # noqa: E402

from triton_dist_tpu.verify import conform as _conform  # noqa: E402


@_conform.conforms(
    "low_latency_allgather",
    grids=((4, {"calls": 1}), (4, {"calls": 3}),
           (4, {"calls": 3, "fmt": "fp8"})),
    doc="double-buffered LL AG across repeated calls on the interpret mesh")
def _ll_ag_conform(n, calls=3, fmt="native"):
    mesh = _conform.team_mesh(n, (TP_AXIS,))
    if isinstance(mesh, _conform.Skip):
        return mesh
    wf = None if fmt == "native" else fmt

    def run(v):
        buf = create_ll_ag_buffer(v.shape, v.dtype, n, wire_format=wf)
        out = v
        for kk in range(calls):
            out, buf = ll_all_gather(v, buf, kk, TP_AXIS, wire_format=wf)
        return out

    x = jnp.ones((8, 128), jnp.float32)
    return _conform.collect_streams(
        mesh, TP_AXIS, run, in_specs=_P(), args=(x,))
