"""Fused GEMM+ReduceScatter.

TPU-native re-design of the reference's GEMM+RS
(ref: python/triton_dist/kernels/nvidia/gemm_reduce_scatter.py:122-583):
there, a producer GEMM counts finished tiles per M-segment and notifies a
consumer reduce kernel on a separate stream (:232-248, :559-562). Here the
producer and consumer fuse into ONE Pallas ring: the verified ring-RS
protocol (see reduce_scatter.py) with the stage buffer *computed by the MXU*
instead of loaded — each ring hop's transfer overlaps with the matmul of the
next chunk's partial product.

Computes: C_shard = ReduceScatter(a @ b)   [row-parallel TP matmul]
  a: (M, K_loc) per device, b: (K_loc, N) per device -> C_shard: (M/n, N),
  where rank r keeps sum_r' (a_r' @ b_r')[r*M/n:(r+1)*M/n].

Chunk schedule (= ring RS): step s sends accumulated chunk (me-s-1) mod n,
receives chunk (me-s-2) mod n, and contributes its own partial of that
chunk, computed *while the hop is in flight*. The reference's tile-counter
+ notify (:232-234) becomes the per-parity DMA delivery semaphore; its
dedicated rs_stream becomes the ring hop running concurrently with MXU work.

Producer tiling (the reference's fully-tiled producer GEMM, :122-248): two
regimes, chosen by VMEM fit.
  resident — b (K_loc, N) lives in VMEM, A chunk rows stream in (tm, K_loc)
  double-buffered tiles. Minimal HBM traffic (b read once) but needs
  K_loc*N*itemsize of VMEM.
  streamed — when b exceeds the budget (e.g. the Qwen3-32B down-proj at
  tp=8: b = (3200, 5120) bf16 = 32.8 MB): the A chunk (m_loc, K_loc) is
  VMEM-resident instead and b streams through (K_loc, tn) double-buffered
  column tiles. b is re-streamed once per chunk (n passes total) — the
  traffic cost of keeping the ring payload full-width; at the 32B shape
  that is ~275 MB vs a ~340 us MXU-bound compute, so the stream still
  hides under the matmul. (The alternative — one ring per N tile so b
  streams once — trades it for nt x smaller, latency-exposed hops; not
  implemented.)

world=1 tax: not measured on today's code (the rounds 4-5 records that
backed a ratio to XLA's dot were deleted with the rig that produced
them; CHANGES.md, PR 24). The round-6 candidate search reaches the
few-grid-step nk==1 direct-store corner (e.g. (1024, 2560, 3200) — a
4-step sweep) the old 14 MiB prune budget excluded.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.lang import shmem
from triton_dist_tpu.lang.core import (
    cost_estimate,
    fit_tile,
    tpu_call,
    compiler_params,
    next_collective_id,
    interpret_no_headroom,
)
from triton_dist_tpu.runtime.init import TP_AXIS
from triton_dist_tpu.trace import events as trace_ev
from triton_dist_tpu.wire import codec as wcodec


@dataclasses.dataclass(frozen=True)
class GemmRsConfig:
    tile_m: int = 128
    # streamed regime: b column-tile width (rounded to a fitting divisor)
    tile_n: int = 512
    # local blocked-matmul regime (world=1 forced): its own tiles. v5e
    # sweep at the 32B down-proj shape (a (2048,3200) @ b (3200,5120)
    # bf16, slope_timer): (512,1280,640) = 0.364 ms vs XLA's 0.337 —
    # wider N tiles (fewer grid steps) dominate; tk is lane-constrained
    # to multiples of 128 dividing K.
    tile_m_local: int = 512
    tile_n_local: int = 1280
    tile_k_local: int = 1024
    vmem_budget: int = 14 << 20
    # race provocation (ref straggler_option, allreduce.py:137-142)
    straggler_rank: int = -1
    straggler_ns: int = 0


def _col_tile_candidates(n_full: int, cap: int):
    """Divisors of n_full that are lane multiples, descending, <= cap."""
    cands = [t for t in range(128, min(cap, n_full) + 1, 128)
             if n_full % t == 0]
    return sorted(cands, reverse=True) or [n_full]


def _partial_chunk(a_ref, b_ref, chunk, m_loc, tm, a_tile, dst, ld_sems,
                   out_dtype):
    """dst[:] = a[chunk rows] @ b, tiled over M (b resident in VMEM).
    A-tile loads are double-buffered against the MXU so no load is
    exposed past the first (the consumer-side pipelining the reference
    gets from num_stages, gemm_reduce_scatter.py:122-248)."""
    mt = m_loc // tm

    def load(i, slot):
        return pltpu.make_async_copy(
            a_ref.at[pl.ds(chunk * m_loc + i * tm, tm)], a_tile.at[slot],
            ld_sems.at[slot],
        )

    load(0, 0).start()
    for i in range(mt):
        if i + 1 < mt:
            load(i + 1, (i + 1) % 2).start()
        load(i, i % 2).wait()
        dst[pl.ds(i * tm, tm), :] = jnp.dot(
            a_tile[i % 2], b_ref[...], preferred_element_type=jnp.float32
        ).astype(out_dtype)


def _partial_chunk_streamed(a_ref, b_ref, chunk, m_loc, tn, a_chunk,
                            b_tile, a_sem, b_sems, dst, out_dtype):
    """dst[:] = a[chunk rows] @ b with b STREAMED in (K_loc, tn) column
    tiles (double-buffered) and the A chunk VMEM-resident — the regime
    for b too large for VMEM (the reference's producer GEMM is fully
    tiled for the same reason, gemm_reduce_scatter.py:122-248)."""
    n_full = b_ref.shape[1]
    nt = n_full // tn

    cp_a = pltpu.make_async_copy(
        a_ref.at[pl.ds(chunk * m_loc, m_loc)], a_chunk, a_sem
    )
    cp_a.start()

    def bload(j, slot):
        return pltpu.make_async_copy(
            b_ref.at[:, pl.ds(j * tn, tn)], b_tile.at[slot],
            b_sems.at[slot],
        )

    bload(0, 0).start()
    cp_a.wait()
    for j in range(nt):
        if j + 1 < nt:
            bload(j + 1, (j + 1) % 2).start()
        bload(j, j % 2).wait()
        dst[:, pl.ds(j * tn, tn)] = jnp.dot(
            a_chunk[...], b_tile[j % 2], preferred_element_type=jnp.float32
        ).astype(out_dtype)


def _rs_ring(axis, n, straggler, partial_fn, o_ref, acc, stage, st_sem,
             send_sem, recv_sems, credit_sem, tctx=None, fmt=None,
             ostage=None):
    """The shared producer ring: partial_fn(chunk, dst_ref) fills dst with
    this rank's partial of a global chunk; the ring protocol (credit flow
    control, parity recv semaphores) is reduce_scatter._ring_rs_kernel's,
    with the stage computed instead of loaded.

    `fmt` (wire.WireFormat, quantized): the travelling acc slots hold
    the block-scaled wire image — partial_fn fills the f32 `stage`,
    each send edge encodes it into its wire slot, each consume edge
    decodes + adds in f32, and the final arrival stores WITHOUT a
    re-encode (via `ostage` when out_dtype != f32). Identical puts /
    credits / semaphores — the sync skeleton is format-invariant
    (verify-proved), only the payload bytes and the local VPU dataflow
    change.

    `tctx` (trace.events.TraceCtx or None) gates the event records:
    per-hop credit waits and recv waits (sem_wait class) vs per-chunk
    partial-GEMM spans (compute) — the wait-vs-MXU breakdown of the
    producer/consumer overlap this kernel exists for."""
    me = jax.lax.axis_index(axis)
    trace_ev.init_ctx(tctx, rank=me)
    R = trace_ev.REGIONS
    wirefmt = None if fmt is None or wcodec.is_native(fmt) else fmt

    def final_store(src):
        st = pltpu.make_async_copy(src, o_ref, st_sem)
        st.start()
        st.wait()

    if n == 1:
        with trace_ev.span(tctx, R["rs.partial"], payload=0):
            partial_fn(jnp.int32(0), stage if wirefmt else acc.at[0])
        if wirefmt:
            # world=1: nothing travels — the send-edge encode still runs
            # (the measurable codec edge cost), the store is the exact
            # partial (pass-through semantics, like RS at n == 1)
            acc[0] = wcodec.encode_rows(stage[...], wirefmt)
            if ostage is not None:
                ostage[...] = stage[...].astype(o_ref.dtype)
            final_store(ostage if ostage is not None else stage)
        else:
            final_store(acc.at[0])
        return

    left = jnp.mod(me - 1, n)
    right = jnp.mod(me + 1, n)
    shmem.neighbor_barrier(axis, me, n)
    if straggler[1] > 0:
        trace_ev.instant(
            tctx, R["straggle"],
            payload=jnp.where(me == straggler[0], straggler[1], 0))
    shmem.straggler_delay(axis, *straggler)
    # Step-0 incoming targets our slot 1 (free): grant left one credit
    # (flow-control protocol of reduce_scatter._ring_rs_kernel).
    shmem.signal(credit_sem, 1, shmem.SIGNAL_ADD, left, axis,
                 label="credit")

    # Compute our partial of the first travelling chunk, (me-1) mod n.
    with trace_ev.span(tctx, R["rs.partial"], payload=0):
        partial_fn(jnp.mod(me - 1, n), stage if wirefmt else acc.at[0])
    if wirefmt:
        acc[0] = wcodec.encode_rows(stage[...], wirefmt)

    for s in range(n - 1):
        cur, nxt = s % 2, (s + 1) % 2
        with trace_ev.span(tctx, R["rs.credit"], payload=s):
            shmem.signal_wait_until(credit_sem, shmem.CMP_GE, 1,
                                    site="credit", slot=s)
        h = shmem.putmem_nbi(acc.at[nxt], acc.at[cur], send_sem,
                             recv_sems.at[nxt], right, axis)
        # MXU fills the stage with our partial of the incoming chunk while
        # the hop is in flight — this is the producer/consumer overlap.
        with trace_ev.span(tctx, R["rs.partial"], payload=s + 1):
            partial_fn(jnp.mod(me - s - 2, n), stage)
        with trace_ev.span(tctx, R["rs.hop"], payload=s):
            h.wait_send()
            if s + 1 <= n - 2:
                shmem.signal(credit_sem, 1, shmem.SIGNAL_ADD, left,
                             axis, label="credit")
            h.wait_recv(slot=s)
        if wirefmt:
            k = stage.shape[-1]
            val = wcodec.decode_rows(acc[nxt], k, wirefmt, jnp.float32) \
                + stage[...]
            if s == n - 2:
                if ostage is not None:
                    ostage[...] = val.astype(o_ref.dtype)
                else:
                    stage[...] = val  # final arrival: no re-encode
            else:
                acc[nxt] = wcodec.encode_rows(val, wirefmt)
        else:
            acc[nxt] = acc[nxt] + stage[...]

    if wirefmt:
        final_store(ostage if ostage is not None else stage)
    else:
        final_store(acc.at[(n - 1) % 2])


def _src_slot(me, n, chunk, a_arrival):
    # a_arrival: A's row blocks are in ag_gemm ring-arrival order
    # (block s = chunk (me - s) mod n), so global chunk c lives at
    # slot (me - c) mod n — a zero-cost index remap.
    return jnp.mod(me - chunk, n) if a_arrival else chunk


def _gemm_rs_kernel(axis: str, n: int, tm: int, out_dtype, straggler,
                    a_arrival: bool, fmt, build, *refs):
    """Resident regime: b in VMEM, A in (tm, K_loc) tiles. `fmt`
    quantized: partials land in the f32 stage and the ring moves the
    wire image (see _rs_ring)."""
    refs = list(refs)
    a_ref, b_ref, o_ref = refs[:3]
    del refs[:3]
    tbuf = refs.pop(0) if build is not None else None
    tcur = refs.pop() if build is not None else None
    wire = fmt is not None and not wcodec.is_native(fmt)
    ostage = refs.pop(3) if wire and o_ref.dtype != jnp.float32 else None
    (acc, stage, a_tile, ld_sems, st_sem, send_sem, recv_sems,
     credit_sem) = refs
    me = jax.lax.axis_index(axis)
    m_loc = o_ref.shape[0]
    part_dtype = jnp.float32 if wire else out_dtype

    def partial_fn(chunk, dst):
        _partial_chunk(a_ref, b_ref, _src_slot(me, n, chunk, a_arrival),
                       m_loc, tm, a_tile, dst, ld_sems, part_dtype)

    _rs_ring(axis, n, straggler, partial_fn, o_ref, acc, stage, st_sem,
             send_sem, recv_sems, credit_sem,
             tctx=trace_ev.make_ctx(build, tbuf, tcur), fmt=fmt,
             ostage=ostage)


def _gemm_rs_kernel_streamed(axis: str, n: int, tn: int, out_dtype,
                             straggler, a_arrival: bool, fmt, build,
                             *refs):
    """Streamed regime: A chunk in VMEM, b in (K_loc, tn) column tiles.
    `fmt` quantized as in _gemm_rs_kernel."""
    refs = list(refs)
    a_ref, b_ref, o_ref = refs[:3]
    del refs[:3]
    tbuf = refs.pop(0) if build is not None else None
    tcur = refs.pop() if build is not None else None
    wire = fmt is not None and not wcodec.is_native(fmt)
    ostage = refs.pop(4) if wire and o_ref.dtype != jnp.float32 else None
    (acc, stage, a_chunk, b_tile, a_sem, b_sems, st_sem, send_sem,
     recv_sems, credit_sem) = refs
    me = jax.lax.axis_index(axis)
    m_loc = o_ref.shape[0]
    part_dtype = jnp.float32 if wire else out_dtype

    def partial_fn(chunk, dst):
        _partial_chunk_streamed(
            a_ref, b_ref, _src_slot(me, n, chunk, a_arrival), m_loc, tn,
            a_chunk, b_tile, a_sem, b_sems, dst, part_dtype,
        )

    _rs_ring(axis, n, straggler, partial_fn, o_ref, acc, stage, st_sem,
             send_sem, recv_sems, credit_sem,
             tctx=trace_ev.make_ctx(build, tbuf, tcur), fmt=fmt,
             ostage=ostage)


def _local_mm_kernel(nk: int, out_dtype, a_ref, b_ref, o_ref, acc=None):
    """world=1 forced-kernel regime at shapes whose accumulator exceeds
    VMEM: a standard blocked matmul on Mosaic's auto pipeline (grid
    (mt, nt, nk), kk innermost) — there is nothing to scatter, so the
    ring machinery would only add an (M, N)-resident accumulator.

    nk == 1 (full-K tiles, the autotuner's direct-store regime): the dot
    result goes straight to the output block — no f32 accumulator scratch
    and none of its zero + read-modify-write + read VMEM round-trips,
    the store restructuring that closes part of the vs-XLA gap at the
    benched Qwen3-32B down-proj shape."""
    if nk == 1:
        o_ref[...] = jnp.dot(a_ref[...], b_ref[...],
                             preferred_element_type=jnp.float32
                             ).astype(out_dtype)
        return

    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jnp.dot(a_ref[...], b_ref[...],
                        preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _store():
        o_ref[...] = acc[...].astype(out_dtype)


# Trace-time record of the regime the last gemm_rs call dispatched to
# ("resident" | "streamed" | "local_mm" | "xla") — a test/debug hook so
# regime-targeted tests can assert they exercise what they claim to
# (the round-5 reviewer caught a 'streamed' test silently running the
# resident kernel).
_last_regime = None


def last_regime():
    return _last_regime


# Trace-time record of the most recent gemm_rs lowering's fitted tiles
# and pallas grid (same idiom; "path" mirrors the regime). Tests pin
# that a tune-cache winner changes the launched grid.
_last_launch = None


def last_launch():
    return _last_launch


def gemm_rs(
    a: jax.Array,
    b: jax.Array,
    axis: str = TP_AXIS,
    config: Optional[GemmRsConfig] = None,
    out_dtype=None,
    force_kernel: bool = False,
    a_order: str = "rank",
    wire_format=None,
) -> jax.Array:
    """Overlapped ReduceScatter(a @ b); per-device function inside shard_map
    (ref host entry: gemm_reduce_scatter.py:569-583 `gemm_rs`).

    a: (M, K_loc); b: (K_loc, N). Returns rank's reduced chunk (M/n, N).
    On the NATIVE wire out_dtype also sets the cross-rank accumulation
    dtype in the ring — out_dtype=jnp.float32 is the f32-accumulation
    option (doubled hop bytes as a side effect, exact-sum parity with
    psum_scatter). wire_format owns the PAYLOAD ENCODING: quantized
    formats ("fp8"/"int8"/wire.WireFormat) ship the block-scaled wire
    image per hop and accumulate in f32 at the consume edge regardless
    of out_dtype (the codec contract) — ~out_itemsize x fewer ICI bytes
    on the SAME credit/parity protocol (format-invariant,
    verifier-proved). At world=1 nothing travels: quantized gemm_rs
    degrades to the plain dot (pass-through, like RS at n == 1).
    a_order="arrival" consumes A whose row blocks are in ag_gemm's
    ring-arrival order (see ag_gemm c_order) by remapping the chunk
    index — free in the kernel, a block un-permute on fallback paths.

    Tracing (trace.building active): one extra trailing output — the
    ring regimes' device trace buffer (credit/hop waits vs partial-GEMM
    spans); local_mm/xla paths return an empty buffer.
    """
    global _last_regime, _last_launch
    cfg = config or GemmRsConfig()
    _last_launch = {"kernel": "gemm_rs", "path": "xla",
                    "overridden": config is not None}
    out_dtype = out_dtype or a.dtype
    assert a_order in ("rank", "arrival"), a_order
    a_arrival = a_order == "arrival"
    fmt = wcodec.resolve(wire_format)
    wirefmt = None if wcodec.is_native(fmt) else fmt
    build = trace_ev.active_build()

    def with_trace(res, tbuf=None):
        return trace_ev.with_trace(build, res, tbuf)

    n = jax.lax.axis_size(axis)
    m, k_loc = a.shape
    k2, n_full = b.shape
    assert k_loc == k2, f"K mismatch {k_loc} vs {k2}"
    if n == 1 and not force_kernel:
        # Nothing to scatter at world=1; XLA's matmul wins (see ag_gemm).
        _last_regime = "xla"
        return with_trace(
            jnp.dot(a, b, preferred_element_type=jnp.float32).astype(
                out_dtype
            ))
    if m % n:
        raise ValueError(f"M={m} not divisible by axis size {n}")
    m_loc = m // n
    # degrade to a dividing tile rather than raising: only the resident
    # regime tiles A by tm (streamed/local_mm never use it)
    tm = fit_tile(cfg.tile_m, m_loc)
    in_itemsize = jnp.dtype(a.dtype).itemsize
    out_itemsize = jnp.dtype(out_dtype).itemsize
    kw = wcodec.wire_cols(n_full, fmt) if wirefmt else 0
    if wirefmt:
        # wire acc slots (int8) + f32 stage (+ out-dtype staging buffer
        # for the final store when out_dtype != f32)
        ring_bytes = 2 * m_loc * kw + m_loc * n_full * 4
        if out_dtype != jnp.float32:
            ring_bytes += m_loc * n_full * out_itemsize
    else:
        # Ring residents shared by both regimes: acc 2x(m_loc, N) + stage
        # + the (m_loc, N) value Mosaic materializes for the fold
        # (acc + stage), as the chip compiler sizes it (PR 24).
        ring_bytes = 4 * m_loc * n_full * out_itemsize
    # resident regime adds b plus the A tile double buffer.
    vmem_resident = (
        ring_bytes
        + k_loc * n_full * in_itemsize
        + 2 * tm * k_loc * in_itemsize
    )

    def vmem_streamed(tn):
        # A chunk resident + b column-tile double buffer.
        return (
            ring_bytes
            + m_loc * k_loc * in_itemsize
            + 2 * k_loc * tn * in_itemsize
        )

    def xla_path():
        a_ = a
        if a_arrival and n > 1:
            from triton_dist_tpu.kernels.allgather_gemm import (
                arrival_to_rank_order,
            )

            a_ = arrival_to_rank_order(a_, axis)
        partial = jnp.dot(a_, b, preferred_element_type=jnp.float32)
        if n == 1:
            return partial.astype(out_dtype)
        if wirefmt:
            # ppermute replay of the wire ring's exact fold order
            from triton_dist_tpu.kernels.reduce_scatter import (
                _wire_rs_xla,
            )

            return _wire_rs_xla(partial, axis, n, wirefmt).astype(
                out_dtype)
        return jax.lax.psum_scatter(partial.astype(out_dtype), axis,
                                    tiled=True)

    if interpret_no_headroom() and not force_kernel:
        _last_regime = "xla"
        return with_trace(xla_path())

    hop_bytes = m_loc * kw if wirefmt else m_loc * n_full * out_itemsize
    cost = cost_estimate(
        flops=2 * m * k_loc * n_full,
        bytes_accessed=(m * k_loc + k_loc * n_full) * in_itemsize
        + m_loc * n_full * out_itemsize,
        remote_bytes=(n - 1) * hop_bytes,
    )
    cid = next_collective_id(f"gemm_rs_{axis}") if n > 1 else None

    def _ring_call(kernel, out_shape, in_specs, out_specs, scratch,
                   params, cost_est):
        if build is not None:
            out_shape = (out_shape, trace_ev.out_shape(build))
            out_specs = (out_specs, trace_ev.out_spec())
            scratch = scratch + [trace_ev.cursor_scratch()]
        res = tpu_call(
            kernel, out_shape=out_shape, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch,
            compiler_params=params, cost_estimate=cost_est,
        )(a, b)
        if build is not None:
            return with_trace(res[0], res[1])
        return res

    def _acc_stage_scratch(extra):
        """Ring scratch head: acc slots + stage (+ wire ostage), then
        the regime's own buffers — the order the kernels unpack."""
        if wirefmt:
            head = [
                pltpu.VMEM((2, m_loc, kw), jnp.int8),
                pltpu.VMEM((m_loc, n_full), jnp.float32),
            ] + extra
            if out_dtype != jnp.float32:
                head.append(pltpu.VMEM((m_loc, n_full), out_dtype))
            return head
        return [
            pltpu.VMEM((2, m_loc, n_full), out_dtype),
            pltpu.VMEM((m_loc, n_full), out_dtype),
        ] + extra

    if vmem_resident <= cfg.vmem_budget:
        _last_regime = "resident"
        _last_launch = {"kernel": "gemm_rs", "path": "resident",
                        "tm": tm, "overridden": config is not None}
        return _ring_call(
            functools.partial(_gemm_rs_kernel, axis, n, tm, out_dtype,
                              (cfg.straggler_rank, cfg.straggler_ns),
                              a_arrival, wirefmt, build),
            jax.ShapeDtypeStruct((m_loc, n_full), out_dtype),
            [
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pltpu.VMEM),
            ],
            pl.BlockSpec(memory_space=pl.ANY),
            _acc_stage_scratch([pltpu.VMEM((2, tm, k_loc), a.dtype)]) + [
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.REGULAR,
            ],
            compiler_params(
                has_side_effects=True,
                # barrier semaphore only exists in the n>1 kernel body (see
                # neighbor_barrier); collective_id must be omitted at n=1.
                collective_id=cid,
                vmem_limit_bytes=cfg.vmem_budget + (2 << 20),
            ),
            # launch_metadata analog (ref allgather_gemm.py:145-155)
            cost,
        )

    # Streamed regime: pick the widest b column tile that fits.
    tn_cands = _col_tile_candidates(n_full, cfg.tile_n)
    tn = next((t for t in tn_cands if vmem_streamed(t) <= cfg.vmem_budget),
              None)
    if tn is None and force_kernel and n > 1:
        tn = tn_cands[-1]  # forced: smallest tile, budget overridden below
    if n > 1 and tn is not None:
        _last_regime = "streamed"
        _last_launch = {"kernel": "gemm_rs", "path": "streamed",
                        "tn": tn, "overridden": config is not None}
        return _ring_call(
            functools.partial(
                _gemm_rs_kernel_streamed, axis, n, tn, out_dtype,
                (cfg.straggler_rank, cfg.straggler_ns), a_arrival,
                wirefmt, build),
            jax.ShapeDtypeStruct((m_loc, n_full), out_dtype),
            [
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            pl.BlockSpec(memory_space=pl.ANY),
            _acc_stage_scratch([
                pltpu.VMEM((m_loc, k_loc), a.dtype),
                pltpu.VMEM((2, k_loc, tn), b.dtype),
            ]) + [
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.REGULAR,
            ],
            compiler_params(
                has_side_effects=True,
                collective_id=cid,
                vmem_limit_bytes=max(cfg.vmem_budget,
                                     vmem_streamed(tn)) + (2 << 20),
            ),
            cost_estimate(
                flops=2 * m * k_loc * n_full,
                # b re-streams once per chunk in this regime
                bytes_accessed=(m * k_loc + n * k_loc * n_full)
                * in_itemsize + m_loc * n_full * out_itemsize,
                remote_bytes=(n - 1) * hop_bytes,
            ),
        )

    if n == 1:
        # force_kernel at world=1 past the resident budget: blocked matmul.
        _last_regime = "local_mm"
        tm_l = fit_tile(cfg.tile_m_local, m)
        tn_l = fit_tile(cfg.tile_n_local, n_full)
        tk_l = fit_tile(cfg.tile_k_local, k_loc)
        nk = k_loc // tk_l
        # Mosaic's auto pipeline double-buffers each block operand; wide
        # autotuner candidates (e.g. full-K direct-store tiles) may need
        # more than the default budget — grant what the tiling implies.
        vmem_local = 2 * (tm_l * tk_l + tk_l * tn_l) * in_itemsize \
            + 2 * tm_l * tn_l * out_itemsize \
            + (tm_l * tn_l * 4 if nk > 1 else 0)
        _last_launch = {"kernel": "gemm_rs", "path": "local_mm",
                        "tm": tm_l, "tn": tn_l, "tk": tk_l,
                        "grid": (m // tm_l, n_full // tn_l, nk),
                        "overridden": config is not None}
        return with_trace(tpu_call(
            functools.partial(_local_mm_kernel, nk, out_dtype),
            grid=(m // tm_l, n_full // tn_l, nk),
            out_shape=jax.ShapeDtypeStruct((m, n_full), out_dtype),
            in_specs=[
                pl.BlockSpec((tm_l, tk_l), lambda i, j, kk: (i, kk),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((tk_l, tn_l), lambda i, j, kk: (kk, j),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((tm_l, tn_l), lambda i, j, kk: (i, j),
                                   memory_space=pltpu.VMEM),
            # nk==1 stores the dot directly: no accumulator scratch
            scratch_shapes=(
                [pltpu.VMEM((tm_l, tn_l), jnp.float32)] if nk > 1 else []
            ),
            compiler_params=compiler_params(
                vmem_limit_bytes=max(cfg.vmem_budget, vmem_local)
                + (2 << 20),
            ),
            cost_estimate=cost,
        )(a, b))

    _last_regime = "xla"
    return with_trace(xla_path())


def gemm_rs_ref(a: jax.Array, b: jax.Array, axis: str = TP_AXIS) -> jax.Array:
    """Unfused XLA reference path."""
    partial = jnp.dot(a, b, preferred_element_type=jnp.float32).astype(a.dtype)
    return jax.lax.psum_scatter(partial, axis, tiled=True)


# -- protocol model (static verifier, triton_dist_tpu.verify) ----------------

from triton_dist_tpu import verify as _v  # noqa: E402
from triton_dist_tpu.kernels.reduce_scatter import (  # noqa: E402
    _ring_rs_skeleton,
)


@_v.protocol("gemm_reduce_scatter",
             grid=({}, {"fmt": "fp8"}, {"fmt": "int8"}),
             doc="GEMM+RS producer ring (_rs_ring): the RS credit ring "
                 "with the stage filled by the partial GEMM (fmt != "
                 "native: wire-image acc slots, same sync skeleton)")
def _gemm_rs_protocol(n, fmt="native"):
    a, b = _v.ref("a"), _v.ref("b")

    def fill_stage(s):
        # partial_fn: synchronous MXU fill of acc[0] / stage from the
        # rank-local A chunk and B shard (no cross-rank content beyond
        # the ring the skeleton carries)
        _v.read(a.at())
        _v.read(b.at())

    _ring_rs_skeleton(n, fill_stage, fmt=fmt)


# -- conformance runner (verify.conform) --------------------------------------

from jax.sharding import PartitionSpec as _P  # noqa: E402

from triton_dist_tpu.verify import conform as _conform  # noqa: E402


@_conform.conforms(
    "gemm_reduce_scatter",
    grids=((4, {}), (4, {"fmt": "fp8"}), (4, {"fmt": "int8"})),
    doc="resident-regime fused GEMM+RS ring on the interpret mesh")
def _gemm_rs_conform(n, fmt="native"):
    mesh = _conform.team_mesh(n, (TP_AXIS,))
    if isinstance(mesh, _conform.Skip):
        return mesh
    wf = None if fmt == "native" else fmt
    a = jnp.ones((8, 128), jnp.float32)
    b = jnp.ones((128, 128), jnp.float32)
    return _conform.collect_streams(
        mesh, TP_AXIS,
        lambda a_, b_: gemm_rs(a_, b_, TP_AXIS, wire_format=wf),
        in_specs=(_P(), _P()), args=(a, b))
