"""Fused AllGather+GEMM — the flagship overlapped kernel.

TPU-native re-design of the reference's AG+GEMM
(ref: python/triton_dist/kernels/nvidia/allgather_gemm.py:158-575): there, a
copy-engine producer pushes shards while a persistent GEMM consumer spins on
per-rank barrier words before each M-tile (dl.wait :236, consume_token :237),
with a rank-offset threadblock swizzle so locally-available tiles compute
first (:224-229). Here the same overlap is ONE Pallas kernel:

  grid = (n_ranks, m_tiles, n_tiles, k_tiles) — outer dim s is the ring
  step. step s computes chunk (me - s) mod n: own shard at s=0 (the swizzle
  analog: zero-wait start), while the ring forward of the previous chunk is
  in flight. The per-rank barrier words become per-step DMA delivery
  semaphores; `dl.wait`+`consume_token` become `wait_recv` ordered before
  the A-tile loads by program order.

Consumer MFU design (the part the reference gets from its persistent-TMA
GEMM, allgather_gemm.py:158-264): the A i-strip is cached in VMEM across
the whole j sweep — each (tm, tk) block is DMA'd once per ring step
instead of once per output column tile, cutting A HBM traffic by nt x —
and the own shard is read straight from a_ref, so the workspace copy and
the ring forward start ride the first tiles' compute instead of blocking
it.

world=1 tax: not measured on today's code. The rounds 3-5 records
that backed a ratio to XLA's matmul here were deleted with the rig that
produced them (CHANGES.md, PR 24); at world=1 the forced kernel has
nothing to overlap and can only lose by its grid-step overhead plus
accumulator traffic. How much is for the chip benchmark to say.

epilogue="silu_pair" fuses the TP-MLP gate/up activation into the store:
b is the fused (K, 2*I) gate|up weight, the kernel keeps one accumulator
per half and writes silu(gate_acc) * up_acc — the f32 intermediate never
round-trips through HBM (the reference fuses the same epilogue into its
persistent GEMM, layers/nvidia/tp_mlp.py dist_triton_fwd).

Computes: C = AllGather(a_shard) @ b   [column-parallel TP matmul]
  a_shard: (M/n, K) per device, b: (K, N_loc) per device -> C: (M, N_loc).
Also returns the gathered A (the reference's ctx workspace is reusable by
later kernels, allgather_gemm.py:458-487).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.faults import guard as _guard
from triton_dist_tpu.faults import plan as _fplan
from triton_dist_tpu.lang import shmem
from triton_dist_tpu.lang.core import (
    tpu_call,
    compiler_params,
    cost_estimate,
    fit_tile,
    next_collective_id,
    cdiv,
    interpret_no_headroom,
)
from triton_dist_tpu.obs import stats as _obs
from triton_dist_tpu.runtime.init import TP_AXIS
from triton_dist_tpu.trace import events as trace_ev
from triton_dist_tpu.verify import conform as _conform
from triton_dist_tpu.wire import codec as wcodec


@dataclasses.dataclass(frozen=True)
class AgGemmConfig:
    """Tile configuration (the reference's context tile fields,
    ref: allgather_gemm.py:417-456 BLOCK_M/N/K, num_stages)."""

    # v5e sweep at (M=2048, K=5120, N=6400) bf16 (benchmark/
    # sweep_ag_gemm.py + slope_timer, round-5 methodology): what
    # dominates at these shapes is PER-GRID-STEP overhead, not HBM
    # traffic — the near-full-width N tile (nt=2) with a small M tile
    # beats every narrower sweep. (Local sweep readings for these tiles
    # ran ~0.98x XLA; the DRIVER artifact has them at ~1.10x — see the
    # module docstring for which number is the claim.) tn is
    # lane-constrained to multiples of 128 dividing N_loc; _fit()
    # degrades both tiles gracefully at other shapes.
    tile_m: int = 256
    tile_n: int = 3200
    tile_k: int = 512
    # VMEM ceiling for the auto fallback / cache-mode decision.
    vmem_budget: int = 15 << 20
    # A-strip VMEM cache: one DMA per (i, kk) block per ring step instead
    # of one per output tile. Cuts A HBM traffic nt x but pays a dynamic
    # cache index per dot — a net loss at the bench shapes (1.12x vs
    # 1.05x); worth flipping via the autotuner when A re-reads dominate
    # (small K, very wide N).
    cache_a: bool = False
    # race provocation (ref straggler_option, allgather_gemm.py:602-603):
    # stall this rank for straggler_ns at the producer entry
    straggler_rank: int = -1
    straggler_ns: int = 0


def _silu_mul_f32(g, u):
    return g * jax.nn.sigmoid(g) * u


def _ag_gemm_kernel(axis: str, n: int, mt: int, nt: int, nk: int,
                    tm: int, tn: int, tk: int, out_dtype, straggler,
                    need_ws: bool, cache_a: bool, silu_pair: bool,
                    arrival: bool, grouped: bool, wire, build, gbuild,
                    obuild, *refs):
    # `wire`: None for the native payload, else (fmt, k) — the A shard /
    # ring workspace hold the block-scaled int8 wire image (payload
    # columns [0, k), per-row f32 scales bitcast at [k, k+4)); the ring
    # forward moves wire bytes on the IDENTICAL protocol, and the
    # consumer dequantizes each A tile at the consume edge, right
    # before the dot (see ag_gemm's wire_format doc).
    refs = list(refs)
    a_ref, b_ref = refs[:2]
    del refs[:2]
    b2_ref = refs.pop(0) if silu_pair else None
    ws_ref, c_ref = refs[:2]
    del refs[:2]
    tbuf = refs.pop(0) if build is not None else None
    gbuf = refs.pop(0) if gbuild is not None else None
    obuf = refs.pop(0) if obuild is not None else None
    ocur = refs.pop() if obuild is not None else None
    gcur = refs.pop() if gbuild is not None else None
    a_buf = refs.pop(0)
    scale_buf = refs.pop(0) if wire is not None else None
    # nk==1 (full-K tiles) stores the dot straight to the output block:
    # no accumulator scratch is allocated (see the consumer below)
    acc = refs.pop(0) if nk > 1 else None
    acc2 = refs.pop(0) if (silu_pair and nk > 1) else None
    stage = None if arrival else refs.pop(0)
    tcur = refs.pop() if build is not None else None
    sc_sem = None
    if wire is not None:
        if arrival:
            ld_sems, sc_sem, cp_sem, send_sem, recv_sems = refs
            st_sem = None
        else:
            ld_sems, sc_sem, st_sem, cp_sem, send_sem, recv_sems = refs
    elif arrival:
        ld_sems, cp_sem, send_sem, recv_sems = refs
        st_sem = None
    else:
        ld_sems, st_sem, cp_sem, send_sem, recv_sems = refs
    tctx = trace_ev.make_ctx(build, tbuf, tcur)
    octx = _obs.make_ctx(obuild, obuf, ocur)
    R = trace_ev.REGIONS
    s = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    kk = pl.program_id(3)
    me = jax.lax.axis_index(axis)
    gctx = _guard.make_ctx(gbuild, gbuf, gcur, tctx=tctx, octx=octx)
    m_loc = a_ref.shape[0]
    chunk = jnp.mod(me - s, n)
    right = jnp.mod(me + 1, n)
    total = mt * nt * nk
    flat = (i * nt + j) * nk + kk

    def fwd_copy(c_idx, step):
        """Ring descriptor for forwarding chunk rows to the right neighbor.
        Reconstructed identically wherever we need to start or wait it."""
        return pltpu.make_async_remote_copy(
            src_ref=ws_ref.at[pl.ds(c_idx * m_loc, m_loc)],
            dst_ref=ws_ref.at[pl.ds(c_idx * m_loc, m_loc)],
            send_sem=send_sem,
            recv_sem=recv_sems.at[step],
            device_id={axis: right},
            device_id_type=pltpu.DeviceIdType.MESH,
        )

    def local_copy():
        return pltpu.make_async_copy(
            a_ref, ws_ref.at[pl.ds(me * m_loc, m_loc)], cp_sem
        )

    def a_load(ii, kki, slot):
        """Start the (tm, tk) A-block DMA into a_buf[slot]. The own shard
        (s=0) reads straight from a_ref — its workspace copy is NOT on
        the consumer's critical path; remote chunks read the ring
        workspace."""
        dst = a_buf.at[slot]
        sem = ld_sems.at[slot]

        @pl.when(s == 0)
        def _own():
            pltpu.make_async_copy(
                a_ref.at[pl.ds(ii * tm, tm), pl.ds(kki * tk, tk)],
                dst, sem,
            ).start()

        if n > 1:
            @pl.when(s > 0)
            def _remote():
                pltpu.make_async_copy(
                    ws_ref.at[pl.ds(chunk * m_loc + ii * tm, tm),
                              pl.ds(kki * tk, tk)],
                    dst, sem,
                ).start()

    def scale_fill():
        """Wire mode: fetch THIS row block's scale stripe (the trailing
        lane of the wire image) once per (ring step, i) — the per-row
        scales are independent of the K tile and the j sweep, so one
        (tm, LANE) DMA at the first tile of the strip serves every
        dot of the sweep (re-fetching per tile would put nk*nt-1
        redundant small DMAs + waits on the consumer path)."""
        if wire is None:
            return

        @pl.when(jnp.logical_and(j == 0, kk == 0))
        def _fill():
            @pl.when(s == 0)
            def _own():
                pltpu.make_async_copy(
                    a_ref.at[pl.ds(i * tm, tm),
                             pl.ds(wire[1], wcodec.LANE)],
                    scale_buf, sc_sem,
                ).start()

            if n > 1:
                @pl.when(s > 0)
                def _remote():
                    pltpu.make_async_copy(
                        ws_ref.at[pl.ds(chunk * m_loc + i * tm, tm),
                                  pl.ds(wire[1], wcodec.LANE)],
                        scale_buf, sc_sem,
                    ).start()

            pltpu.make_async_copy(
                ws_ref.at[pl.ds(0, tm), pl.ds(0, wcodec.LANE)],
                scale_buf, sc_sem,
            ).wait()

    # what one ring forward actually puts on the wire, per step (wire
    # legs move the int8 image: kw columns x 1 byte)
    ws_send_bytes = m_loc * ws_ref.shape[1] \
        * jnp.dtype(ws_ref.dtype).itemsize

    def meter_fwd():
        if octx is not None:
            octx.add_bytes(ws_send_bytes)

    def note_fwd(c_idx, step):
        # conformance record for a ring forward start; the wait notes
        # reconstruct the idents (the descriptor itself is rebuilt in a
        # later grid step, so no PutHandle can be threaded there)
        _conform.note_put(send_sem, recv_sems.at[step], right,
                          ws_ref.at[pl.ds(c_idx * m_loc, m_loc)],
                          ws_send_bytes)

    def a_wait(slot):
        # descriptor only carries the byte count for the semaphore wait
        with _obs.span(tctx, octx, R["ag.a_wait"], payload=flat, aux=s):
            pltpu.make_async_copy(
                ws_ref.at[pl.ds(0, tm), pl.ds(0, tk)], a_buf.at[slot],
                ld_sems.at[slot],
            ).wait()

    def a_dequant(raw):
        """Consume edge: dequantize the wire A tile right before the
        MXU dot (per-row f32 scale from the strip's scale stripe)."""
        if wire is None:
            return raw
        fmtw, _k, a_dtype = wire
        sc = jax.lax.bitcast_convert_type(
            scale_buf[:, :wcodec.SCALE_BYTES], jnp.float32)
        if fmtw.kind == "fp8":
            raw = jax.lax.bitcast_convert_type(raw, jnp.float8_e4m3fn)
        return (raw.astype(jnp.float32) * sc[:, None]).astype(a_dtype)

    # trace + obs init: the first grid step, before any emit below (the
    # meter must be zeroed before the straggle instant can tick it)
    @pl.when(jnp.logical_and(flat == 0, s == 0))
    def _trace_init():
        trace_ev.init_ctx(tctx, rank=me)
        _obs.init_ctx(octx, rank=me,
                      fmt=_obs.fmt_code(wire[0] if wire else None))
        if straggler[1] > 0:
            _obs.instant(
                tctx, octx, R["straggle"],
                payload=jnp.where(me == straggler[0], straggler[1], 0))

    if gctx is not None:
        # guard init likewise rides the first grid step (grid order
        # guarantees it precedes every ring wait); gated on gctx so the
        # unguarded build traces byte-identically
        @pl.when(jnp.logical_and(flat == 0, s == 0))
        def _guard_init():
            _guard.init_ctx(gctx, rank=me)

    # --- producer side: runs once per ring step, before that step's tiles.
    if need_ws:
        @pl.when(jnp.logical_and(flat == 0, s == 0))
        def _first_step():
            if n > 1:
                shmem.neighbor_barrier(axis, me, n)
                shmem.straggler_delay(axis, *straggler)
            local_copy().start()
            if n > 1 and total == 1:
                # single-tile grids have no later slot to defer to
                local_copy().wait()
                fwd_copy(me, 0).start()
                note_fwd(me, 0)
                meter_fwd()

        if n > 1 and total > 1:
            # the forward start needs the local copy done, but the
            # consumer does not (it reads a_ref): defer both off the
            # first tile so compute starts immediately
            @pl.when(jnp.logical_and(flat == 1, s == 0))
            def _start_ring():
                local_copy().wait()
                fwd_copy(me, 0).start()
                note_fwd(me, 0)
                meter_fwd()

        if n == 1:
            # gathered-output-only copy: drain before kernel exit
            @pl.when(flat == total - 1)
            def _drain():
                local_copy().wait()

    if n > 1:
        @pl.when(jnp.logical_and(flat == 0, s > 0))
        def _later_steps():
            prev_chunk = jnp.mod(me - s + 1, n)
            prev = fwd_copy(prev_chunk, s - 1)
            idents = _conform.put_idents(send_sem, recv_sems.at[s - 1])
            with _obs.span(tctx, octx, R["ag.ring_wait"], payload=s):
                prev.wait_send()
                _conform.note_wait_send(idents)
                # consumer wait: this step's A rows have landed
                # (the dl.wait/consume_token contract, ref :236-237).
                if gctx is None:
                    prev.wait_recv()
                    _conform.note_wait_recv(idents)
                else:
                    # bounded ring-step watchdog: readiness is the full
                    # chunk's byte count (what a DMA semaphore tallies)
                    _guard.set_progress(s, ctx=gctx)
                    amount = (m_loc * ws_ref.shape[1]
                              * jnp.dtype(ws_ref.dtype).itemsize)
                    _guard.watchdog_wait(
                        prev.wait_recv, recv_sems.at[s - 1], amount,
                        "ring", slot=s, ctx=gctx)
                    _conform.note_wait_recv(idents)

            @pl.when(s < n - 1)
            def _():
                fwd_copy(chunk, s).start()
                note_fwd(chunk, s)
                meter_fwd()

    # --- A-block staging.
    if cache_a:
        # strip cache: the j==0 sweep DMAs each (i, kk) block once with a
        # one-block lookahead; j>0 sweeps reuse it from VMEM.
        @pl.when(j == 0)
        def _fill():
            @pl.when(kk == 0)
            def _cold():
                a_load(i, 0, 0)

            @pl.when(kk + 1 < nk)
            def _ahead():
                a_load(i, kk + 1, kk + 1)

            a_wait(kk)

        a_tile = a_buf[kk]
    else:
        slot = jnp.mod(flat, 2)

        @pl.when(flat == 0)
        def _cold():
            a_load(0, 0, 0)

        nxt = flat + 1

        @pl.when(nxt < total)
        def _ahead():
            kk_n = jnp.mod(nxt, nk)
            i_n = nxt // (nk * nt)
            a_load(i_n, kk_n, jnp.mod(nxt, 2))

        scale_fill()
        a_wait(slot)
        a_tile = a_dequant(a_buf[slot])

    # --- consumer: this K block's partial product on the MXU. nk > 1
    # accumulates in f32 VMEM scratch; nk == 1 (full-K tile) keeps the
    # single dot in registers and stores it directly — the zero +
    # read-modify-write + read round-trips of the accumulator never
    # happen (the store restructuring behind the wide-tk autotuner
    # candidates).
    if nk > 1:
        @pl.when(kk == 0)
        def _zero():
            acc[...] = jnp.zeros_like(acc)
            if silu_pair:
                acc2[...] = jnp.zeros_like(acc2)

    # grouped mode: b blocks are (1, tk, tn) slices of a per-expert weight
    # stack, selected by the M-tile's expert (block-diagonal grouped GEMM)
    b_tile = b_ref[0] if grouped else b_ref[...]
    contrib = jnp.dot(a_tile, b_tile, preferred_element_type=jnp.float32)
    contrib2 = None
    if silu_pair:
        b2_tile = b2_ref[0] if grouped else b2_ref[...]
        contrib2 = jnp.dot(
            a_tile, b2_tile, preferred_element_type=jnp.float32
        )
    if nk > 1:
        acc[...] += contrib
        if silu_pair:
            acc2[...] += contrib2

    # --- store the finished output tile.
    @pl.when(kk == nk - 1)
    def _store():
        _obs.instant(tctx, octx, R["ag.tile"], payload=flat, aux=s)
        g = contrib if nk == 1 else acc[...]
        if silu_pair:
            u = contrib2 if nk == 1 else acc2[...]
            out = _silu_mul_f32(g, u).astype(out_dtype)
        else:
            out = g.astype(out_dtype)
        if arrival:
            # C in ring-arrival order: the block index (s*mt+i, j) is a
            # pure grid function, so the store is Mosaic's auto output
            # pipeline — zero scalar overhead, double-buffered for free.
            c_ref[...] = out
        else:
            stage[...] = out
            st = pltpu.make_async_copy(
                stage,
                c_ref.at[pl.ds(chunk * m_loc + i * tm, tm),
                         pl.ds(j * tn, tn)],
                st_sem,
            )
            st.start()
            st.wait()


# trace-time record of the most recent ag_gemm lowering decision — the
# fitted tiles and pallas grid that actually launched (or "xla" when the
# call fell back). Debug/test hook in the last_regime() idiom
# (gemm_reduce_scatter.py): tests pin that a tune-cache winner changes
# the launched grid without reverse-engineering the jaxpr.
_last_launch = None


def last_launch():
    return _last_launch


def arrival_to_rank_order(c, axis: str):
    """Permute an arrival-order C (ring-step-major row blocks: block s
    holds global chunk (me - s) mod n) back to global rank order."""
    n = jax.lax.axis_size(axis)
    if n == 1:
        return c
    me = jax.lax.axis_index(axis)
    blocks = c.reshape(n, c.shape[0] // n, *c.shape[1:])
    idx = jnp.mod(me - jnp.arange(n), n)
    return jnp.take(blocks, idx, axis=0).reshape(c.shape)


def ag_gemm(
    a_shard: jax.Array,
    b: jax.Array,
    axis: str = TP_AXIS,
    config: Optional[AgGemmConfig] = None,
    return_gathered: bool = False,
    out_dtype=None,
    force_kernel: bool = False,
    epilogue: Optional[str] = None,
    c_order: str = "rank",
    wire_format=None,
):
    """Overlapped AllGather(a_shard) @ b; per-device function inside shard_map
    (ref host entry: allgather_gemm.py:534-575 `ag_gemm`).

    a_shard: (M/n, K); b: (K, N_loc). Returns C (M, N_loc), and the gathered
    A (M, K) when return_gathered. out_dtype=float32 lets a following
    elementwise epilogue fuse without a bf16 round-trip.

    epilogue="silu_pair": b is a (w_gate, w_up) pair, each (K, I), and
    the result is silu(A@gate) * (A@up) of shape (M, I) — in the kernel
    the f32 intermediate never reaches HBM; at world=1 XLA's own epilogue
    fusion over two clean dots wins and the call short-circuits to it.

    c_order="arrival" returns C's row blocks in RING-ARRIVAL order
    (block s = global chunk (me - s) mod n; identical to rank order at
    world=1). In this layout the output block index is a pure grid
    function, so the store runs on Mosaic's auto output pipeline instead
    of manual DMA+wait — measurably faster — and an order-aware consumer
    (gemm_rs(a_order="arrival"), the TP-MLP down-proj) indexes chunks by
    arrival slot at zero cost. Use arrival_to_rank_order to un-permute
    for order-sensitive consumers.

    wire_format ("fp8"/"int8"/wire.WireFormat, per-row scales only):
    the AG wire leg moves the block-scaled int8 wire image instead of
    native A rows — a_shard is encoded ONCE at the send edge (pack),
    the ring forwards wire bytes on the IDENTICAL semaphore protocol
    (format-invariant, verifier-proved), and the consumer dequantizes
    each A tile at the consume edge right before its dot (every row —
    including the own shard — passes the codec, so the result equals
    the roundtrip-composed XLA path). ~itemsize x fewer ICI bytes per
    ring step; drift per wire.numerics. Dense form only (no silu_pair /
    grouped); K must be lane-aligned. return_gathered returns the
    DECODED gathered A.

    Tracing (trace.building active): one extra trailing output — the
    device trace buffer (ring-step recv waits, per-tile A-load waits,
    tile-store instants); fallback paths return an empty buffer.
    """
    cfg = config or AgGemmConfig()
    global _last_launch
    _last_launch = {"kernel": "ag_gemm", "path": "xla",
                    "overridden": config is not None}
    build = trace_ev.active_build()
    gbuild = _guard.active_build()
    obuild = _obs.active_build()

    def with_trace(res, tbuf=None):
        return trace_ev.with_trace(build, res, tbuf)

    def with_fallback(res):
        # fallback paths owe every trailing buffer (empty streams)
        return _obs.with_stats(
            obuild, _guard.with_guard(gbuild, with_trace(res)))
    out_dtype = out_dtype or a_shard.dtype
    silu_pair = epilogue == "silu_pair"
    assert epilogue in (None, "silu_pair"), f"unknown epilogue {epilogue}"
    assert c_order in ("rank", "arrival"), c_order
    arrival = c_order == "arrival"
    n = jax.lax.axis_size(axis)
    m_loc, k = a_shard.shape
    if silu_pair:
        assert isinstance(b, tuple) and len(b) == 2, (
            "silu_pair takes b=(w_gate, w_up)"
        )
        b_gate, b_up = b
        assert b_gate.shape == b_up.shape
        shp = b_gate.shape
        assert not return_gathered, "silu_pair does not return gathered A"
    else:
        shp = b.shape
    # 3-D b is the GROUPED form (E, K, N_loc): a_shard rows are E
    # fixed-capacity expert blocks (moe_utils.pack_by_expert) and block e
    # multiplies b[e] — the fused AG + grouped GEMM of the MoE pair
    # (ref: kernels/nvidia/allgather_group_gemm.py:535 consumer; the ring
    # machinery is shared with the dense kernel, per-segment waits become
    # the same per-ring-step DMA semaphores).
    grouped = len(shp) == 3
    e_groups = shp[0] if grouped else 1
    k2, width = shp[-2], shp[-1]
    i_loc = width
    n_loc = 2 * width if silu_pair else width
    assert k == k2, f"K mismatch {k} vs {k2}"
    if grouped:
        assert m_loc % e_groups == 0, (
            f"packed rows {m_loc} must be E={e_groups} equal blocks"
        )
    cap_pad = m_loc // e_groups

    fmt = wcodec.resolve(wire_format)
    wire = not wcodec.is_native(fmt)
    if wire:
        if silu_pair or grouped:
            raise ValueError(
                "quantized wire supports the dense ag_gemm form only "
                f"(silu_pair={silu_pair}, grouped={grouped})")
        if fmt.block is not None:
            raise ValueError(
                "ag_gemm wire uses per-row scales (block=None): the "
                "consumer loads one f32 scale per A row")
        if k % wcodec.LANE:
            raise ValueError(
                f"ag_gemm wire needs lane-aligned K (got {k})")
        kw = wcodec.wire_cols(k, fmt)
        aw = wcodec.pack(a_shard, fmt)
    else:
        kw, aw = k, a_shard

    def _grouped_dot(a_full, w):
        # batched per-expert dot: (E, n*cap, K) x (E, K, N) on the MXU
        xe = jnp.moveaxis(
            a_full.reshape(n, e_groups, cap_pad, k), 1, 0
        ).reshape(e_groups, n * cap_pad, k)
        ye = jax.lax.dot_general(
            xe, w, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        return jnp.moveaxis(
            ye.reshape(e_groups, n, cap_pad, width), 0, 1
        ).reshape(n * m_loc, width)

    def xla_path():
        if wire:
            # the fallback gathers the SAME wire image the kernel
            # forwards, then decodes — identical wire fidelity
            a_full_w = (aw if n == 1
                        else jax.lax.all_gather(aw, axis, tiled=True))
            a_full = wcodec.unpack(a_full_w, (k,), fmt, a_shard.dtype)
        else:
            a_full = (a_shard if n == 1
                      else jax.lax.all_gather(a_shard, axis, tiled=True))
        dot = _grouped_dot if grouped else (
            lambda a, w: jnp.dot(a, w, preferred_element_type=jnp.float32))
        if silu_pair:
            g = dot(a_full, b_gate)
            u = dot(a_full, b_up)
            c = _silu_mul_f32(g, u).astype(out_dtype)
        else:
            c = dot(a_full, b).astype(out_dtype)
        if arrival and n > 1:
            # honor the promised arrival layout on the fallback path:
            # block s <- global chunk (me - s) mod n (inverse of
            # arrival_to_rank_order, which is self-inverse)
            c = arrival_to_rank_order(c, axis)
        return (c, a_full) if return_gathered else c

    if n == 1 and not force_kernel:
        # Nothing to overlap at world=1; XLA's matmul is the fastest path
        # (and XLA fuses the silu_pair epilogue into the dot's output for
        # free — measured 0.73 vs 0.80 ms for the two-accumulator Pallas
        # variant at the bench shape, benchmark/sweep_ag_gemm.py).
        return with_fallback(xla_path())

    fit = fit_tile  # shared tile-fitting rule (lang.core)

    # grouped: the M tile subdivides one expert block (cap_pad rows)
    tm = fit(cfg.tile_m, cap_pad)
    tk = fit(cfg.tile_k, k)
    # in silu_pair mode the C tile is the per-half width
    tn = fit(max(cfg.tile_n // 2, 128) if silu_pair else cfg.tile_n,
             i_loc)

    itemsize = jnp.dtype(a_shard.dtype).itemsize
    out_itemsize = jnp.dtype(out_dtype).itemsize
    mt = cdiv(m_loc, tm)
    tiles_per_e = cap_pad // tm
    nt = cdiv(i_loc, tn)
    nk = cdiv(k, tk)

    # Fixed VMEM residents: B block(s) (tk, tn) x2 each (Pallas pipeline),
    # acc(s) f32 (tm, tn) — only when the K sweep is tiled (nk > 1; at
    # nk == 1 the dot stores directly) — and the store stage (tm, tn)
    # (x2 window when arrival).
    n_acc = 2 if silu_pair else 1
    # wire A tiles are int8 (+ a lane-wide scale stripe per slot)
    a_isz = 1 if wire else itemsize
    vmem_fixed = n_acc * 2 * tk * tn * itemsize \
        + (n_acc * tm * tn * 4 if nk > 1 else 0) \
        + 2 * tm * tn * out_itemsize
    # A strip cache (whole (tm, K) strip, one DMA per block per ring step,
    # reused across the j sweep) — opt-in via config, see AgGemmConfig;
    # the wire consumer keeps the simple double buffer (the strip cache
    # would have to cache dequantized strips to pay off).
    cache_a = (cfg.cache_a and nt >= 2 and not wire
               and vmem_fixed + nk * tm * tk * itemsize <= cfg.vmem_budget)
    a_slots = nk if cache_a else 2
    vmem_need = vmem_fixed + a_slots * tm * tk * a_isz \
        + (tm * wcodec.LANE if wire else 0)
    if (vmem_need > cfg.vmem_budget or interpret_no_headroom()) and (
        not force_kernel
    ):
        # Fallback: XLA AG + dot (the reference's torch path analog).
        return with_fallback(xla_path())

    need_ws = n > 1 or return_gathered
    grid = (n, mt, nt, nk)
    _last_launch = {"kernel": "ag_gemm", "path": "pallas",
                    "tm": tm, "tn": tn, "tk": tk, "grid": grid,
                    "overridden": config is not None}
    if grouped:
        b_spec = pl.BlockSpec(
            (1, tk, tn),
            lambda s, i, j, kk, _t=tiles_per_e: (i // _t, kk, j),
            memory_space=pltpu.VMEM,
        )
    else:
        b_spec = pl.BlockSpec(
            (tk, tn), lambda s, i, j, kk: (kk, j),
            memory_space=pltpu.VMEM,
        )
    if silu_pair:
        in_specs = [pl.BlockSpec(memory_space=pl.ANY), b_spec, b_spec]
        inputs = [aw, b_gate, b_up]
    else:
        in_specs = [pl.BlockSpec(memory_space=pl.ANY), b_spec]
        inputs = [aw, b]

    scratch = [pltpu.VMEM((a_slots, tm, tk),
                          jnp.int8 if wire else a_shard.dtype)]
    if wire:  # per-strip scale stripe (one lane of the wire image)
        scratch.append(pltpu.VMEM((tm, wcodec.LANE), jnp.int8))
    if nk > 1:  # nk==1 stores the dot directly — no accumulator
        scratch.append(pltpu.VMEM((tm, tn), jnp.float32))
        if silu_pair:
            scratch.append(pltpu.VMEM((tm, tn), jnp.float32))
    if not arrival:
        scratch.append(pltpu.VMEM((tm, tn), out_dtype))
    scratch.append(pltpu.SemaphoreType.DMA((a_slots,)))
    if wire:
        scratch.append(pltpu.SemaphoreType.DMA)  # sc_sem
    if not arrival:
        scratch.append(pltpu.SemaphoreType.DMA)  # st_sem
    scratch += [
        pltpu.SemaphoreType.DMA,
        pltpu.SemaphoreType.DMA,
        pltpu.SemaphoreType.DMA((max(n - 1, 1),)),
    ]

    c_spec = (
        pl.BlockSpec((tm, tn),
                     lambda s, i, j, kk, _mt=mt: (s * _mt + i, j),
                     memory_space=pltpu.VMEM)
        if arrival else pl.BlockSpec(memory_space=pl.ANY)
    )
    out_shape = (
        jax.ShapeDtypeStruct((n * m_loc, kw),
                             jnp.int8 if wire else a_shard.dtype),
        jax.ShapeDtypeStruct(
            (n * m_loc, i_loc if silu_pair else n_loc), out_dtype
        ),
    )
    out_specs = (
        pl.BlockSpec(memory_space=pl.ANY),
        c_spec,
    )
    if build is not None:
        out_shape += (trace_ev.out_shape(build),)
        out_specs += (trace_ev.out_spec(),)
        scratch.append(trace_ev.cursor_scratch())
    if gbuild is not None:
        out_shape += (_guard.out_shape(gbuild),)
        out_specs += (_guard.out_spec(),)
        scratch.append(_guard.cursor_scratch())
    if obuild is not None:
        out_shape += (_obs.out_shape(obuild),)
        out_specs += (_obs.out_spec(),)
        scratch.append(_obs.cursor_scratch())
    straggler = _fplan.scheduled_straggler("allgather_gemm") \
        or (cfg.straggler_rank, cfg.straggler_ns)
    res = tpu_call(
        functools.partial(_ag_gemm_kernel, axis, n, mt, nt, nk,
                          tm, tn, tk, out_dtype, straggler,
                          need_ws, cache_a, silu_pair, arrival, grouped,
                          (fmt, k, a_shard.dtype) if wire else None,
                          build, gbuild, obuild),
        grid=grid,
        out_shape=out_shape,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
        compiler_params=compiler_params(
            has_side_effects=True,
            # The barrier semaphore (keyed by collective_id) is only used by
            # the n>1 neighbor_barrier; Mosaic rejects a collective_id when
            # no custom barrier exists in the kernel (world=1).
            collective_id=(
                next_collective_id(f"ag_gemm_{axis}") if n > 1 else None
            ),
            # forced wide-tile candidates may exceed the default budget:
            # grant what the tiling actually implies
            vmem_limit_bytes=max(cfg.vmem_budget, vmem_need) + (2 << 20),
        ),
        # launch_metadata analog (ref allgather_gemm.py:145-155).
        # flops: per-row work is 2*k*n_loc in BOTH modes (grouped rows
        # multiply only their own expert's slice, and n_loc is the
        # per-expert width there); the B stack bytes scale with E.
        cost_estimate=cost_estimate(
            flops=2 * n * m_loc * k * n_loc,
            # C is (n*m_loc, i_loc): half of n_loc in silu_pair mode;
            # wire legs move kw int8 columns per A row
            bytes_accessed=n * m_loc * kw * a_isz
            + e_groups * k * n_loc * itemsize
            + n * m_loc * i_loc * out_itemsize,
            remote_bytes=(n - 1) * m_loc * kw * a_isz,
        ),
    )(*inputs)
    ws, c = res[:2]
    if wire and return_gathered:
        ws = wcodec.unpack(ws, (k,), fmt, a_shard.dtype)
    k_res = 2
    tbuf = res[k_res] if build is not None else None
    k_res += 1 if build is not None else 0
    gbuf = res[k_res] if gbuild is not None else None
    k_res += 1 if gbuild is not None else 0
    obuf = res[k_res] if obuild is not None else None
    return _obs.with_stats(
        obuild,
        _guard.with_guard(
            gbuild, with_trace((c, ws) if return_gathered else c, tbuf),
            gbuf),
        obuf)


def ag_gemm_ref(a_shard: jax.Array, b: jax.Array, axis: str = TP_AXIS):
    """Unfused XLA reference path (the reference's torch_fwd analog,
    ref: layers/nvidia/tp_mlp.py torch_fwd)."""
    a_full = jax.lax.all_gather(a_shard, axis, tiled=True)
    return jnp.dot(a_full, b, preferred_element_type=jnp.float32).astype(
        a_shard.dtype
    )


# -- protocol model (static verifier, triton_dist_tpu.verify) ----------------

from triton_dist_tpu import verify as _v  # noqa: E402


@_v.protocol("allgather_gemm",
             grid=({}, {"fmt": "fp8"}),
             doc="AG+GEMM producer ring (_ag_gemm_kernel, need_ws "
                 "n>1 regime) with the per-ring-step consumer reads; "
                 "fmt != native rides the wire image on the same ring")
def _ag_gemm_protocol(n, fmt="native"):
    """The producer ring of _ag_gemm_kernel: publish the local shard
    into ws[me], forward chunk (me-s) right each step on per-step recv
    semaphores, and CONSUME (GEMM-read) step s's rows only after that
    step's delivery wait — the in-kernel producer/consumer contract the
    `ag.ring_wait` trace spans measure dynamically. The wire variant
    packs a once at the send edge and dequantizes per consumed tile —
    local dataflow only; the ring skeleton is format-invariant."""
    me = shmem.my_pe(TP_AXIS)
    a, ws = _v.ref("a"), _v.ref("ws")
    cp = _v.sem("cp_sem")
    send, recv = _v.sem("send_sem"), _v.sem("recv_sems")
    if fmt != "native":
        _v.read(a.at())   # send edge: pack a into the wire image
        _v.write(a.at())
    shmem.neighbor_barrier(TP_AXIS, me, n)
    _v.read(a.at())  # step-0 consumer reads the own shard from a_ref
    lc = _v.copy(ws.at(me), a.at(), cp.at())
    lc.wait()
    prev = shmem.putmem_nbi(ws.at(me), ws.at(me), send.at(), recv.at(0),
                            (me + 1) % n, TP_AXIS)
    for s in range(1, n):
        prev.wait()  # our step s-1 send drained + step s-1 rows landed
        chunk = (me - s) % n
        _v.read(ws.at(chunk))  # this step's GEMM reads
        if s < n - 1:
            prev = shmem.putmem_nbi(ws.at(chunk), ws.at(chunk),
                                    send.at(), recv.at(s),
                                    (me + 1) % n, TP_AXIS)


# -- conformance runner (verify.conform) --------------------------------------

from jax.sharding import PartitionSpec as _P  # noqa: E402


@_conform.conforms(
    "allgather_gemm",
    grids=((4, {}), (4, {"fmt": "fp8"})),
    doc="overlapped AG+GEMM ring (inline notes thread the cross-step "
        "descriptor idents) on the interpret mesh")
def _ag_gemm_conform(n, fmt="native"):
    mesh = _conform.team_mesh(n, (TP_AXIS,))
    if isinstance(mesh, _conform.Skip):
        return mesh
    wf = None if fmt == "native" else fmt
    a = jnp.ones((8, 128), jnp.float32)
    b = jnp.ones((128, 128), jnp.float32)
    return _conform.collect_streams(
        mesh, TP_AXIS,
        lambda a_, b_: ag_gemm(a_, b_, TP_AXIS, wire_format=wf),
        in_specs=(_P(), _P()), args=(a, b))
