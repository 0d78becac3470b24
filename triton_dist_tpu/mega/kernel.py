"""The persistent TPU megakernel: one Pallas kernel runs the whole task
queue of a decode step.

TPU-native re-design of the reference's generated MEGA_TRITON_KERNEL
(ref: python/triton_dist/mega_triton_kernel/core/code_generator.py:31-175
and kernels/task_context.py:92-140). The mapping:

  NUM_SMS persistent blocks      -> one Pallas grid over the task queue
                                    (a TPU chip has 1-2 TensorCores, not
                                    132 SMs; see mega/scheduler.py)
  uint32 work-queue tensor       -> scalar-prefetched int32 queue rows
  generated if/elif on task_type -> lax.switch over branch closures built
                                    at trace time, one per distinct
                                    (op, static-config) key — trace-time
                                    specialization IS the codegen step
  tensor pointers in the row     -> workspace slot ids (flat HBM
                                    activation arena planned by
                                    tdt_plan_slots) + layer ids indexing
                                    stacked weight arrays
  scoreboard signal table        -> same-core program order within one
                                    queue (topologically sorted); ACROSS
                                    cores, per-queue completion counts on
                                    a regular-semaphore scoreboard: each
                                    task broadcasts "queue c finished its
                                    k-th task" and waiters consume static
                                    watermark deltas (see compile_graph;
                                    the ref's device scoreboard,
                                    kernels/task_context.py:92-140);
                                    cross-chip AR uses remote DMA delivery
                                    semaphores
  in-kernel multimem allreduce   -> one-shot mailbox AR over ICI remote
                                    DMA, parity-double-buffered across
                                    decode steps (ref mega
                                    kernels/allreduce.py)

Weight loads are double-buffered against the MXU inside the matmul branch
(the reference's prefetch task analog, mega kernels/prefetch.py).

Layout notes forced by Mosaic HBM tiling (slices along the second-minor
dim must be sublane-aligned): workspace slots are PB-row stripes with
PB = round_up(batch, sublane); the decode KV cache is (L, Hkv, B, S, D)
so per-head reads slice only leading dims; and the attention task does
NOT append to the cache in-kernel — it emits the rope'd k/v rows as
ordinary workspace outputs, folds the new element into its own softmax,
and the caller scatters them into the cache with one XLA
dynamic_update_slice fused into the same jit (the ref's paged KV append,
mega_triton_kernel/models/paged_kv_cache.py, is a device-side scatter for
the same reason: the cache write is not on the kernel's critical path).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.lang import shmem
from triton_dist_tpu.lang.core import (
    backend_device,
    compiler_params,
    min_tile,
    next_collective_id,
    round_up,
    tpu_call,
)
from triton_dist_tpu.mega.core import Graph, plan_mm_tiles
from triton_dist_tpu.mega.scheduler import (
    Schedule,
    monotone_watermarks,
    plan_prefetch,
    plan_store_forward,
)
from triton_dist_tpu.trace import events as trace_ev

# Queue row layout (all static, built at compile time):
#   [branch, a0..a5,
#    pf_code, pf_layer, pf_slot, pf_in,      # weight-streaming pipeline
#    pend_w, pend_early, defer_st, fwd_in]   # store/forward pipeline
#
# pf_*: cross-task weight prefetch (the reference's prefetch tasks, mega
# kernels/prefetch.py, made implicit). The scheduler's prefetch plan
# (scheduler.plan_prefetch) assigns each upcoming matmul's first weight
# tile to an EARLIER row of the same queue: that row starts the DMA into
# rotating arena slot pf_slot as early as its own DMA ordering allows
# (see _maybe_prefetch), and the consuming matmul (pf_in = slot+1; 0 =
# cold) reads the arena instead of issuing a cold load. With arena depth
# >= 2 the issue site may be several tasks upstream — the hint streams
# through attention KV tails and AR wait windows without clobbering the
# tile the current matmul is about to consume.
#
# pend_w / pend_early / defer_st / fwd_in: the cross-task store pipeline
# (single-core only). defer_st=1 tells a task to leave its workspace
# store in flight instead of blocking on it; the FOLLOWING row drains it
# (pend_w = 1+index into the static store-width table) either before its
# own workspace loads (pend_early=1, required when its reads alias the
# stored slot) or just before it first overwrites vout. fwd_in=1 means
# this task's main input is the immediately preceding task's result and
# is read straight out of vout (VMEM) — the HBM store+load round trip
# leaves the critical path entirely.
ROW = 15


def physical_core_count() -> int:
    """TensorCores per chip, from the device-kind table (PJRT devices do
    not reliably expose num_cores). TDT_NUM_CORES overrides; a kind the
    table does not know is an error, not a guess."""
    env = os.environ.get("TDT_NUM_CORES")
    if env:
        return int(env)
    kind = backend_device().device_kind.lower()
    if "lite" in kind or "v5e" in kind or "v6e" in kind:
        return 1
    if "v4" in kind or "v5" in kind:
        return 2  # megacore chips (v4, v5p)
    raise RuntimeError(
        f"unknown TPU device_kind {kind!r}: add its TensorCore count to "
        "mega.kernel.physical_core_count (or set TDT_NUM_CORES)")


def tile_weight_major(w, tn: int):
    """Re-lay a stacked weight (..., K, N) as tile-major
    (..., N//tn, K, tn): block [..., j] is then K*tn*itemsize fully
    CONTIGUOUS bytes in HBM, so its DMA streams at peak bandwidth
    instead of N-strided tn-wide bursts. Done ONCE at init (a
    materializing transpose — never per step); the kernel reads tiled
    weights via compile_graph(tiled_weights=...)."""
    *lead, k, n = w.shape
    nt = n // tn
    assert nt * tn == n, f"N={n} not divisible by tile {tn}"
    return jnp.moveaxis(w.reshape(*lead, k, nt, tn), -2, -3)


@dataclasses.dataclass
class _Env:
    """Refs + static dims visible to branch builders."""

    dtype: Any
    batch: int     # logical batch rows
    pb: int        # sublane-padded stripe height of one workspace slot
    wmax: int
    pos: Any = None
    table: Any = None  # (B, MAXP) int32 page table in SMEM
    ws: Any = None
    weights: Dict[str, Any] = dataclasses.field(default_factory=dict)
    norms: Any = None
    rope_cs: Any = None
    k_cache: Any = None
    v_cache: Any = None
    vin: Any = None
    vin2: Any = None
    vout: Any = None
    straggler: tuple = (-1, 0)  # (rank, ns) AR-branch skew injection
    vw: Any = None
    vkv: Any = None
    vrope: Any = None
    vnq: Any = None
    vnk: Any = None
    vpf: Any = None
    pfsem: Any = None
    pf_specs: Any = None  # [(wname, K, TN)] in weight-name order
    pf_depth: int = 1     # rotating prefetch-arena slots
    # byte-budgeted matmul tile map (mega/core.plan_mm_tiles): branch
    # key -> TN; the scheduler's prefetch plan is built on the same map
    mm_tn: Dict = dataclasses.field(default_factory=dict)
    # weight names stored tile-major (L, nt, K, TN): block [layer, j]
    # is contiguous in HBM (see tile_weight_major)
    tiled: frozenset = frozenset()
    store_widths: Any = ()  # static store-width table (pend_w indexes it)
    chsem: Any = None       # scratch sem for the interpret-mode AR churn
    mailbox: Any = None
    ld1: Any = None
    ld2: Any = None
    st: Any = None
    wsems: Any = None
    kvsem: Any = None
    kvsems: Any = None
    send: Any = None
    recv: Any = None
    tctx: Any = None  # trace.events.TraceCtx (None = tracing off)

    def ws_rows(self, slot, width):
        return self.ws.at[pl.ds(slot * self.pb, self.pb), pl.ds(0, width)]


# -- shared op math (one definition: fused and standalone branches must
# never diverge — the e2e tests compare their outputs token-for-token) ---


def _rms_f32(x, w, eps):
    """rms_norm in f32: x (B, W) value, w (W,) value."""
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w[None, :]


def _silu_f32(g, u):
    return g * jax.nn.sigmoid(g) * u


# -- branch builders (one per op kind; key carries the static config) --------


def _w_tile_src(env: _Env, wname: str, layer, j, K: int, TN: int):
    """The (K, TN) HBM source of weight tile j: tile-major weights
    index a contiguous block, plain (L, K, N) weights a strided column
    slice. ONE definition for own-tile loads and prefetch issues — the
    layouts must never diverge between the two."""
    if wname in env.tiled:
        return env.weights[wname].at[layer, j]
    return env.weights[wname].at[layer, :, pl.ds(j * TN, TN)]


def _pf_copy(env: _Env, wname: str, layer, K: int, TN: int, slot):
    """THE prefetch descriptor: start (issuer) and wait (consumer) must
    reconstruct it identically for the semaphore accounting to balance —
    single construction site for both. `slot` selects the rotating arena
    slot (and its per-slot semaphore), so up to pf_depth first tiles can
    be in flight across task boundaries."""
    return pltpu.make_async_copy(
        _w_tile_src(env, wname, layer, 0, K, TN),
        env.vpf.at[slot, pl.ds(0, K), pl.ds(0, TN)],
        env.pfsem.at[slot],
    )


def _maybe_prefetch(env: _Env, pf_code, pf_layer, pf_slot):
    """Start an upcoming matmul's first weight tile (hinted by the queue
    row; assigned by scheduler.plan_prefetch). Branches that mark
    handles_prefetch issue it as EARLY as their own DMA ordering allows —
    right after queueing their input loads (rms/silu/add/AR), after the
    last own weight tile is queued (matmul), during the last KV load
    (attention), or before the rank wait (barrier). Measured on the 8B
    decode chain, early-within-task beats end-of-task by ~1.6%. Every
    current branch sets handles_prefetch; the dispatch wrapper's fallback
    only guards future branches that forget to."""
    for wi, (wname, K, TN) in enumerate(env.pf_specs):
        @pl.when(pf_code == wi + 1)
        def _(wname=wname, K=K, TN=TN):
            _pf_copy(env, wname, pf_layer, K, TN, pf_slot).start()


def _pf_args(args):
    """(pf_code, pf_layer, pf_slot) triple from a queue row."""
    return args[6], args[7], args[8]


def _drain_pending(env: _Env, pend_w):
    """Wait the PREVIOUS task's deferred workspace store (see the ROW
    comment). pend_w indexes the static store-width table + 1, so the
    wait descriptor reconstructs the exact byte count the deferred
    store's start put on env.st."""
    for i, w in enumerate(env.store_widths):
        @pl.when(pend_w == i + 1)
        def _(w=w):
            pltpu.make_async_copy(
                env.vout.at[:, pl.ds(0, w)],
                env.ws.at[pl.ds(0, env.pb), pl.ds(0, w)],
                env.st,
            ).wait()


def _drain_late(env: _Env, args):
    """The pend_early=0 drain: called by branch bodies right before they
    first overwrite vout (the deferred store's source)."""
    pend_w, pend_early = args[10], args[11]

    @pl.when(jnp.logical_and(pend_w > 0, pend_early == 0))
    def _():
        _drain_pending(env, pend_w)


def _finish_store(env: _Env, st, args):
    """Start the task's workspace store; block on it only when the row
    does not defer (defer_st=0: multi-core queues, or the queue's last
    row — the next row's _drain_pending otherwise picks it up)."""
    st.start()

    @pl.when(args[12] == 0)
    def _():
        st.wait()



def _matmul_branch(key, env: _Env):
    """Tiled matmul with an optional fused input prologue (the
    reference's fused task kernels, mega kernels/mlp_fc1.py: norm or
    activation computed in-register on the loaded input instead of
    round-tripping through a separate task + HBM slot — at decode shapes
    the saved task boundaries are a measurable share of the step).

    prologue: None · "rms" (input rms-norm, per-task norm row in a3) ·
    "silu" (input is [gate|up] of width 2K; a = silu(gate) * up)."""
    _, wname, K, N, prologue, eps = key
    TN = env.mm_tn[key]  # byte-budgeted tile map (core.plan_mm_tiles)
    nt = N // TN
    in_w = 2 * K if prologue == "silu" else K
    pf_eligible = any(w == wname and kk == K and tn == TN
                      for w, kk, tn in env.pf_specs)
    VW = env.vw.shape[0]  # own-tile arena depth (outstanding DMAs = VW-1)

    def wcopy(layer, j, slot):
        return pltpu.make_async_copy(
            _w_tile_src(env, wname, layer, j, K, TN),
            env.vw.at[slot, pl.ds(0, K), pl.ds(0, TN)],
            env.wsems.at[slot],
        )

    def body(args):
        layer, src, dst, nrow = args[0], args[1], args[2], args[3]
        pf_in, fwd_in = args[9], args[13]
        cp_in = pltpu.make_async_copy(
            env.ws_rows(src, in_w), env.vin.at[:, pl.ds(0, in_w)], env.ld1
        )

        @pl.when(fwd_in == 0)
        def _load():
            cp_in.start()

        if pf_eligible:
            # prefetch-arena consume: payload > 0 = hit (arena slot
            # pf_in - 1 was streamed by an earlier row), 0 = cold miss
            trace_ev.instant(env.tctx, trace_ev.REGIONS["mega.pf"],
                             payload=pf_in)

            @pl.when(pf_in == 0)
            def _cold_first_tile():
                wcopy(layer, 0, 0).start()
        else:
            wcopy(layer, 0, 0).start()
        if prologue == "rms":
            cp_w = pltpu.make_async_copy(
                env.norms.at[pl.ds(nrow * 8, 8)], env.vnq, env.ld2
            )
            cp_w.start()

        def _from_ws():
            cp_in.wait()
            return env.vin[:, :in_w]

        def _from_fwd():
            # previous task's result still lives in vout — skip the HBM
            # round trip (its deferred store only READS vout: safe)
            return env.vout[:, :in_w]

        raw = jax.lax.cond(fwd_in == 1, _from_fwd, _from_ws)
        if prologue == "rms":
            cp_w.wait()
            a = _rms_f32(
                raw[:, :K].astype(jnp.float32),
                env.vnq[0, :K].astype(jnp.float32), eps,
            ).astype(env.dtype)
        elif prologue == "silu":
            a = _silu_f32(
                raw[:, :K].astype(jnp.float32),
                raw[:, K:2 * K].astype(jnp.float32),
            ).astype(env.dtype)
        else:
            a = raw[:, :K]
        # about to overwrite vout (the deferred store's source)
        _drain_late(env, args)
        for j in range(nt):
            # keep VW-1 own-tile DMAs in flight ahead of the dot
            if j == 0:
                for ah in range(1, VW):
                    if ah < nt:
                        wcopy(layer, ah, ah % VW).start()
            elif j + VW - 1 < nt:
                wcopy(layer, j + VW - 1, (j + VW - 1) % VW).start()
            if j == nt - 1 and (nt > 1 or env.pf_depth > 1):
                # all own tiles are queued: queue the hinted matmul's
                # first weight tile NOW, before the last wait+dot, so the
                # weight stream never drains at the task boundary. (At
                # nt==1 this is only safe with a rotating arena — the
                # depth-1 arena would overwrite the tile this task is
                # reading; that case issues in the epilogue below.)
                _maybe_prefetch(env, *_pf_args(args))
            if j == 0:
                if pf_eligible:
                    def _from_prefetch():
                        slot = pf_in - 1
                        _pf_copy(env, wname, layer, K, TN, slot).wait()
                        return env.vpf[slot, :K, :TN]

                    def _from_cold():
                        wcopy(layer, 0, 0).wait()
                        return env.vw[0, :K, :TN]

                    w_tile = jax.lax.cond(pf_in > 0, _from_prefetch,
                                          _from_cold)
                else:
                    # weight excluded from prefetching (non-unique
                    # (K, TN)): pf_in is statically never > 0 for this
                    # branch and vpf may be smaller than (K, TN)
                    wcopy(layer, 0, 0).wait()
                    w_tile = env.vw[0, :K, :TN]
            else:
                wcopy(layer, j, j % VW).wait()
                w_tile = env.vw[j % VW, :K, :TN]
            acc = jax.lax.dot_general(
                a, w_tile, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            env.vout[:, j * TN:(j + 1) * TN] = acc.astype(env.dtype)
        st = pltpu.make_async_copy(
            env.vout.at[:, pl.ds(0, N)], env.ws_rows(dst, N), env.st
        )
        st.start()
        if nt == 1 and env.pf_depth == 1:
            _maybe_prefetch(env, *_pf_args(args))

        @pl.when(args[12] == 0)
        def _wait_store():
            st.wait()

    body.handles_prefetch = True
    return body


def _rms_norm_branch(key, env: _Env):
    _, W, eps = key

    def body(args):
        nrow, src, dst = args[0], args[1], args[2]
        fwd_in = args[13]
        cp_in = pltpu.make_async_copy(
            env.ws_rows(src, W), env.vin.at[:, pl.ds(0, W)], env.ld1
        )
        # norms ship 8-row-striped (row i at 8*i): single-row dynamic
        # slices are not tiling-aligned on Mosaic, 8-row stripes are
        cp_w = pltpu.make_async_copy(
            env.norms.at[pl.ds(nrow * 8, 8)], env.vnq, env.ld2
        )

        @pl.when(fwd_in == 0)
        def _load():
            cp_in.start()

        cp_w.start()
        _maybe_prefetch(env, *_pf_args(args))

        def _from_ws():
            cp_in.wait()
            return env.vin[:, :W]

        raw = jax.lax.cond(fwd_in == 1, lambda: env.vout[:, :W], _from_ws)
        cp_w.wait()
        y = _rms_f32(raw.astype(jnp.float32),
                     env.vnq[0, :W].astype(jnp.float32), eps)
        _drain_late(env, args)
        env.vout[:, :W] = y.astype(env.dtype)
        st = pltpu.make_async_copy(
            env.vout.at[:, pl.ds(0, W)], env.ws_rows(dst, W), env.st
        )
        _finish_store(env, st, args)

    body.handles_prefetch = True
    return body


def _silu_mul_branch(key, env: _Env):
    _, I = key

    def body(args):
        src, dst = args[0], args[1]
        fwd_in = args[13]
        cp_in = pltpu.make_async_copy(
            env.ws_rows(src, 2 * I), env.vin.at[:, pl.ds(0, 2 * I)],
            env.ld1,
        )

        @pl.when(fwd_in == 0)
        def _load():
            cp_in.start()

        _maybe_prefetch(env, *_pf_args(args))

        def _from_ws():
            cp_in.wait()
            return env.vin[:, :2 * I]

        raw = jax.lax.cond(fwd_in == 1, lambda: env.vout[:, :2 * I],
                           _from_ws)
        y = _silu_f32(raw[:, :I].astype(jnp.float32),
                      raw[:, I:2 * I].astype(jnp.float32))
        _drain_late(env, args)
        env.vout[:, :I] = y.astype(env.dtype)
        st = pltpu.make_async_copy(
            env.vout.at[:, pl.ds(0, I)], env.ws_rows(dst, I), env.st
        )
        _finish_store(env, st, args)

    body.handles_prefetch = True
    return body


def _add_branch(key, env: _Env):
    _, W = key

    def body(args):
        asrc, bsrc, dst = args[0], args[1], args[2]
        cp_a = pltpu.make_async_copy(
            env.ws_rows(asrc, W), env.vin.at[:, pl.ds(0, W)], env.ld1
        )
        cp_b = pltpu.make_async_copy(
            env.ws_rows(bsrc, W),
            env.vin2.at[pl.ds(0, env.pb), pl.ds(0, W)], env.ld2,
        )
        cp_a.start()
        cp_b.start()
        _maybe_prefetch(env, *_pf_args(args))
        cp_a.wait()
        cp_b.wait()
        _drain_late(env, args)
        env.vout[:, :W] = env.vin[:, :W] + env.vin2[:env.pb, :W]
        st = pltpu.make_async_copy(
            env.vout.at[:, pl.ds(0, W)], env.ws_rows(dst, W), env.st
        )
        _finish_store(env, st, args)

    body.handles_prefetch = True
    return body


def _barrier_branch(key, env: _Env):
    _, axis, n = key

    def body(args):
        # the pf DMA reads only local weights: issue it before waiting
        # for the slowest rank, not after
        _maybe_prefetch(env, *_pf_args(args))
        shmem.barrier_all(axis)

    body.handles_prefetch = True
    return body


def _allreduce_add_branch(key, env: _Env):
    """One-shot mailbox AR + residual add (ref mega kernels/allreduce.py
    multimem ld_reduce analog; see make_allreduce_add for the parity
    flow-control argument)."""
    _, W, axis, n = key

    def body(args):
        src, res, dst, parity = args[0], args[1], args[2], args[3]
        fwd_in = args[13]
        pb = env.pb
        cp_res = pltpu.make_async_copy(
            env.ws_rows(res, W),
            env.vin2.at[pl.ds(0, pb), pl.ds(0, W)], env.ld2,
        )
        cp_res.start()
        if n > 1:
            me = jax.lax.axis_index(axis)
            cp_loc = pltpu.make_async_copy(
                env.ws_rows(src, W),
                env.mailbox.at[parity, me, :, pl.ds(0, W)],
                env.ld1,
            )
            cp_loc.start()
            _maybe_prefetch(env, *_pf_args(args))

            def skew():
                # race provocation (tests only): stall the straggler
                # BETWEEN its individual puts, so its payload reaches
                # the first peer on time but the remaining peers late.
                # The on-time peer completes this AR and runs ahead to
                # the NEXT one; its next-parity delivery then arrives at
                # the still-waiting peers while the straggler's put for
                # THIS parity is in flight — exactly the misattribution
                # only per-parity recv semaphores prevent (a shared recv
                # counts the early next-parity bytes and reads a stale
                # mailbox row). Interpret-mode skew is a LOCAL-DMA
                # churn: semaphore churn is unusable in a multi-core
                # kernel (signal and wait can land on different cores'
                # semaphore instances); a copy start/wait pair is the
                # per-core pattern every branch already relies on. The
                # churn runs on its own scratch semaphore (chsem): on
                # ld1 its waits could consume cp_loc's identical-byte
                # completion while cp_loc is still in flight.
                # Native uses cycle-accurate pl.delay.
                s_rank, s_ns = env.straggler
                if s_ns <= 0:
                    return
                from triton_dist_tpu.lang.core import use_interpret

                if use_interpret():
                    @pl.when(me == s_rank)
                    def _skew():
                        def churn(_, c):
                            cp = pltpu.make_async_copy(
                                env.ws_rows(src, W),
                                env.vin.at[:, pl.ds(0, W)], env.chsem,
                            )
                            cp.start()
                            cp.wait()
                            return c

                        jax.lax.fori_loop(0, max(1, s_ns // 5000),
                                          churn, 0)
                else:
                    shmem.straggler_delay(axis, *env.straggler)

            handles = []
            for i in range(1, n):
                peer = jax.lax.rem(me + i, n)
                # recv is per-parity (DMA((2,))): under rank skew a fast
                # peer's AR m+1 delivery must not satisfy this rank's AR m
                # recv wait while a slow peer's AR m put is in flight —
                # same misattribution low_latency_allgather.py documents,
                # same fix (recv_sems.at[parity]).
                h = shmem.putmem_nbi(
                    env.mailbox.at[parity, me, :, pl.ds(0, W)],
                    env.ws_rows(src, W),
                    env.send, env.recv.at[parity], peer, axis,
                )
                handles.append(h)
                if i == 1:
                    skew()
            cp_loc.wait()
            for h in handles:
                h.wait()
            acc = env.mailbox[parity, 0, :, :W].astype(jnp.float32)
            for r in range(1, n):
                acc = acc + env.mailbox[parity, r, :, :W].astype(jnp.float32)
        else:
            cp_loc = pltpu.make_async_copy(
                env.ws_rows(src, W), env.vin.at[:, pl.ds(0, W)], env.ld1
            )

            @pl.when(fwd_in == 0)
            def _load():
                cp_loc.start()

            _maybe_prefetch(env, *_pf_args(args))

            def _from_ws():
                cp_loc.wait()
                return env.vin[:, :W]

            acc = jax.lax.cond(
                fwd_in == 1, lambda: env.vout[:, :W], _from_ws
            ).astype(jnp.float32)
        cp_res.wait()
        acc = acc + env.vin2[:env.pb, :W].astype(jnp.float32)
        _drain_late(env, args)
        env.vout[:, :W] = acc.astype(env.dtype)
        st = pltpu.make_async_copy(
            env.vout.at[:, pl.ds(0, W)], env.ws_rows(dst, W), env.st
        )
        _finish_store(env, st, args)

    body.handles_prefetch = True
    return body


def _kv_chunk(smax: int, page: int = 0) -> int:
    """KV page length for the chunked attention: whole-cache at small
    contexts (one page, the static path), 512-token pages past that.
    page > 0 pins an explicit page size (the paged-cache mode)."""
    if page > 0:
        assert smax % page == 0, f"s_max {smax} % page {page} != 0"
        return page
    if smax <= 1024:
        return smax
    assert smax % 512 == 0, f"s_max {smax} must be a multiple of 512"
    return 512


def _attention_branch(key, env: _Env):
    """qk-norm + rope + GQA decode (ref: mega kernels/flash_attn.py page
    attention task). The new token's k/v rows are written to workspace
    slots and folded into the softmax directly; the caller scatters them
    into the cache (see module docstring)."""
    (_, hq_l, hkv_l, D, SMAX, eps, use_qk_norm, q_base, k_base,
     page) = key
    B = env.batch
    half = D // 2
    g = hq_l // hkv_l
    scale = D ** -0.5
    kw = hkv_l * D
    hqd = hq_l * D
    WQKV = hqd + 2 * kw
    # lane-aligned staging layout (DMA widths padded to 128; readers only
    # consume the true kw/hqd prefixes of the destination slots)
    kwp = round_up(kw, 128)
    hqdp = round_up(hqd, 128)

    def rope(x, c, s):
        # x (B, h, D), c/s (B, half) f32; half-split convention
        x1 = x[..., :half]
        x2 = x[..., half:]
        cb = c[:B, None, :]
        sb = s[:B, None, :]
        return jnp.concatenate([x1 * cb - x2 * sb, x2 * cb + x1 * sb],
                               axis=-1)

    def rmsn(x, w):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + eps) * w[None, None, :]

    def body(args):
        layer, src, dst, kn_dst, vn_dst = (
            args[0], args[1], args[2], args[3], args[4]
        )
        fwd_in = args[13]
        cp_in = pltpu.make_async_copy(
            env.ws_rows(src, WQKV), env.vin.at[:, pl.ds(0, WQKV)], env.ld1
        )

        @pl.when(fwd_in == 0)
        def _load():
            cp_in.start()

        if use_qk_norm:
            cp_qn = pltpu.make_async_copy(
                env.norms.at[pl.ds((q_base + layer) * 8, 8)], env.vnq,
                env.ld2,
            )
            cp_kn = pltpu.make_async_copy(
                env.norms.at[pl.ds((k_base + layer) * 8, 8)], env.vnk,
                env.kvsem,
            )
            cp_qn.start()
            cp_kn.start()
        rope_cps = []
        for b in range(B):
            cp = pltpu.make_async_copy(
                env.rope_cs.at[pl.ds(env.pos[b] * 8, 8)],
                env.vrope.at[b],
                env.wsems.at[b % 2],
            )
            cp.start()
            rope_cps.append(cp)
        def _from_ws():
            cp_in.wait()
            return env.vin[:, :WQKV]

        # fwd_in: the qkv matmul immediately precedes on this queue and
        # its result still sits in vout — read it there, skip the HBM
        # round trip (its deferred store only READS vout: safe)
        raw_qkv = jax.lax.cond(fwd_in == 1, lambda: env.vout[:, :WQKV],
                               _from_ws)
        if use_qk_norm:
            cp_qn.wait()
            cp_kn.wait()
        for cp in rope_cps:
            cp.wait()

        # full-PB loads/stores only: Mosaic rejects sub-sublane ref slices;
        # value-level slicing to the B live rows is free vreg selection
        qkv_full = raw_qkv.astype(jnp.float32)
        qkv = qkv_full[:B]
        q = qkv[:, :hqd].reshape(B, hq_l, D)
        kn = qkv[:, hqd:hqd + kw].reshape(B, hkv_l, D)
        vn = qkv[:, hqd + kw:WQKV].reshape(B, hkv_l, D)
        if use_qk_norm:
            q = rmsn(q, env.vnq[0, :D].astype(jnp.float32))
            kn = rmsn(kn, env.vnk[0, :D].astype(jnp.float32))
        cs_rows = env.vrope[:, 0, :]  # (B, D)
        c = cs_rows[:, :half]
        s = cs_rows[:, half:D]
        q = rope(q, c, s)
        kn = rope(kn, c, s)

        def pad_rows(v):
            pb = env.pb
            if v.shape[0] == pb:
                return v
            return jnp.concatenate(
                [v, jnp.zeros((pb - v.shape[0], v.shape[1]), v.dtype)], 0
            )

        # about to overwrite vout (a deferred store's source; raw_qkv is
        # already materialized in registers above)
        _drain_late(env, args)
        # stage: [0,hqdp) attention out · then k_new · then v_new
        env.vout[:, hqdp:hqdp + kw] = pad_rows(
            kn.reshape(B, kw).astype(env.dtype))
        env.vout[:, hqdp + kwp:hqdp + kwp + kw] = pad_rows(
            vn.reshape(B, kw).astype(env.dtype))

        # ---- chunked-KV online attention (flash-decode over the cache;
        # ref: mega_triton_kernel/models/paged_kv_cache.py — context
        # scales past VMEM by streaming SCHUNK-token KV pages). EVERY
        # cache access indirects through the page table (SMEM): the
        # dense cache is the identity table over its own page grid, the
        # paged cache maps (seq, chunk) -> pool page (per-seq growth +
        # pool sharing; the ref's page_table lookup, paged_kv_cache.py).
        # The online state is SEEDED with the new token's contribution
        # (always unmasked), so the running max is real from the start
        # and fully-masked chunks contribute exactly zero. Chunks past a
        # sequence's prefix read table slot 0 (zero-init) — in-bounds,
        # and their logits are position-masked to -inf.
        schunk = _kv_chunk(SMAX, page)
        nch = SMAX // schunk

        def kv_start(h, ci, slot):
            for which, ref in ((0, env.k_cache), (1, env.v_cache)):
                for b in range(B):
                    pid = env.table[b, ci]
                    pltpu.make_async_copy(
                        ref.at[layer, h, pid],
                        env.vkv.at[slot, which, b],
                        env.kvsems.at[slot],
                    ).start()

        def kv_wait(slot):
            for which, ref in ((0, env.k_cache), (1, env.v_cache)):
                for b in range(B):
                    pltpu.make_async_copy(
                        ref.at[0, 0, 0], env.vkv.at[slot, which, b],
                        env.kvsems.at[slot],
                    ).wait()

        def chunk_update(h, ci, state):
            """One KV page folded into the per-b online softmax state."""
            m, den, acc = state  # (B, g, 1), (B, g, 1), (B, g, D)
            kf = env.vkv[ci % 2, 0].astype(jnp.float32)  # (B, schunk, D)
            vf = env.vkv[ci % 2, 1].astype(jnp.float32)
            ms, dens, accs = [], [], []
            for b in range(B):
                qb = q[b, h * g:(h + 1) * g] * scale  # (g, D)
                lg = jax.lax.dot_general(
                    qb, kf[b], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # (g, schunk)
                spos = jax.lax.broadcasted_iota(
                    jnp.int32, (g, schunk), 1) + ci * schunk
                lg = jnp.where(spos < env.pos[b], lg, -1e30)
                m_new = jnp.maximum(m[b], jnp.max(lg, -1, keepdims=True))
                alpha = jnp.exp(m[b] - m_new)
                p_ = jnp.exp(lg - m_new)
                ms.append(m_new)
                dens.append(den[b] * alpha
                            + jnp.sum(p_, -1, keepdims=True))
                accs.append(acc[b] * alpha + jax.lax.dot_general(
                    p_, vf[b], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ))
            return (jnp.stack(ms), jnp.stack(dens), jnp.stack(accs))

        out_rows = []  # per-b (1, hqd) attention outputs, kv-head-major
        for h in range(hkv_l):
            # seed: the new token (logit lg_new, value vn) at weight 1
            m0, d0, a0 = [], [], []
            for b in range(B):
                qb = q[b, h * g:(h + 1) * g] * scale
                lg_new = jnp.sum(qb * kn[b, h][None, :], axis=-1,
                                 keepdims=True)  # (g, 1)
                m0.append(lg_new)
                d0.append(jnp.ones_like(lg_new))
                a0.append(jnp.broadcast_to(vn[b, h][None, :], (g, D)))
            state = (jnp.stack(m0), jnp.stack(d0), jnp.stack(a0))

            if nch == 1:
                # static path (whole cache is one page; bench shapes)
                kv_start(h, 0, 0)
                if h == hkv_l - 1:
                    _maybe_prefetch(env, *_pf_args(args))
                kv_wait(0)
                state = chunk_update(h, 0, state)
            else:
                # long-context path: dynamic trip count — only pages
                # that intersect some sequence's prefix are touched
                maxp = env.pos[0]
                for b in range(1, B):
                    maxp = jnp.maximum(maxp, env.pos[b])
                n_act = jnp.minimum((maxp + schunk - 1) // schunk, nch)

                @pl.when(n_act > 0)
                def _first():
                    kv_start(h, 0, 0)

                if h == hkv_l - 1:
                    _maybe_prefetch(env, *_pf_args(args))

                def loop_body(ci, state):
                    @pl.when(ci + 1 < n_act)
                    def _ahead():
                        kv_start(h, ci + 1, (ci + 1) % 2)

                    kv_wait(ci % 2)
                    return chunk_update(h, ci, state)

                state = jax.lax.fori_loop(0, n_act, loop_body, state)

            _, den, acc = state
            for b in range(B):
                ob = acc[b] / den[b]
                if h == 0:
                    out_rows.append([ob.reshape(1, g * D)])
                else:
                    out_rows[b].append(ob.reshape(1, g * D))

        out = jnp.concatenate(
            [jnp.concatenate(per_b, axis=1) for per_b in out_rows], axis=0
        )  # (B, hqd)
        env.vout[:, :hqd] = pad_rows(out.astype(env.dtype))

        cps = [
            pltpu.make_async_copy(
                env.vout.at[:, pl.ds(0, hqdp)], env.ws_rows(dst, hqdp),
                env.st,
            ),
            pltpu.make_async_copy(
                env.vout.at[:, pl.ds(hqdp, kwp)],
                env.ws_rows(kn_dst, kwp), env.wsems.at[0],
            ),
            pltpu.make_async_copy(
                env.vout.at[:, pl.ds(hqdp + kwp, kwp)],
                env.ws_rows(vn_dst, kwp), env.wsems.at[1],
            ),
        ]
        for cp in cps:
            cp.start()
        for cp in cps:
            cp.wait()

    body.handles_prefetch = True
    return body


def _noop_branch(key, env: _Env):
    """Multi-core filler: drain rows (whose scoreboard waits happen in the
    dispatch wrapper) and queue padding execute this empty body."""

    def body(args):
        _maybe_prefetch(env, *_pf_args(args))

    body.handles_prefetch = True
    return body


_BRANCH_BUILDERS: Dict[str, Callable] = {
    "matmul": _matmul_branch,
    "rms_norm": _rms_norm_branch,
    "silu_mul": _silu_mul_branch,
    "add": _add_branch,
    "allreduce_add": _allreduce_add_branch,
    "attention": _attention_branch,
    "barrier": _barrier_branch,
    "noop": _noop_branch,
}


@dataclasses.dataclass
class CompiledMega:
    """The compiled megakernel + its static plan."""

    run: Callable  # (pos, ws, weights_dict, norms, rope_cs, k, v) -> ws
    queue: np.ndarray  # (n_tasks, ROW) int32
    n_slots: int
    pb: int        # stripe height (sublane-padded batch)
    wmax: int
    norm_width: int  # required minor dim of the stacked norms array
    branch_keys: List[Any]
    weight_names: List[str]
    # byte-budgeted matmul tile map (branch key -> TN) and the weight
    # names `run` expects in tile-major (L, nt, K, TN) layout
    mm_tiles: Dict[Any, int] = dataclasses.field(default_factory=dict)
    tiled_weights: tuple = ()

    def workspace(self, dtype) -> jnp.ndarray:
        return jnp.zeros((self.n_slots * self.pb, self.wmax), dtype)

    def slot_rows(self, buf_slot: int):
        return slice(buf_slot * self.pb, buf_slot * self.pb + self.pb)

    def tile_cols(self, wname: str) -> int:
        """TN of weight `wname` (every matmul using a weight must agree
        on one tile for it to be addressable here — same uniqueness rule
        as prefetchability)."""
        tns = {tn for k, tn in self.mm_tiles.items() if k[1] == wname}
        assert len(tns) == 1, f"{wname}: non-unique tile set {tns}"
        return tns.pop()


def compile_graph(
    graph: Graph,
    sched: Schedule,
    dtype,
    name: str = "megakernel",
    straggler: tuple = (-1, 0),
    tiled_weights: tuple = (),
) -> CompiledMega:
    """Lower (graph, schedule) to one pallas_call (the reference's
    ModelBuilder.compile, model_builder.py:372-389: codegen + jit). The
    queue array is built once; the returned `run` is pure and jittable
    (call it inside shard_map for world>1 graphs).

    Tracing: when compile_graph runs under trace.building(), the kernel
    carries a per-core record buffer — task spans (payload=branch,
    aux=queue row), scoreboard-wait spans, prefetch hit/miss instants —
    and `run` returns (ws, trace_buf); trace/attribution.
    compare_predicted diffs the result against scheduler.predicted_stalls
    queue by queue. Default builds are bit-identical (the flag is read
    ONCE here, at graph-compile time)."""
    build = trace_ev.active_build()
    B = graph.batch
    PB = round_up(B, min_tile(dtype)[0])
    tasks = graph.tasks
    nc = int(sched.watermarks.shape[1])
    # multi-core rows append the scoreboard plan: nc wait-delta columns
    # (consume this many completions of queue c' before starting) and one
    # broadcast flag (announce completion to every core)
    row_len = ROW + (nc + 1 if nc > 1 else 0)

    # branch table: first-seen order over the scheduled queue
    branch_keys: List[Any] = []
    branch_of: Dict[Any, int] = {}
    for t in tasks:
        if t.branch_key not in branch_of:
            branch_of[t.branch_key] = len(branch_keys)
            branch_keys.append(t.branch_key)
    if nc > 1 and ("noop",) not in branch_of:
        branch_of[("noop",)] = len(branch_keys)
        branch_keys.append(("noop",))

    # weight-streaming plan (scheduler.plan_prefetch): pf_specs is the
    # arena geometry, the per-task issue/consume arrays fill row columns
    # 7-10. Schedules produced by schedule_graph carry the plan; bare
    # Schedules (tests) get one planned here (byte-aware auto depth).
    pf_plan = sched.prefetch
    if pf_plan is None:
        pf_plan = plan_prefetch(graph, sched)
    pf_specs = pf_plan.specs
    pf_depth = pf_plan.depth

    # byte-budgeted matmul tile map — MUST be the map the prefetch plan
    # was built on (both call core.plan_mm_tiles; the assert catches an
    # env-var flip between scheduling and compiling)
    mm_tiles = plan_mm_tiles([k for k in {t.branch_key for t in tasks}
                              if k[0] == "matmul"])
    for wname, kk, tn in pf_specs:
        got = {mm_tiles[k] for k in mm_tiles if k[1] == wname}
        assert got == {tn}, (
            f"prefetch plan tiles {wname} at {tn} but the kernel would "
            f"tile it at {got} — TDT_MEGA_TILE_BYTES changed between "
            "schedule_graph and compile_graph")
    tiled_weights = tuple(tiled_weights)
    mm_names = {k[1] for k in mm_tiles}
    assert set(tiled_weights) <= mm_names, (
        f"tiled_weights {tiled_weights} not all matmul weights "
        f"({sorted(mm_names)})")

    # store/forward plan (single-core only; see scheduler.StorePlan).
    # Per-branch capabilities live here because only the kernel knows
    # each branch body's structure.
    def _store_caps(t):
        """(deferrable store width, can_late_drain, fwd_spec)."""
        k = t.branch_key
        if k[0] == "matmul":
            in_w = 2 * k[2] if k[4] == "silu" else k[2]
            return k[3], True, (t.reads[0], in_w)
        if k[0] == "rms_norm":
            return k[1], True, (t.reads[0], k[1])
        if k[0] == "silu_mul":
            return k[1], True, (t.reads[0], 2 * k[1])
        if k[0] == "add":
            return k[1], True, None  # two-input body: no single forward
        if k[0] == "allreduce_add":
            # n>1 publishes src to the mailbox — must come from HBM
            fwd = (t.reads[0], k[1]) if k[3] == 1 else None
            return k[1], True, fwd
        if k[0] == "attention":
            # multi-store epilogue cannot defer, but the body can both
            # late-drain and read its qkv input straight from vout
            wqkv = (k[1] + 2 * k[2]) * k[3]
            return 0, True, (t.reads[0], wqkv)
        return 0, False, None  # barrier

    caps = [_store_caps(t) for t in tasks]
    st_plan = plan_store_forward(
        graph, sched,
        [c[0] for c in caps], [c[1] for c in caps], [c[2] for c in caps],
    )
    store_widths = st_plan.widths

    def base_row(t):
        row = [branch_of[t.branch_key]] + list(t.args)
        row += [0] * (ROW - len(row))
        for pos_ in t.buf_args:
            row[1 + pos_] = int(sched.buf_slot[row[1 + pos_]])
        tid = t.id
        row[7] = int(pf_plan.issue_code[tid])
        row[8] = int(pf_plan.issue_layer[tid])
        row[9] = int(pf_plan.issue_slot[tid])
        row[10] = int(pf_plan.consume[tid])
        row[11] = int(st_plan.pend_w[tid])
        row[12] = int(st_plan.pend_early[tid])
        row[13] = int(st_plan.defer_st[tid])
        row[14] = int(st_plan.fwd_in[tid])
        return row[:ROW]

    order = sched.order
    if nc == 1:
        # queue rows in schedule order, buffer args rewritten to slots
        queue = np.zeros((len(order), ROW), np.int32)
        for qi, tid in enumerate(order):
            queue[qi] = base_row(tasks[tid])
        qmax = len(order)
    else:
        # per-core queues + scoreboard plan. Queue identity (program_id 0)
        # is decoupled from PHYSICAL core identity (the interpreter
        # randomizes the parallel-coordinate -> core assignment; Mosaic's
        # megacore split is its own choice), so completions are BROADCAST:
        # finishing a task of queue c signals scoreboard semaphore sb[c]
        # on every core, and a waiter consumes from its local instance —
        # whichever core it landed on. Watermarks are monotonized along
        # each queue (scheduler.monotone_watermarks) so each row's wait is
        # a static DELTA, and a final drain row per queue returns every
        # local semaphore instance to zero (Mosaic requires semaphores
        # drained at kernel exit).
        wm_mono = monotone_watermarks(sched)
        qlens = [len(q) for q in sched.queues]
        qmax = max(qlens) + 1  # +1 for the drain row
        queue = np.zeros((nc, qmax, row_len), np.int32)
        noop_row = [branch_of[("noop",)]] + [0] * (row_len - 1)
        for c, qtasks in enumerate(sched.queues):
            prev = np.zeros(nc, np.int64)
            for p, tid in enumerate(qtasks):
                r = base_row(tasks[tid]) + [0] * (nc + 1)
                for c2 in range(nc):
                    if c2 != c:
                        r[ROW + c2] = int(wm_mono[tid][c2] - prev[c2])
                prev = np.maximum(prev, wm_mono[tid])
                r[ROW + nc] = 1  # broadcast completion
                queue[c, p] = r
            dr = list(noop_row)
            for c2 in range(nc):
                dr[ROW + c2] = (qlens[c] if c2 == c
                                else int(qlens[c2] - prev[c2]))
            queue[c, qlens[c]] = dr
            for p in range(qlens[c] + 1, qmax):
                queue[c, p] = noop_row

    # static dims
    wmax = round_up(max(b.width for b in graph.buffers), 128)
    for k in branch_keys:
        if k[0] == "attention":  # padded staging layout (attention branch)
            wmax = max(wmax, round_up(k[1] * k[3], 128)
                       + 2 * round_up(k[2] * k[3], 128))
    mm_keys = [k for k in branch_keys if k[0] == "matmul"]
    kmax = max((k[2] for k in mm_keys), default=128)
    tnmax = max((mm_tiles[k] for k in mm_keys), default=128)
    at_keys = [k for k in branch_keys if k[0] == "attention"]
    assert len({k[1:] for k in at_keys}) <= 1, (
        "one attention geometry per megakernel graph"
    )
    if at_keys:
        _, hq_l, hkv_l, D, SMAX, _, _, _, _, page_ = at_keys[0]
        half = D // 2
    else:
        hkv_l, D, SMAX, half, page_ = 1, 128, 8, 64, 0
    SCHUNK = _kv_chunk(SMAX, page_)
    ar_keys = [k for k in branch_keys if k[0] in ("allreduce_add",
                                                  "barrier")]
    arw = max((k[1] for k in ar_keys if k[0] == "allreduce_add"),
              default=128)
    world = max((k[-1] for k in ar_keys), default=1)
    weight_names = sorted({k[1] for k in mm_keys})
    norm_ws = [k[1] for k in branch_keys if k[0] == "rms_norm"]
    norm_ws += [k[2] for k in mm_keys if k[4] == "rms"]
    if any(k[6] for k in at_keys):  # use_qk_norm
        norm_ws.append(D)
    norm_width = round_up(max(norm_ws, default=128), 128)

    pf_kmax = max((k for _, k, _ in pf_specs), default=8)
    pf_tnmax = max((t for _, _, t in pf_specs), default=128)

    n_slots = sched.n_slots
    isz = jnp.dtype(dtype).itemsize
    vmem = (
        pf_depth * pf_kmax * pf_tnmax * isz +
        4 * PB * wmax * max(isz, 4)
        + 2 * kmax * tnmax * isz
        + min(2, SMAX // SCHUNK) * 2 * B * SCHUNK * D * isz
        + 2 * world * PB * arw * isz
        # margin for the branch math's values (f32 rows, matmul
        # operands in flight): the chip compiler sized them 8.8 MiB at
        # Qwen3-8B widths on one chip and 11.5 MiB on a tp=4 shard —
        # the 4 MiB this replaces failed both (63.56M needed vs 58.75M
        # asked; PR 24)
        + (16 << 20)
    )

    # world/axis for the trace header rank (the AR/barrier branch keys
    # carry the mesh axis when the graph is distributed)
    trace_axis = next((k[2] for k in ar_keys
                       if k[0] == "allreduce_add" and k[3] > 1),
                      None) or next((k[1] for k in ar_keys
                                     if k[0] == "barrier" and k[2] > 1),
                                    None)

    def kernel(q_ref, pos_ref, tbl_ref, ws_in, *rest):
        nw = len(weight_names)
        w_refs = rest[:nw]
        tail = list(rest[nw:])
        tcur = tail.pop() if build is not None else None
        if nc > 1:
            sb = tail.pop()
        (norms, rope_cs, k_cache, v_cache, ws_out) = tail[:5]
        tail = tail[5:]
        tbuf = tail.pop(0) if build is not None else None
        (vin, vin2, vout, vw, vkv, vrope, vnq, vnk, vpf, mailbox,
         ld1, ld2, st, wsems, kvsem, kvsems, send, recv, pfsem,
         chsem) = tail
        del ws_in  # aliased: access via the output ref
        tctx = trace_ev.make_ctx(
            build, tbuf, tcur,
            lane=pl.program_id(0) if nc > 1 else 0)
        env = _Env(
            tctx=tctx,
            dtype=dtype, batch=B, pb=PB, wmax=wmax, pos=pos_ref,
            table=tbl_ref, straggler=straggler,
            ws=ws_out, weights=dict(zip(weight_names, w_refs)),
            norms=norms, rope_cs=rope_cs, k_cache=k_cache,
            v_cache=v_cache, vin=vin, vin2=vin2, vout=vout, vw=vw,
            vkv=vkv, vrope=vrope, vnq=vnq, vnk=vnk, vpf=vpf,
            pfsem=pfsem, pf_specs=pf_specs, pf_depth=pf_depth,
            mm_tn=mm_tiles, tiled=frozenset(tiled_weights),
            store_widths=store_widths, chsem=chsem, mailbox=mailbox,
            ld1=ld1, ld2=ld2,
            st=st, wsems=wsems, kvsem=kvsem, kvsems=kvsems, send=send,
            recv=recv,
        )
        bodies = [_BRANCH_BUILDERS[k[0]](k, env) for k in branch_keys]
        if nc > 1:
            ci = pl.program_id(0)
            ti = pl.program_id(1)

            def row(j):
                return q_ref[ci, ti, j]
        else:
            ti = pl.program_id(0)

            def row(j):
                return q_ref[ti, j]

        a = [row(j) for j in range(1, ROW)]

        # trace init: each core's first queue row, before any emit
        if build is not None:
            @pl.when(ti == 0)
            def _trace_init():
                trace_ev.init_ctx(
                    tctx,
                    rank=(jax.lax.axis_index(trace_axis)
                          if trace_axis is not None else 0),
                    lane_id=pl.program_id(0) if nc > 1 else 0)

        if nc > 1:
            # scoreboard waits: consume the planned delta of completions
            # of each other queue from the LOCAL semaphore instance
            for c2 in range(nc):
                delta = row(ROW + c2)

                @pl.when(delta > 0)
                def _(c2=c2, delta=delta):
                    with trace_ev.span(tctx,
                                       trace_ev.REGIONS["mega.sb_wait"],
                                       payload=c2, aux=ti):
                        pltpu.semaphore_wait(sb.at[c2], delta)

        def dispatch(f):
            # pend_early=1: the previous row's deferred store must land
            # before this task's loads (its reads alias the stored slot,
            # or the branch has no late-drain site)
            @pl.when(jnp.logical_and(a[10] > 0, a[11] == 1))
            def _early_drain():
                _drain_pending(env, a[10])

            f(a)
            if not getattr(f, "handles_prefetch", False):
                _maybe_prefetch(env, a[6], a[7], a[8])

        # task span: payload = branch id, aux = queue position. Padding
        # and drain rows (the noop branch) are excluded so a queue's
        # traced span count equals its scheduled length
        # (attribution.compare_predicted's coverage check).
        if build is not None:
            noop_b = branch_of.get(("noop",))
            is_task = jnp.asarray(True) if noop_b is None \
                else (row(0) != noop_b)

            @pl.when(is_task)
            def _task_begin():
                trace_ev.emit(tctx, trace_ev.REGIONS["mega.task"],
                              trace_ev.KIND_BEGIN, payload=row(0),
                              aux=ti)

        jax.lax.switch(row(0), [lambda f=f: dispatch(f) for f in bodies])

        if build is not None:
            @pl.when(is_task)
            def _task_end():
                trace_ev.emit(tctx, trace_ev.REGIONS["mega.task"],
                              trace_ev.KIND_END, payload=row(0), aux=ti)

        if nc > 1:
            sig = row(ROW + nc)

            @pl.when(sig > 0)
            def _():
                # broadcast completion of queue `ci` to every core's
                # instance of sb[ci] (queue id != physical core id)
                for c2 in range(nc):
                    pltpu.semaphore_signal(sb.at[ci], 1, core_index=c2)

    def run(pos, table, ws, weights: Dict[str, jax.Array], norms,
            rope_cs, k, v):
        """k/v are PAGE POOLS (L, Hkv_loc, n_pages, SCHUNK, D); `table`
        (B, SMAX//SCHUNK) int32 maps (seq, chunk) -> pool page. Dense
        callers pass their cache reshaped to the page grid plus the
        identity table (see MegaQwen3._device_step)."""
        any_spec = pl.BlockSpec(memory_space=pl.ANY)
        nw = len(weight_names)
        grid = (nc, qmax) if nc > 1 else (len(order),)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * 2
            + [any_spec] * (1 + nw + 4),
            out_specs=((any_spec, trace_ev.out_spec())
                       if build is not None else any_spec),
            scratch_shapes=[
                pltpu.VMEM((PB, wmax), dtype),           # vin
                pltpu.VMEM((max(PB, 2), wmax), dtype),   # vin2 (rows 0/1:
                                                         #  norm vectors)
                pltpu.VMEM((PB, wmax), dtype),           # vout
                pltpu.VMEM((2, kmax, tnmax), dtype),     # vw double buffer
                # KV page slots: 1 when the whole cache is one page,
                # a double buffer on the chunked long-context path
                pltpu.VMEM((min(2, SMAX // SCHUNK), 2, B, SCHUNK, D),
                           dtype),
                pltpu.VMEM((B, 8, D), jnp.float32),      # vrope stripes
                # f32 8-row stripes (see _rms_norm_branch)
                pltpu.VMEM((8, norm_width), jnp.float32),  # vnq
                pltpu.VMEM((8, norm_width), jnp.float32),  # vnk
                pltpu.VMEM((pf_depth, pf_kmax, pf_tnmax),  # vpf arena
                           dtype),
                pltpu.VMEM((2, world, PB, arw), dtype),  # AR mailbox
                pltpu.SemaphoreType.DMA,                 # ld1
                pltpu.SemaphoreType.DMA,                 # ld2
                pltpu.SemaphoreType.DMA,                 # st
                pltpu.SemaphoreType.DMA((2,)),           # wsems
                pltpu.SemaphoreType.DMA,                 # kvsem
                pltpu.SemaphoreType.DMA(                 # kvsems (pages)
                    (min(2, SMAX // SCHUNK),)),
                pltpu.SemaphoreType.DMA,                 # send
                pltpu.SemaphoreType.DMA((2,)),           # recv (per-parity)
                pltpu.SemaphoreType.DMA((pf_depth,)),    # pfsem (per-slot)
                pltpu.SemaphoreType.DMA,                 # chsem (AR churn)
            ] + (
                # multi-core scoreboard: sb[c] counts queue c completions
                [pltpu.SemaphoreType.REGULAR((nc,))] if nc > 1 else []
            ) + (
                [trace_ev.cursor_scratch()] if build is not None else []
            ),
        )
        extra: Dict[str, Any] = {}
        if nc > 1:
            from triton_dist_tpu.lang.core import use_interpret

            if use_interpret():
                extra["interpret"] = pltpu.InterpretParams(
                    num_cores_or_threads=nc,
                    detect_races=os.environ.get("TDT_MEGA_RACES") == "1",
                )
            else:
                phys = physical_core_count()
                if phys < nc:
                    raise RuntimeError(
                        f"megakernel schedule uses {nc} cores but this "
                        f"chip has {phys} TensorCore(s); re-schedule with "
                        f"num_cores={phys} (multi-core needs v4/v5p-class "
                        "megacore chips)"
                    )
        out_shape = (jax.ShapeDtypeStruct(ws.shape, ws.dtype),) + (
            (trace_ev.out_shape(build, lanes=nc),)
            if build is not None else ())
        fn = tpu_call(
            kernel,
            name=name,
            grid_spec=grid_spec,
            out_shape=out_shape if build is not None else out_shape[0],
            # inputs: queue(0) pos(1) table(2) ws(3) weights(4..) ...
            input_output_aliases={3: 0},
            compiler_params=compiler_params(
                has_side_effects=True,
                collective_id=next_collective_id(name) if world > 1
                else None,
                vmem_limit_bytes=int(vmem),
                dimension_semantics=(
                    ("parallel", "arbitrary") if nc > 1
                    else ("arbitrary",)
                ),
            ),
            **extra,
        )
        w_list = [weights[n] for n in weight_names]
        return fn(jnp.asarray(queue), pos, jnp.asarray(table, jnp.int32),
                  ws, *w_list, norms, rope_cs, k, v)
        # (traced builds: fn returns (ws, trace_buf) — see docstring)

    return CompiledMega(
        run=run, queue=queue, n_slots=n_slots, pb=PB, wmax=wmax,
        norm_width=norm_width, branch_keys=branch_keys,
        weight_names=weight_names, mm_tiles=mm_tiles,
        tiled_weights=tiled_weights,
    )
