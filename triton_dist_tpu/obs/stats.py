"""In-kernel aggregate stat rows — O(1) always-on kernel telemetry.

The trace plane (trace/events.py) answers "where did this run's time
go" with a full event stream: per-record SMEM stores, a (1+cap, 8)
buffer per core, offline decode. That is the right tool for a deep
dive and the wrong one for always-on production telemetry. This module
is the O(1) counterpart: each metered kernel carries ONE trailing
(1, STAT_WORDS) i32 SMEM row per core — the trace-buffer
trailing-output idiom with the buffer collapsed to aggregates:

    [OMAGIC, rank, events, sem_wait, dma_wait, send_bytes, trips, fmt]

  events      the metering clock: one tick per trace-record-equivalent
              event (span BEGIN/END, instant) — the same deterministic
              seq clock trace/collect.py assigns virtual time on.
  sem_wait /  accumulated wait-span durations in vticks, classified by
  dma_wait    trace.events.REGION_CLASS. When a kernel is built under
              BOTH trace.building() and obs.stats.building(), the
              combined span/instant helpers below advance this clock in
              lockstep with the trace cursor, so the stat-row sums are
              EXACTLY the per-region span-time sums trace/attribution
              computes from the full stream (test-pinned,
              tests/test_obs.py). Metered-only builds tick the same
              clock without the stream: each bare wait costs 1 vtick.
  send_bytes  wire bytes this core pushed (remote DMA payload bytes at
              the format actually on the wire — int8 image bytes for
              quantized legs), the always-on form of
              attribution.wire_send_bytes.
  trips       guard-watchdog trips recorded by a coexisting guard build
              (faults/guard.py bumps this through GuardCtx.octx).
  fmt         wire-format code (FMT_CODES) so bytes are attributable
              by format without a side channel.

Two instrumentation styles, both zero-cost when off:

  explicit    kernels with existing trace regions (ag_gemm) replace
              their raw trace_ev.span/instant calls with the combined
              helpers here, passing (tctx, octx) — the agreement-pinned
              style.
  ambient     kernels whose waits/puts all route through lang/shmem
              primitives (the two-shot-AR ring legs, ring/full-mesh
              allgather, LL-AG) attach ONE MeterCtx around their body
              (`with stats.attached(octx):`, the faults/guard pattern);
              the primitives call `meter_wait`/`meter_send` hooks that
              are a single None-check when no ctx is attached.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.trace import events as trace_ev

STAT_WORDS = 8
OMAGIC = 0x5D7A  # 'obs' header tag (trace 0x7D7A / guard 0x6D7A family)

# word indices of the stat row
W_MAGIC, W_RANK, W_EVENTS, W_SEM, W_DMA, W_BYTES, W_TRIPS, W_FMT = \
    range(STAT_WORDS)

FMT_CODES = {"native": 0, "fp8": 1, "int8": 2}
_FMT_NAMES = {v: k for k, v in FMT_CODES.items()}

_WAIT_WORD = {"sem_wait": W_SEM, "dma_wait": W_DMA}


def fmt_code(fmt) -> int:
    """Stat-row format code of a wire.WireFormat / format kind / None."""
    kind = getattr(fmt, "kind", fmt) or "native"
    return FMT_CODES.get(str(kind), 0)


# -- build flag (host side, the trace.building discipline) -------------------


@dataclasses.dataclass(frozen=True)
class ObsBuild:
    """Active metering build: kernels constructed while one is active
    compile the stat-row stores in (one extra trailing SMEM output per
    metered entry point, AFTER any trace and guard buffers); otherwise
    they compile to exactly the unmetered program."""


_BUILD_STATE = threading.local()


def active_build() -> Optional[ObsBuild]:
    return getattr(_BUILD_STATE, "build", None)


@contextlib.contextmanager
def building():
    """Enable stat-row metering for kernels traced inside the block.

    Contract: every metered entry point returns ONE extra trailing
    output — its (1, STAT_WORDS) i32 stat row ((cores, 1, STAT_WORDS)
    for multi-core kernels) — after any trace buffer and guard buffer;
    fallback paths return an empty row (build-stable output trees, the
    trace.with_trace idiom)."""
    prev = getattr(_BUILD_STATE, "build", None)
    _BUILD_STATE.build = ObsBuild()
    try:
        yield _BUILD_STATE.build
    finally:
        _BUILD_STATE.build = prev


def out_shape(build: ObsBuild, lanes: int = 0):
    shape = (1, STAT_WORDS)
    if lanes:
        shape = (lanes,) + shape
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def out_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def cursor_scratch():
    # [0] = event (vtick) cursor, [1] = accumulated straggle delay
    return pltpu.SMEM((2,), jnp.int32)


def new_stream(build: ObsBuild, rank=-1, fmt=0):
    """An empty host-level stat row (fallback paths owe one under an
    active build)."""
    row = jnp.zeros((1, STAT_WORDS), jnp.int32)
    return row.at[0, W_MAGIC].set(OMAGIC) \
              .at[0, W_RANK].set(jnp.asarray(rank, jnp.int32)) \
              .at[0, W_FMT].set(jnp.asarray(fmt, jnp.int32))


def with_stats(build: Optional[ObsBuild], res, row=None):
    """Append the trailing stat-row output a metered entry point owes
    its caller under an active build — the outermost trailing buffer
    (strip order: stats, then guard, then trace)."""
    if build is None:
        return res
    if row is None:
        row = new_stream(build)
    return res + (row,) if isinstance(res, tuple) else (res, row)


def primary(res):
    """The metered call's primary result(s), stat row stripped when a
    build is active (the trace/guard `primary` analog for composite
    callers that do not thread rows outward)."""
    if active_build() is None:
        return res
    out = res[:-1]
    return out[0] if len(out) == 1 else out


@contextlib.contextmanager
def metered(registry=None):
    """building() plus an ambient Registry: host entry points that own
    their kernels' stat rows (all_reduce_op, ll_all_gather_op) decode
    the rows into this registry and return their ORIGINAL output tree —
    the ergonomic always-on form:

        with obs.stats.metered() as reg:
            out = all_reduce_op(arr, mesh, wire_format="fp8")
        reg.counter("obs_wire_bytes", kernel="allreduce", fmt="fp8")

    Lower-level entry points (ag_gemm, two_shot_all_reduce) still
    return their trailing rows — they run inside jit, where a registry
    cannot be written."""
    from triton_dist_tpu.obs.registry import Registry

    reg = registry if registry is not None else Registry()
    prev_reg = getattr(_BUILD_STATE, "registry", None)
    _BUILD_STATE.registry = reg
    try:
        with building():
            yield reg
    finally:
        _BUILD_STATE.registry = prev_reg


def ambient_registry():
    """The registry of the innermost `metered()` block (None outside).
    Host entry points fold decoded rows into it via record_stats."""
    return getattr(_BUILD_STATE, "registry", None)


# -- kernel-side context ------------------------------------------------------


@dataclasses.dataclass
class MeterCtx:
    """In-kernel handle: `row` the (1, STAT_WORDS) (or per-core
    (lanes, 1, STAT_WORDS)) i32 SMEM output ref, `cur` the 2-word SMEM
    event-cursor/straggle scratch."""

    row: Any
    cur: Any
    lane: Any = None

    def _set(self, w, v):
        if self.lane is not None:
            self.row[self.lane, 0, w] = v
        else:
            self.row[0, w] = v

    def _get(self, w):
        return (self.row[self.lane, 0, w] if self.lane is not None
                else self.row[0, w])

    def vt(self):
        """Current virtual time: event count + injected straggle delay
        (exactly trace/collect.py's vtime at the same program point)."""
        return self.cur[0] + self.cur[1]

    def tick(self) -> None:
        """One trace-record-equivalent event on the metering clock."""
        nxt = self.cur[0] + 1
        self.cur[0] = nxt
        self._set(W_EVENTS, nxt)

    def straggle(self, payload) -> None:
        """Injected-skew delay (the trace 'straggle' payload): shifts
        the virtual clock for every later event."""
        self.cur[1] = self.cur[1] + jnp.asarray(payload, jnp.int32)

    def add(self, word: int, amount) -> None:
        self._set(word, self._get(word) + jnp.asarray(amount, jnp.int32))

    def add_wait(self, cls: Optional[str], dur) -> None:
        w = _WAIT_WORD.get(cls)
        if w is not None:
            self.add(w, dur)

    def add_bytes(self, nbytes) -> None:
        self.add(W_BYTES, nbytes)

    def add_trip(self) -> None:
        self.add(W_TRIPS, 1)


def make_ctx(build: Optional[ObsBuild], row_ref, cur_ref,
             lane=None) -> Optional[MeterCtx]:
    if build is None:
        return None
    return MeterCtx(row=row_ref, cur=cur_ref, lane=lane)


def init_ctx(ctx: Optional[MeterCtx], rank=0, fmt: int = 0) -> None:
    """Write the header words and zero every counter (SMEM is NOT
    zero-initialized — decode trusts only rows carrying the magic)."""
    if ctx is None:
        return
    ctx.cur[0] = 0
    ctx.cur[1] = 0
    ctx._set(W_MAGIC, OMAGIC)
    ctx._set(W_RANK, jnp.asarray(rank, jnp.int32))
    for w in (W_EVENTS, W_SEM, W_DMA, W_BYTES, W_TRIPS):
        ctx._set(w, 0)
    ctx._set(W_FMT, jnp.asarray(fmt, jnp.int32))


# -- the trace-time attach stack (ambient style, the guard pattern) ----------

_CTX_STATE = threading.local()


def current() -> Optional[MeterCtx]:
    stack = getattr(_CTX_STATE, "stack", None)
    return stack[-1] if stack else None


@contextlib.contextmanager
def attached(ctx: Optional[MeterCtx]):
    """Make `ctx` the ambient meter while the kernel body traces (None
    attaches nothing — the zero-cost-off path)."""
    if ctx is None:
        yield None
        return
    stack = getattr(_CTX_STATE, "stack", None)
    if stack is None:
        stack = _CTX_STATE.stack = []
    stack.append(ctx)
    try:
        yield ctx
    finally:
        stack.pop()


def meter_wait(cls: str = "sem_wait",
               ctx: Optional[MeterCtx] = None) -> None:
    """Ambient hook the shmem wait primitives call after consuming: one
    wait-span equivalent (BEGIN + END ticks, duration 1 vtick) accrued
    to `cls`. One None-check when nothing is attached."""
    ctx = ctx or current()
    if ctx is None:
        return
    ctx.tick()
    ctx.add_wait(cls, 1)
    ctx.tick()


def meter_send(nbytes: int, ctx: Optional[MeterCtx] = None) -> None:
    """Ambient hook for remote puts: `nbytes` is the payload byte count
    actually on the wire (the int8 image bytes on quantized legs)."""
    ctx = ctx or current()
    if ctx is None:
        return
    ctx.tick()
    ctx.add_bytes(nbytes)


# -- combined trace+obs emit helpers (explicit style) -------------------------


@contextlib.contextmanager
def span(tctx, octx: Optional[MeterCtx], region: int, payload=0, aux=0):
    """Combined span: the trace BEGIN/END records (when tctx) plus the
    span's vtick duration accrued to the region's REGION_CLASS bucket
    (when octx). The meter clock ticks once per record in the same
    order the trace cursor advances, which is what makes the stat-row
    sums bitwise-equal to attribution's per-region totals on a shared
    traced+metered build."""
    trace_ev.emit(tctx, region, trace_ev.KIND_BEGIN, payload, aux)
    t0 = octx.vt() if octx is not None else None
    if octx is not None:
        octx.tick()
    yield
    if octx is not None:
        cls = trace_ev.REGION_CLASS.get(trace_ev.region_name(region))
        octx.add_wait(cls, octx.vt() - t0)
        octx.tick()
    trace_ev.emit(tctx, region, trace_ev.KIND_END, payload, aux)


def instant(tctx, octx: Optional[MeterCtx], region: int, payload=0,
            aux=0) -> None:
    """Combined instant; 'straggle' payloads also advance the meter's
    delay clock (trace/collect.py's virtual-time rule)."""
    trace_ev.instant(tctx, region, payload, aux)
    if octx is not None:
        octx.tick()
        if region == trace_ev.REGIONS["straggle"]:
            octx.straggle(payload)


# -- host-side decode ---------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelStats:
    """One decoded stat row (one core of one kernel execution)."""

    rank: int
    events: int
    sem_wait: int
    dma_wait: int
    send_bytes: int
    trips: int
    fmt: int

    @property
    def fmt_name(self) -> str:
        return _FMT_NAMES.get(self.fmt, f"fmt{self.fmt}")

    def __str__(self):
        return (f"rank {self.rank}: events={self.events} "
                f"sem_wait={self.sem_wait} dma_wait={self.dma_wait} "
                f"bytes={self.send_bytes} trips={self.trips} "
                f"fmt={self.fmt_name}")


def decode(buf) -> List[KernelStats]:
    """Decode stat row(s) — any array whose trailing dims are
    (1, STAT_WORDS); leading dims (ranks, legs, ...) flatten. A row
    without the magic is malformed (uninitialized or clobbered)."""
    import numpy as np

    a = np.asarray(buf)
    if a.ndim < 2 or a.shape[-1] != STAT_WORDS:
        raise ValueError(f"not a stat row: shape {a.shape}")
    flat = a.reshape(-1, STAT_WORDS)
    out: List[KernelStats] = []
    for r in flat:
        if int(r[W_MAGIC]) != OMAGIC:
            raise ValueError(
                f"stat row magic {int(r[W_MAGIC]):#x} != {OMAGIC:#x} "
                "(uninitialized or clobbered)")
        out.append(KernelStats(
            rank=int(r[W_RANK]), events=int(r[W_EVENTS]),
            sem_wait=int(r[W_SEM]), dma_wait=int(r[W_DMA]),
            send_bytes=int(r[W_BYTES]), trips=int(r[W_TRIPS]),
            fmt=int(r[W_FMT])))
    return out


def totals(*bufs) -> KernelStats:
    """Sum of every decoded row (rank/fmt = -1/0 unless uniform)."""
    rows: List[KernelStats] = []
    for b in bufs:
        if b is not None:
            rows.extend(decode(b))
    ranks = {r.rank for r in rows}
    fmts = {r.fmt for r in rows}
    return KernelStats(
        rank=ranks.pop() if len(ranks) == 1 else -1,
        events=sum(r.events for r in rows),
        sem_wait=sum(r.sem_wait for r in rows),
        dma_wait=sum(r.dma_wait for r in rows),
        send_bytes=sum(r.send_bytes for r in rows),
        trips=sum(r.trips for r in rows),
        fmt=fmts.pop() if len(fmts) == 1 else 0)


def record_stats(registry, stats, kernel: str) -> None:
    """Fold decoded rows (or a buffer) into a metrics Registry — the
    bridge from the in-kernel tier to the always-on tier: counters
    obs_sem_wait_ticks / obs_dma_wait_ticks / obs_wire_bytes{fmt=} /
    obs_guard_trips, labelled by kernel."""
    if not isinstance(stats, (list, tuple)):
        stats = decode(stats)
    for s in stats:
        registry.inc("obs_sem_wait_ticks", s.sem_wait, kernel=kernel)
        registry.inc("obs_dma_wait_ticks", s.dma_wait, kernel=kernel)
        registry.inc("obs_wire_bytes", s.send_bytes, kernel=kernel,
                     fmt=s.fmt_name)
        registry.inc("obs_guard_trips", s.trips, kernel=kernel)
        registry.inc("obs_kernel_events", s.events, kernel=kernel)


def consume_rows(buf, kernel: str) -> None:
    """Host-op tail: fold a trailing stat-row output into the ambient
    metered() registry (no-op without one). ONE definition of the
    consume contract — the rows are eaten here, so every host op's
    output tree keeps its documented shape."""
    import numpy as np

    reg = ambient_registry()
    if reg is None:
        return
    record_stats(
        reg, decode(np.asarray(buf).reshape(-1, STAT_WORDS)),
        kernel=kernel)


def agree_with_trace(stats: List[KernelStats], tl, stream: str) -> None:
    """THE agreement pin: on a run whose kernel was built under BOTH
    trace.building() and stats.building(), every rank's stat-row
    sem_wait/dma_wait must equal the summed span durations of that
    class in the trace timeline (attribution's per-region totals
    aggregated by REGION_CLASS). Raises AssertionError with the diff."""
    from triton_dist_tpu.trace import attribution as attr

    cls = attr.classify(tl)
    by_rank: dict = {}
    for (st, rank, _lane), d in cls.items():
        if st != stream:
            continue
        agg = by_rank.setdefault(rank, {"sem_wait": 0.0, "dma_wait": 0.0})
        agg["sem_wait"] += d["sem_wait"]
        agg["dma_wait"] += d["dma_wait"]
    for s in stats:
        want = by_rank.get(s.rank, {"sem_wait": 0.0, "dma_wait": 0.0})
        assert s.sem_wait == int(want["sem_wait"]), (
            f"rank {s.rank}: stat-row sem_wait {s.sem_wait} != trace "
            f"attribution {want['sem_wait']}")
        assert s.dma_wait == int(want["dma_wait"]), (
            f"rank {s.rank}: stat-row dma_wait {s.dma_wait} != trace "
            f"attribution {want['dma_wait']}")
