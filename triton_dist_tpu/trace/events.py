"""Device-side event records for the in-kernel trace subsystem.

The reference ships intra-kernel profiling hooks (ref: the
`profile_allocated_buffer` / in-kernel event slots of
python/triton_dist/tools/profiler.py) so overlap quality can be SEEN,
not inferred from end-to-end scalars; T3 (arXiv 2401.16677) makes the
same point — fine-grained tracking of compute/collective progress is
the substrate for both diagnosing and driving overlap. This module is
the TPU-native analog: a fixed-capacity per-core buffer of fixed-width
i32 records written by scalar SMEM stores inside Pallas kernels.

Record format (RECORD_WORDS i32 words per row):

    [region, kind, seq, payload, t_lo, t_hi, aux, 0]

  region   stable id from REGIONS (see region_id/region_name)
  kind     KIND_BEGIN | KIND_END | KIND_INSTANT
  seq      per-buffer monotonic record index (the deterministic clock)
  payload  region-specific datum (ring step, chunk id, branch id, ...)
  t_lo/hi  split i64 timestamp; all-zero under the seq clock
  aux      second region-specific datum

Row 0 of every buffer is a header:

    [MAGIC, count, cap, rank, lane, clock, stream, 0]

`count` is the TOTAL number of emits (count > cap means count - cap
records were dropped off the tail — the buffer saturates rather than
wrapping, so BEGIN/END pairs in the kept prefix never tear).

Clock semantics (the injectable-clock design): records carry the
monotonic `seq` counter — deterministic, identical across reruns, and
exactly ordered within a buffer. Wall-clock is reconstructed host-side
(trace/collect.py): per-region host timing anchors each buffer, and
injected straggler delays ride as REGION "straggle" payload ticks so
skew is visible deterministically on the lockstep CPU interpreter.
`t_lo/t_hi` are reserved for a real cycle-counter stamp on hardware —
`TraceCtx.stamp` is the single injection point; today it returns zeros
(documented limitation: Mosaic has no portable cycle read).

Zero cost when off: every helper is a trace-time no-op when its ctx (or
the active build) is None — no refs are added, no stores are emitted,
and instrumented kernels trace byte-identical programs (enforced by
tests/test_trace.py).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

RECORD_WORDS = 8
MAGIC = 0x7D7A  # 'trace' header tag
KIND_BEGIN = 0
KIND_END = 1
KIND_INSTANT = 2

CLOCK_SEQ = 0  # monotonic per-buffer record index (deterministic)

# Stable region registry: ids are part of the on-disk trace format
# (scripts/trace_report.py reads exported JSONs from other runs), so
# entries are append-only.
REGIONS = {
    "straggle": 1,       # injected skew (payload = delay ticks, 0 off-rank)
    "a2a.local": 2,      # local-segment chunk copy wait (payload=chunk)
    "a2a.send": 3,       # remote chunk DMA issued (payload=step, aux=chunk)
    "a2a.wait": 4,       # delivery-semaphore wait (payload=step, aux=chunk)
    "a2a.meta": 5,       # splits-metadata transfer
    "ag.ring_wait": 6,   # AG+GEMM ring-step delivery wait (payload=step)
    "ag.a_wait": 7,      # AG+GEMM A-tile DMA wait (payload=flat tile)
    "ag.tile": 8,        # AG+GEMM output tile stored (payload=flat tile)
    "rs.partial": 9,     # GEMM+RS partial-chunk MXU work (payload=chunk)
    "rs.credit": 10,     # GEMM+RS credit wait (payload=ring step)
    "rs.hop": 11,        # GEMM+RS hop recv wait (payload=ring step)
    "mega.task": 12,     # megakernel task span (payload=branch, aux=row)
    "mega.sb_wait": 13,  # scoreboard wait (payload=queue waited on)
    "mega.pf": 14,       # prefetch-arena consume (payload=pf_in; 0=cold)
    "ep.phase": 15,      # pipeline phase mark (payload=phase code)
    "ep.ffn_chunk": 16,  # per-chunk grouped FFN (payload=chunk)
    "host": 17,          # host-side python span (collect.TraceSession)
    "fp.send": 18,       # flash-prefill segment DMA issued (payload=offset)
    "fp.wait": 19,       # flash-prefill segment delivery wait (payload=offset)
    "fp.fold": 20,       # flash-prefill per-segment fold (payload=offset)
    "guard.trip": 21,    # watchdog trip (payload=site id, aux=slot) —
    # emitted when a kernel carries BOTH a trace ctx and a guard ctx
    # (faults/guard.py), so every recovery is attributable in Perfetto
    "fault.inject": 22,  # host-side fault-injection instant (chaos
    # plane / scheduler quarantine markers ride host spans; this region
    # tags in-band injection points)
    # 23-25 were the regions of a loop that is gone (PR 32): the ids
    # stay unused, so a trace exported before then still names no other
    # region by them
}
_REGION_NAMES = {v: k for k, v in REGIONS.items()}

# Attribution classification (trace/attribution.py): how each region's span
# time is classified. Regions absent here are structural (instants).
REGION_CLASS = {
    "a2a.local": "dma_wait",
    "a2a.wait": "sem_wait",
    "a2a.meta": "dma_wait",
    "ag.ring_wait": "sem_wait",
    "ag.a_wait": "dma_wait",
    "rs.partial": "compute",
    "rs.credit": "sem_wait",
    "rs.hop": "sem_wait",
    "mega.task": "compute",
    "mega.sb_wait": "sem_wait",
    "ep.ffn_chunk": "compute",
    "fp.wait": "sem_wait",
    "fp.fold": "compute",
}

# ep.phase payload codes
PHASE_DISPATCH = 1
PHASE_FFN = 2
PHASE_COMBINE = 3

# Shared verify/trace event classification: which trace region OBSERVES each
# static-verifier op kind at run time, per instrumented protocol. The
# static HB engine (verify/engine.py) proves ordering over "put" and
# "wait_recv" ops; the trace subsystem measures the same events as
# "a2a.send" instants and "a2a.wait" spans — tests/test_verify.py
# cross-validates the verifier's delivery edges against the
# a2a_step_waits replay through this table, so the two subsystems can
# never silently disagree about what a protocol event is.
VERIFY_OP_REGIONS = {
    "all_to_all_chunked": {"put": "a2a.send", "wait_recv": "a2a.wait"},
    "allgather_gemm": {"wait_recv": "ag.ring_wait"},
    "gemm_reduce_scatter": {"wait": "rs.credit", "wait_recv": "rs.hop"},
    "flash_prefill": {"put": "fp.send", "wait_recv": "fp.wait"},
}


def region_id(name: str) -> int:
    return REGIONS[name]


def region_name(rid: int) -> str:
    return _REGION_NAMES.get(int(rid), f"region{int(rid)}")


# -- build flag (host side) ---------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TraceBuild:
    """Active trace build: kernels constructed while a build is active
    compile the record stores in (an extra trailing SMEM output on each
    instrumented kernel); otherwise they compile to exactly the
    uninstrumented program."""

    cap: int = 512
    clock: int = CLOCK_SEQ


_BUILD: Optional[TraceBuild] = None

# the fusion plan whose execution the trace covers (plan.Plan.plan_id):
# models/dense.forward notes it at trace time, collect.assemble stamps
# it onto the Timeline so exported spans carry provenance
_LAST_PLAN: Optional[str] = None


def note_plan(plan_id: Optional[str]) -> None:
    """Record the plan-id of the forward being traced (host-side,
    trace-time). The most recent note wins — one Timeline covers one
    step shape, which holds one Plan."""
    global _LAST_PLAN
    _LAST_PLAN = plan_id


def last_plan() -> Optional[str]:
    return _LAST_PLAN


def active_build() -> Optional[TraceBuild]:
    """The build in effect at TRACE time (None = tracing off). Kernels
    consult this when the pallas_call is constructed — flipping it after
    a jit has cached its executable has no effect on that executable."""
    return _BUILD


@contextlib.contextmanager
def building(cap: int = 512):
    """Enable trace instrumentation for kernels traced inside the block.

    Contract: while a build is active, every instrumented entry point
    returns ONE extra trailing output — its (1+cap, RECORD_WORDS) i32
    trace buffer (per core for the megakernel) — which the caller feeds
    to trace.collect.assemble. Default builds return exactly their
    documented outputs."""
    global _BUILD, _LAST_PLAN
    prev = _BUILD
    _BUILD = TraceBuild(cap=int(cap))
    _LAST_PLAN = None  # a fresh build must not inherit a stale plan-id
    try:
        yield _BUILD
    finally:
        _BUILD = prev


def with_trace(build: Optional["TraceBuild"], res, tbuf=None):
    """Append the trailing trace output an instrumented entry point owes
    its caller under an active build (an empty stream when the executed
    path produced none — fallbacks, n==1 shortcuts). THE one helper for
    that contract; kernels share it instead of hand-rolling the arity
    logic."""
    if build is None:
        return res
    if tbuf is None:
        tbuf = new_stream(build)
    return res + (tbuf,) if isinstance(res, tuple) else (res, tbuf)


def primary(res):
    """The instrumented call's primary result(s), with the trailing
    trace buffer stripped when a build is active. Composite callers that
    do not (yet) thread per-kernel buffers outward wrap their inner
    calls with this so their call graphs stay build-safe — the records
    of that inner call are dropped, nothing else changes."""
    if _BUILD is None:
        return res
    out = res[:-1]
    return out[0] if len(out) == 1 else out


# -- kernel-side API ----------------------------------------------------------


@dataclasses.dataclass
class TraceCtx:
    """In-kernel handle: `buf` is the (lanes, 1+cap, WORDS) or
    (1+cap, WORDS) i32 SMEM output ref, `cur` a small SMEM scratch
    holding the cursor, `lane` the per-core row (None for single-buffer
    kernels)."""

    buf: Any
    cur: Any
    cap: int
    lane: Any = None

    def stamp(self, seq):
        """The injectable in-kernel clock. Seq clock: no extra words
        (t_lo/t_hi stay 0). Hardware cycle counters hook in here."""
        del seq
        return None

    def _row(self, r):
        return (self.buf.at[self.lane, r] if self.lane is not None
                else self.buf.at[r])

    def _store(self, r, w, v):
        if self.lane is not None:
            self.buf[self.lane, r, w] = v
        else:
            self.buf[r, w] = v


def out_shape(build: TraceBuild, lanes: int = 0):
    """ShapeDtypeStruct of the kernel's trace output (lanes=0: single
    buffer; >0: one buffer per core)."""
    shape = (1 + build.cap, RECORD_WORDS)
    if lanes:
        shape = (lanes,) + shape
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def out_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def cursor_scratch():
    return pltpu.SMEM((2,), jnp.int32)


def make_ctx(build: Optional[TraceBuild], buf_ref, cur_ref,
             lane=None) -> Optional[TraceCtx]:
    if build is None:
        return None
    return TraceCtx(buf=buf_ref, cur=cur_ref, cap=build.cap, lane=lane)


def init_ctx(ctx: Optional[TraceCtx], rank=0, lane_id=0,
             stream: int = 0) -> None:
    """Write the header and zero the cursor. Must run before the first
    emit of the (core's) buffer: SMEM scratch and output memory are NOT
    zero-initialized (the interpreter leaves an int32-min sentinel, and
    Mosaic leaves garbage) — decode trusts only rows the header counts."""
    if ctx is None:
        return
    ctx.cur[0] = 0
    ctx._store(0, 0, MAGIC)
    ctx._store(0, 1, 0)
    ctx._store(0, 2, ctx.cap)
    ctx._store(0, 3, jnp.asarray(rank, jnp.int32))
    ctx._store(0, 4, jnp.asarray(lane_id, jnp.int32))
    ctx._store(0, 5, CLOCK_SEQ)
    ctx._store(0, 6, stream)
    ctx._store(0, 7, 0)


def emit(ctx: Optional[TraceCtx], region: int, kind: int, payload=0,
         aux=0) -> None:
    """Append one record (drop + count when the buffer is full). A
    trace-time no-op when ctx is None — the uninstrumented program."""
    if ctx is None:
        return
    idx = ctx.cur[0]

    @pl.when(idx < ctx.cap)
    def _write():
        r = idx + 1
        ctx._store(r, 0, region)
        ctx._store(r, 1, kind)
        ctx._store(r, 2, idx)
        ctx._store(r, 3, jnp.asarray(payload, jnp.int32))
        t = ctx.stamp(idx)
        ctx._store(r, 4, 0 if t is None else t[0])
        ctx._store(r, 5, 0 if t is None else t[1])
        ctx._store(r, 6, jnp.asarray(aux, jnp.int32))
        ctx._store(r, 7, 0)

    ctx.cur[0] = idx + 1
    ctx._store(0, 1, idx + 1)


def instant(ctx: Optional[TraceCtx], region: int, payload=0,
            aux=0) -> None:
    emit(ctx, region, KIND_INSTANT, payload, aux)


@contextlib.contextmanager
def span(ctx: Optional[TraceCtx], region: int, payload=0, aux=0):
    """BEGIN on enter, END on exit — trace-time sugar (kernel bodies are
    python, so the context manager costs nothing at run time)."""
    emit(ctx, region, KIND_BEGIN, payload, aux)
    yield
    emit(ctx, region, KIND_END, payload, aux)


# -- host/jit-level marks (pure jnp — no kernels, no callbacks) ---------------


def new_stream(build: TraceBuild, stream: int = 0, rank=None):
    """A mark stream: the same (1+cap, WORDS) buffer layout as a value
    threaded functionally through jit-level code (XLA ops between
    kernels — e.g. the per-chunk FFN of the EP pipeline). Works under
    any partitioner and on hardware: marks are dynamic_update_slice, not
    callbacks."""
    buf = jnp.zeros((1 + build.cap, RECORD_WORDS), jnp.int32)
    hdr = jnp.array(
        [MAGIC, 0, build.cap, -1, 0, CLOCK_SEQ, stream, 0], jnp.int32)
    buf = buf.at[0].set(hdr)
    if rank is not None:
        buf = buf.at[0, 3].set(jnp.asarray(rank, jnp.int32))
    return buf


def mark(buf, region: int, kind: int = KIND_INSTANT, payload=0, aux=0,
         token=None):
    """Append a record to a mark stream; returns the updated stream.
    `token`: any scalar the mark must execute after — folded in as a
    zero so the data dependency (not a side effect) carries ordering.
    No-op (returns None) when buf is None."""
    if buf is None:
        return None
    idx = buf[0, 1]
    cap = buf.shape[0] - 1
    payload = jnp.asarray(payload, jnp.int32)
    if token is not None:
        payload = payload + (jnp.asarray(token).astype(jnp.int32) * 0)
    row = jnp.stack([
        jnp.asarray(region, jnp.int32), jnp.asarray(kind, jnp.int32),
        idx, payload, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
        jnp.asarray(aux, jnp.int32), jnp.zeros((), jnp.int32),
    ])
    # saturating semantics, same as the device buffers: a full stream
    # drops the record (the header count keeps counting)
    at = jnp.where(idx < cap, idx + 1, cap)
    keep = (idx < cap)[None]
    cur = jax.lax.dynamic_slice(buf, (at, 0), (1, RECORD_WORDS))
    new = jnp.where(keep, row[None], cur)
    buf = jax.lax.dynamic_update_slice(buf, new, (at, 0))
    return buf.at[0, 1].set(idx + 1)
