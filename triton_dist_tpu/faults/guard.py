"""Bounded-wait watchdogs: the in-kernel guard machinery.

A semaphore-granular overlap kernel has exactly one catastrophic
failure mode: a wait whose signal never arrives. Unguarded, that is a
hang. The guard plane converts it into a STRUCTURED, attributable
failure:

  - while a `guards.building()` block is active, instrumented kernels
    compile every guarded wait as a bounded poll: read the semaphore,
    consume only when satisfied; on deadline, write one guard row —
    (site, slot, progress, expected, observed, rank) — to the kernel's
    guard output and CONTINUE (results are garbage, but the host never
    returns them);
  - the host decodes the guard output after the kernel and raises
    `DeadlineExceeded` with the decoded rows (`guard.check`);
  - outside a build, every helper is a trace-time no-op: no refs, no
    polls, bit-identical programs with unchanged `pallas_call_count`
    (the trace/verify zero-cost-off discipline, test-enforced).

Poll semantics: a deadline-bounded re-read loop over
`pl.semaphore_read`. Mosaic lowers it; the installed (0.9.0) Pallas
TPU interpreter has no rule for `semaphore_read`, so guarded builds
compile for the chip only (tests/test_chip_compile.py keeps that
compile) and the CPU-mesh tests that construct one fail at lowering.

Buffer layout mirrors trace/events.py: (1 + cap, GUARD_WORDS) i32 SMEM,
header row [GMAGIC, trip_count, cap, rank, deadline, 0, 0, 0], trip rows
[site, slot, progress, expected, observed, rank, seq, 0] (saturating).

The module also hosts the DEGRADATION registry: a host entry point that
catches a guard trip can mark its protocol degraded
(`guard.degrade(name)`); subsequent calls with `fallback="xla"` route
straight to the XLA-collective path — a degraded step completes rather
than dies (docs/robustness.md "degradation ladder").
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

from triton_dist_tpu.faults.errors import DeadlineExceeded

_sem_read = pl.semaphore_read

GUARD_WORDS = 8
GMAGIC = 0x6D7A  # 'guard' header tag

# Stable wait-site registry (ids ride in decoded rows and tests).
SITES = {
    "wait": 1,      # generic signal_wait_until
    "barrier": 2,   # barrier_all / neighbor_barrier join
    "recv": 3,      # DMA delivery (PutHandle.wait_recv)
    "credit": 4,    # ring flow-control credit wait
    "ring": 5,      # fused-kernel ring-step delivery wait
    "segment": 6,   # flash-prefill per-segment delivery wait
    "collect": 7,   # full-mesh collect slot wait
    "wire": 8,      # wire-image integrity failure at a consume edge
}
_SITE_NAMES = {v: k for k, v in SITES.items()}


def site_name(sid: int) -> str:
    return _SITE_NAMES.get(int(sid), f"site{int(sid)}")


# -- build flag (host side, the trace.building discipline) -------------------


@dataclasses.dataclass(frozen=True)
class GuardBuild:
    """Active guard build: kernels constructed while one is active
    compile bounded-wait watchdogs in (plus one extra trailing SMEM
    guard output per instrumented entry point); otherwise they compile
    to exactly the unguarded program.

    The hardware wait budget is TIME-shaped, not an iteration count:
    each of the `deadline` polls sleeps `poll_ns` (pl.delay) between
    re-reads, so the default budget is ~deadline * poll_ns = 2.56 ms —
    far above any healthy ICI delivery, far below forever. A raw
    back-to-back re-read loop would burn its budget in microseconds
    and trip on benign latency."""

    cap: int = 32          # max recorded trips per buffer
    deadline: int = 256    # hardware polls per wait
    poll_ns: int = 10_000  # pl.delay between hardware polls


_BUILD_STATE = threading.local()


def active_build() -> Optional[GuardBuild]:
    return getattr(_BUILD_STATE, "build", None)


@contextlib.contextmanager
def suppressed():
    """Trace kernels UNGUARDED inside the block even when a build is
    active. For composite callers that cannot consume a guard buffer
    (e.g. the EP pipeline's transport leg): a guarded kernel whose trip
    rows are discarded would convert a detected fault into a silently
    wrong result — strictly worse than the unguarded status quo, which
    at least fails the way it always did. Suppression keeps the
    contract honest: guards exist exactly where their error channel
    reaches the host."""
    prev = getattr(_BUILD_STATE, "build", None)
    _BUILD_STATE.build = None
    try:
        yield
    finally:
        _BUILD_STATE.build = prev


@contextlib.contextmanager
def building(cap: int = 32, deadline: int = 256, poll_ns: int = 10_000):
    """Enable watchdog instrumentation for kernels traced inside the
    block. Contract: every guard-instrumented entry point returns ONE
    extra trailing output — its (1+cap, GUARD_WORDS) i32 guard buffer —
    AFTER any trace buffer; fallback paths return an empty stream
    (build-stable output trees, the trace/with_trace idiom)."""
    prev = getattr(_BUILD_STATE, "build", None)
    _BUILD_STATE.build = GuardBuild(cap=int(cap), deadline=int(deadline),
                                    poll_ns=int(poll_ns))
    try:
        yield _BUILD_STATE.build
    finally:
        _BUILD_STATE.build = prev


def out_shape(build: GuardBuild):
    return jax.ShapeDtypeStruct((1 + build.cap, GUARD_WORDS), jnp.int32)


def out_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def cursor_scratch():
    # [0] = trip cursor, [1] = progress counter (guard_progress)
    return pltpu.SMEM((2,), jnp.int32)


def new_stream(build: GuardBuild, rank=-1):
    """An empty host-level guard buffer (fallback paths owe one under
    an active build)."""
    buf = jnp.zeros((1 + build.cap, GUARD_WORDS), jnp.int32)
    hdr = jnp.array(
        [GMAGIC, 0, build.cap, rank, build.deadline, 0, 0, 0], jnp.int32)
    return buf.at[0].set(hdr)


def with_guard(build: Optional[GuardBuild], res, gbuf=None):
    """Append the trailing guard output an instrumented entry point
    owes its caller under an active build."""
    if build is None:
        return res
    if gbuf is None:
        gbuf = new_stream(build)
    return res + (gbuf,) if isinstance(res, tuple) else (res, gbuf)


def primary(res):
    """The instrumented call's primary result(s), with the trailing
    guard buffer stripped when a build is active (the trace.events
    `primary` analog): composite callers that do not (yet) thread guard
    buffers outward wrap their inner calls with this so their call
    graphs stay build-safe — that inner call's trips are dropped,
    nothing else changes."""
    if active_build() is None:
        return res
    out = res[:-1]
    return out[0] if len(out) == 1 else out


# -- kernel-side context ------------------------------------------------------


@dataclasses.dataclass
class GuardCtx:
    """In-kernel handle: `buf` the (1+cap, WORDS) i32 SMEM output ref,
    `cur` the 2-word SMEM cursor/progress scratch, `tctx` an optional
    TraceCtx so trips also land as trace instants (attributability)."""

    buf: Any
    cur: Any
    cap: int
    deadline: int
    poll_ns: int = 10_000
    rank: Any = 0
    tctx: Any = None
    octx: Any = None  # obs/stats.MeterCtx: trips land in the stat row


def make_ctx(build: Optional[GuardBuild], buf_ref, cur_ref, rank=0,
             tctx=None, octx=None) -> Optional[GuardCtx]:
    if build is None:
        return None
    return GuardCtx(buf=buf_ref, cur=cur_ref, cap=build.cap,
                    deadline=build.deadline, poll_ns=build.poll_ns,
                    rank=rank, tctx=tctx, octx=octx)


def init_ctx(ctx: Optional[GuardCtx], rank=0) -> None:
    """Write the header and zero the cursor (SMEM is NOT
    zero-initialized — decode trusts only rows the header counts)."""
    if ctx is None:
        return
    ctx.rank = rank
    ctx.cur[0] = 0
    ctx.cur[1] = 0
    ctx.buf[0, 0] = GMAGIC
    ctx.buf[0, 1] = 0
    ctx.buf[0, 2] = ctx.cap
    ctx.buf[0, 3] = jnp.asarray(rank, jnp.int32)
    ctx.buf[0, 4] = ctx.deadline
    ctx.buf[0, 5] = 0
    ctx.buf[0, 6] = 0
    ctx.buf[0, 7] = 0


# The trace-time attach stack: shmem primitives (signal_wait_until,
# barrier waits, PutHandle.wait_recv) consult `current()` so kernels
# instrument every wait by attaching ONE ctx around their body trace.
_CTX_STATE = threading.local()


def current() -> Optional[GuardCtx]:
    stack = getattr(_CTX_STATE, "stack", None)
    return stack[-1] if stack else None


@contextlib.contextmanager
def attached(ctx: Optional[GuardCtx]):
    """Make `ctx` the ambient guard context while the kernel body
    traces (None attaches nothing — the zero-cost-off path)."""
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


def set_progress(value, ctx: Optional[GuardCtx] = None) -> None:
    """Record the kernel's progress counter (ring step, chunk index);
    trips report the value current at the time of the trip."""
    ctx = ctx or current()
    if ctx is None:
        return
    ctx.cur[1] = jnp.asarray(value, jnp.int32)


def _clamp_i32(v):
    if isinstance(v, int):
        return jnp.asarray(min(v, 2**31 - 1), jnp.int32)
    return jnp.asarray(v).astype(jnp.int32)


def _trip_store(ctx: GuardCtx, site: int, slot, expected, observed):
    """Append one trip row (saturating, header counts all trips)."""
    idx = ctx.cur[0]

    @pl.when(idx < ctx.cap)
    def _write():
        r = idx + 1
        ctx.buf[r, 0] = jnp.asarray(site, jnp.int32)
        ctx.buf[r, 1] = jnp.asarray(slot, jnp.int32)
        ctx.buf[r, 2] = ctx.cur[1]
        ctx.buf[r, 3] = _clamp_i32(expected)
        ctx.buf[r, 4] = _clamp_i32(observed)
        ctx.buf[r, 5] = jnp.asarray(ctx.rank, jnp.int32)
        ctx.buf[r, 6] = idx
        ctx.buf[r, 7] = 0

    ctx.cur[0] = idx + 1
    ctx.buf[0, 1] = idx + 1
    if ctx.tctx is not None:
        from triton_dist_tpu.trace import events as trace_ev

        trace_ev.instant(ctx.tctx, trace_ev.REGIONS["guard.trip"],
                         payload=site, aux=slot)
    # coexisting obs build: the trip also lands in the O(1) stat row
    # (explicitly wired octx, or the ambient meter of attached-style
    # kernels). When the trace instant above fired too, mirror its tick
    # so the meter clock stays in lockstep with the trace cursor.
    from triton_dist_tpu.obs import stats as _obs_stats

    octx = ctx.octx if ctx.octx is not None else _obs_stats.current()
    if octx is not None:
        octx.add_trip()
        if ctx.tctx is not None:
            octx.tick()


# -- the watchdog -------------------------------------------------------------

# The shipped watchdog vs the seeded-bad variants the chaos harness must
# distinguish (tests/_mutants.py "guard_reset_poll": a watchdog that
# resets its poll counter on every re-read never reaches its deadline —
# it never trips on a real deadlock, the exact polarity bug a guard
# plane can silently rot into).
_IMPL_STATE = threading.local()


def watchdog_impl() -> str:
    return getattr(_IMPL_STATE, "impl", "shipped")


@contextlib.contextmanager
def _watchdog_override(impl: str):
    """TEST-ONLY: swap the watchdog implementation ("shipped" |
    "reset_poll") for kernels traced inside the block."""
    prev = getattr(_IMPL_STATE, "impl", "shipped")
    _IMPL_STATE.impl = impl
    try:
        yield
    finally:
        _IMPL_STATE.impl = prev


def _satisfied(sem, amount, deadline, poll_ns=10_000):
    """Bounded-poll readiness: up to `deadline` re-reads with a
    `poll_ns` pl.delay between them, so the budget is wall-time-shaped
    (~deadline * poll_ns) and exits early once satisfied — a raw
    back-to-back re-read loop would burn its budget in microseconds and
    trip on benign delivery latency."""
    amt = jnp.asarray(amount, jnp.int32)
    if watchdog_impl() == "reset_poll":
        # MUTANT: the poll budget "resets" on every re-read, so the
        # deadline is never reached — modeled as a wait that always
        # declares success and consumes blindly (on hardware this is
        # the spin that never gives up).
        return jnp.asarray(True)

    def cond(carry):
        it, ok = carry
        return jnp.logical_and(it < deadline, jnp.logical_not(ok))

    def body(carry):
        it, _ok = carry
        pl.delay(poll_ns)
        return it + 1, _sem_read(sem) >= amt

    _, ok = jax.lax.while_loop(
        cond, body, (jnp.int32(0), _sem_read(sem) >= amt))
    return ok


def watchdog_wait(consume, sem, amount, site: str, slot=0,
                  ctx: Optional[GuardCtx] = None) -> None:
    """Guarded wait: `consume()` performs the real (blocking,
    decrementing) wait; `sem` is a readable view of the semaphore it
    consumes and `amount` the satisfaction threshold. No ambient ctx ->
    plain consume (zero cost off)."""
    ctx = ctx or current()
    if ctx is None:
        consume()
        return
    sid = SITES[site]
    ok = _satisfied(sem, amount, ctx.deadline, ctx.poll_ns)

    @pl.when(ok)
    def _consume():
        consume()

    @pl.when(jnp.logical_not(ok))
    def _tripped():
        _trip_store(ctx, sid, slot, amount, _sem_read(sem))


def stream_trip(gbuf, ok, site: str = "wire", slot=0, rank=-1):
    """Host/jit-level analog of `integrity_trip` for entry points whose
    consume edge runs OUTSIDE the kernel (e.g. the LL-AG decode):
    append one trip row to a guard STREAM (a guard buffer as a value)
    when `ok` is False; returns the updated stream. Pure jnp."""
    ok = jnp.asarray(ok)
    idx = gbuf[0, 1]
    cap = gbuf.shape[0] - 1
    row = jnp.stack([
        jnp.asarray(SITES[site], jnp.int32), jnp.asarray(slot, jnp.int32),
        jnp.zeros((), jnp.int32), jnp.ones((), jnp.int32),
        jnp.zeros((), jnp.int32), jnp.asarray(rank, jnp.int32),
        idx, jnp.zeros((), jnp.int32),
    ])
    at = jnp.where(idx < cap, idx + 1, cap)
    cur = jax.lax.dynamic_slice(gbuf, (at, 0), (1, GUARD_WORDS))
    new = jnp.where(jnp.logical_or(ok, idx >= cap), cur, row[None])
    out = jax.lax.dynamic_update_slice(gbuf, new, (at, 0))
    return out.at[0, 1].set(jnp.where(ok, idx, idx + 1))


def integrity_trip(ok, site: str = "wire", slot=0,
                   ctx: Optional[GuardCtx] = None) -> None:
    """Record a wire-integrity failure (`ok` is the consume edge's
    checksum verdict) as a guard row. No ambient ctx -> no-op."""
    ctx = ctx or current()
    if ctx is None:
        return

    @pl.when(jnp.logical_not(jnp.asarray(ok)))
    def _tripped():
        _trip_store(ctx, SITES[site], slot, 1, 0)


# -- host-side decode / raise -------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GuardTrip:
    rank: int
    site: int
    slot: int
    progress: int
    expected: int
    observed: int
    seq: int

    @property
    def site_label(self) -> str:
        return site_name(self.site)

    def __str__(self):
        return (f"rank {self.rank}: {self.site_label} wait tripped "
                f"(slot={self.slot}, progress={self.progress}, "
                f"expected>={self.expected}, observed={self.observed})")


def decode(buf) -> List[GuardTrip]:
    """Decode guard buffer(s) — any array whose trailing dims are
    (1+cap, GUARD_WORDS); leading dims (ranks, legs, ...) flatten."""
    import numpy as np

    a = np.asarray(buf)
    if a.shape[-1] != GUARD_WORDS or a.ndim < 2:
        raise ValueError(f"not a guard buffer: shape {a.shape}")
    flat = a.reshape(-1, a.shape[-2], GUARD_WORDS)
    trips: List[GuardTrip] = []
    for b in flat:
        if int(b[0, 0]) != GMAGIC:
            raise ValueError(
                f"guard buffer header magic {int(b[0, 0]):#x} != "
                f"{GMAGIC:#x} (uninitialized or clobbered)")
        count = min(int(b[0, 1]), int(b[0, 2]))
        for r in range(1, 1 + count):
            trips.append(GuardTrip(
                rank=int(b[r, 5]), site=int(b[r, 0]), slot=int(b[r, 1]),
                progress=int(b[r, 2]), expected=int(b[r, 3]),
                observed=int(b[r, 4]), seq=int(b[r, 6])))
    return trips


def check(*bufs, context: str = "") -> None:
    """Decode and raise when any watchdog tripped — THE host-side
    consume edge of the guard contract. Trips that are ALL wire-
    integrity rows raise `WireIntegrityError` (payload corrupted, not a
    deadline); any deadline-class trip raises `DeadlineExceeded`."""
    trips: List[GuardTrip] = []
    for b in bufs:
        if b is not None:
            trips.extend(decode(b))
    if not trips:
        return
    head = f"{context}: " if context else ""
    lines = "; ".join(str(t) for t in trips[:6])
    more = f" (+{len(trips) - 6} more)" if len(trips) > 6 else ""
    if all(t.site == SITES["wire"] for t in trips):
        from triton_dist_tpu.faults.errors import WireIntegrityError

        raise WireIntegrityError(
            f"{head}{len(trips)} wire-integrity guard row(s): "
            f"{lines}{more}")
    raise DeadlineExceeded(
        f"{head}{len(trips)} guard watchdog trip(s): {lines}{more}",
        trips=trips)


# -- degradation registry -----------------------------------------------------

_DEGRADED: set = set()
_DEG_LOCK = threading.Lock()


def degrade(name: str) -> None:
    """Mark protocol `name` degraded: entry points called with
    fallback="xla" route to their XLA-collective path until reset."""
    with _DEG_LOCK:
        _DEGRADED.add(name)


def is_degraded(name: str) -> bool:
    with _DEG_LOCK:
        return name in _DEGRADED


def degraded() -> set:
    with _DEG_LOCK:
        return set(_DEGRADED)


def reset_degraded() -> None:
    with _DEG_LOCK:
        _DEGRADED.clear()
