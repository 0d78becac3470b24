"""Host-side trace assembly: device buffers -> a merged Timeline.

The device side (trace/events.py) hands back fixed-capacity i32 buffers
of (region, kind, seq, payload) records on the deterministic seq clock.
This module decodes them, derives spans, and assigns every record a
VIRTUAL TIME:

    vtime(record) = seq + sum of straggle payloads of earlier records

i.e. one tick per record, plus any injected skew (shmem.straggler_delay
provocations ride along as a "straggle" instant whose payload is the
delay on the delayed rank and 0 elsewhere — emitted on EVERY rank so
record sequences stay aligned across ranks). On the lockstep CPU
interpreter this is the honest clock: the discharge model executes the
mesh as synchronous rendezvous waves, so per-rank wall time carries no
per-source information — but the PROTOCOL events (which chunk was sent
when, relative to the injected skew) are real, and replaying them
(attribution.a2a_step_waits) reproduces exactly the per-step waits a
delivery-granular consumer would observe. On hardware, the same
pipeline runs on real stamps once TraceCtx.stamp is wired to a cycle
counter (events.py clock notes).

Wall-clock anchoring: TraceSession.host_span records python-level
perf_counter_ns spans around the traced calls (into an obs.spans.SpanLog
of the session's own); export.to_chrome_trace
places device streams at their host anchors so the Perfetto view lines
up with real time (per-region host timing — the documented compiled-
mode reconstruction).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from triton_dist_tpu.obs.spans import SpanLog
from triton_dist_tpu.trace import events as ev


@dataclasses.dataclass(frozen=True)
class Event:
    stream: str
    rank: int
    lane: int
    region: int
    kind: int
    seq: int
    payload: int
    aux: int
    t: float  # vticks (seq clock) — see module doc


@dataclasses.dataclass(frozen=True)
class Span:
    stream: str
    rank: int
    lane: int
    region: int
    payload: int
    aux: int
    t0: float
    t1: float

    @property
    def dur(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass
class Timeline:
    events: List[Event]
    spans: List[Span]
    drops: Dict[Tuple[str, int, int], int]  # (stream, rank, lane) -> n
    host_spans: List[Tuple[str, int, int]]  # (name, t0_ns, t1_ns)
    label: str = "trace"
    plan_id: Optional[str] = None  # fusion plan provenance (plan.Plan)

    def streams(self):
        return sorted({e.stream for e in self.events})

    def ranks(self, stream: str):
        return sorted({e.rank for e in self.events if e.stream == stream})

    def select(self, stream: str, rank: Optional[int] = None,
               lane: Optional[int] = None) -> List[Event]:
        return [e for e in self.events
                if e.stream == stream
                and (rank is None or e.rank == rank)
                and (lane is None or e.lane == lane)]

    def spans_of(self, stream: str, rank: Optional[int] = None,
                 lane: Optional[int] = None,
                 region: Optional[str] = None) -> List[Span]:
        rid = ev.REGIONS[region] if isinstance(region, str) else region
        return [s for s in self.spans
                if s.stream == stream
                and (rank is None or s.rank == rank)
                and (lane is None or s.lane == lane)
                and (rid is None or s.region == rid)]


class MalformedTrace(ValueError):
    """A buffer without the header magic / an unpairable BEGIN-END
    structure. scripts/trace_report.py turns this into exit 1."""


def _decode_buffer(stream: str, buf: np.ndarray) -> Tuple[List[Event],
                                                          int]:
    """One (1+cap, WORDS) buffer -> (events in seq order, n_dropped)."""
    hdr = buf[0]
    if int(hdr[0]) != ev.MAGIC:
        raise MalformedTrace(
            f"stream {stream!r}: header magic {int(hdr[0]):#x} != "
            f"{ev.MAGIC:#x} (not a trace buffer, or clobbered)")
    count, cap = int(hdr[1]), int(hdr[2])
    rank, lane = int(hdr[3]), int(hdr[4])
    kept = min(count, cap)
    out: List[Event] = []
    delay = 0.0
    for r in range(1, 1 + kept):
        region, kind, seq, payload, _tl, _th, aux, _ = (
            int(x) for x in buf[r])
        t = float(seq) + delay
        if region == ev.REGIONS["straggle"]:
            delay += float(payload)
        out.append(Event(stream, rank, lane, region, kind, seq, payload,
                         aux, t))
    return out, max(0, count - cap)


def _pair_spans(events: List[Event],
                allow_unclosed: bool = False) -> List[Span]:
    """Match BEGIN/END within one buffer by (region, payload, aux) —
    span identity is carried on both records, so nesting of DIFFERENT
    spans is free and same-key spans pair LIFO. An unclosed BEGIN is
    malformed unless `allow_unclosed` (its END was dropped past the
    buffer cap — the saturating-drop casualty)."""
    stacks: Dict[tuple, List[Event]] = {}
    spans: List[Span] = []
    for e in events:
        key = (e.region, e.payload, e.aux)
        if e.kind == ev.KIND_BEGIN:
            stacks.setdefault(key, []).append(e)
        elif e.kind == ev.KIND_END:
            st = stacks.get(key)
            if not st:
                raise MalformedTrace(
                    f"stream {e.stream!r} rank {e.rank}: END without "
                    f"BEGIN for region {ev.region_name(e.region)} "
                    f"payload={e.payload} aux={e.aux} at seq {e.seq}")
            b = st.pop()
            spans.append(Span(e.stream, e.rank, e.lane, e.region,
                              e.payload, e.aux, b.t, e.t))
    if not allow_unclosed:
        for key, st in stacks.items():
            if st:
                e = st[0]
                raise MalformedTrace(
                    f"stream {e.stream!r} rank {e.rank}: BEGIN without "
                    f"END for region {ev.region_name(e.region)} "
                    f"payload={e.payload} (and no drops to explain it)")
    return spans


def assemble(buffers: Dict[str, np.ndarray],
             label: str = "trace",
             host_spans=None,
             plan_id: Optional[str] = None) -> Timeline:
    """Build a Timeline from {stream: buffer array}. Each value may be
    one buffer (1+cap, WORDS), a stack (k, 1+cap, WORDS) — e.g. the
    shard_map-stacked per-rank outputs — or any higher-rank stack, which
    is flattened over the leading dims. Buffers whose header count is 0
    are kept (empty streams are legal); a missing magic raises
    MalformedTrace."""
    all_events: List[Event] = []
    all_spans: List[Span] = []
    drops: Dict[Tuple[str, int, int], int] = {}
    for stream, arr in buffers.items():
        a = np.asarray(arr)
        if a.ndim < 2 or a.shape[-1] != ev.RECORD_WORDS:
            raise MalformedTrace(
                f"stream {stream!r}: shape {a.shape} is not a record "
                f"buffer (minor dim must be {ev.RECORD_WORDS})")
        flat = a.reshape(-1, a.shape[-2], a.shape[-1]) if a.ndim > 2 \
            else a[None]
        for b in flat:
            evs, dropped = _decode_buffer(stream, b)
            spans = _pair_spans(evs, allow_unclosed=dropped > 0)
            all_events.extend(evs)
            all_spans.extend(spans)
            if evs or dropped:
                key = (stream, evs[0].rank if evs else -1,
                       evs[0].lane if evs else 0)
                drops[key] = drops.get(key, 0) + dropped
    all_events.sort(key=lambda e: (e.stream, e.rank, e.lane, e.seq))
    if plan_id is None:
        # the plan noted by the forward traced under this build, if any
        plan_id = ev.last_plan()
    return Timeline(all_events, all_spans, drops,
                    list(host_spans or []), label=label,
                    plan_id=plan_id)


class TraceSession:
    """Host-side bookkeeping around traced calls: python-level wall
    spans (the per-region host timing that anchors device streams in the
    export) and a one-stop assemble."""

    def __init__(self, label: str = "trace"):
        self.label = label
        self.log = SpanLog()

    def host_span(self, name: str):
        return self.log.span(name)

    def assemble(self, buffers: Dict[str, np.ndarray]) -> Timeline:
        return assemble(buffers, label=self.label,
                        host_spans=self.log.triples())


@contextlib.contextmanager
def tracing(label: str = "trace", cap: int = 512):
    """`with tracing("x") as (build, session):` — enables the device
    build AND opens a host session in one step."""
    with ev.building(cap=cap) as build:
        yield build, TraceSession(label)
