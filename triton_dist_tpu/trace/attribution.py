"""Stall attribution: classify traced time and diff it against the
scheduler's predictions.

Classification (events.REGION_CLASS): every span region maps to one of

  compute   — MXU/VPU work (megakernel task bodies, GEMM+RS partials,
              per-chunk grouped FFN marks)
  sem_wait  — waiting on a semaphore another agent must signal (chunk
              delivery waits, ring-step recv waits, credit waits,
              scoreboard waits)
  dma_wait  — waiting on this core's own DMA queue (A-tile loads,
              local-segment copies)
  idle      — traced wall not covered by any span (scheduling gaps,
              untraced prologue)

All totals are in the timeline's clock units (vticks on the
deterministic interpret clock; cycles once a hardware stamp is wired —
see trace/events.py). Fractions, not absolute units, are what the
measured-vs-predicted comparisons assert.

`a2a_step_waits` is the delivery-replay reconstruction: receiver q's
wait for ring step i, chunk c gates on the SENDER-side "a2a.send"
instant of rank (q - i) mod n — the event that carries injected skew on
the lockstep interpreter (see trace/collect.py module doc). On hardware
the receiver-side wait spans measure the same quantity directly; the
replay is the clock-agnostic formulation.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from triton_dist_tpu.trace import events as ev
from triton_dist_tpu.trace.collect import Timeline

CLASSES = ("compute", "sem_wait", "dma_wait", "idle")


def classify(tl: Timeline) -> Dict[tuple, Dict[str, float]]:
    """Per (stream, rank, lane): time per attribution class + total.
    idle = traced extent not covered by classified spans (clamped at 0:
    nested spans may legitimately overlap)."""
    out: Dict[tuple, Dict[str, float]] = {}
    extent: Dict[tuple, list] = {}
    for e in tl.events:
        key = (e.stream, e.rank, e.lane)
        lo_hi = extent.setdefault(key, [e.t, e.t])
        lo_hi[0] = min(lo_hi[0], e.t)
        lo_hi[1] = max(lo_hi[1], e.t)
        out.setdefault(key, {c: 0.0 for c in CLASSES})
    for s in tl.spans:
        cls = ev.REGION_CLASS.get(ev.region_name(s.region))
        if cls is None:
            continue
        out[(s.stream, s.rank, s.lane)][cls] += s.dur
    for key, d in out.items():
        lo, hi = extent[key]
        d["total"] = hi - lo
        covered = d["compute"] + d["sem_wait"] + d["dma_wait"]
        d["idle"] = max(0.0, d["total"] - covered)
    return out


def per_region(tl: Timeline) -> Dict[tuple, Dict[str, float]]:
    """Per (stream, region name): total span time + span count +
    instant count — the per-region totals of the attribution table."""
    out: Dict[tuple, Dict[str, float]] = {}
    for s in tl.spans:
        d = out.setdefault((s.stream, ev.region_name(s.region)),
                           {"time": 0.0, "spans": 0, "instants": 0})
        d["time"] += s.dur
        d["spans"] += 1
    for e in tl.events:
        if e.kind == ev.KIND_INSTANT:
            d = out.setdefault((e.stream, ev.region_name(e.region)),
                               {"time": 0.0, "spans": 0, "instants": 0})
            d["instants"] += 1
    return out


def format_table(tl: Timeline) -> str:
    """The attribution table examples/scripts print: per-stream class
    fractions plus the per-region totals."""
    lines = []
    cls = classify(tl)
    by_stream: Dict[str, Dict[str, float]] = {}
    for (stream, _r, _l), d in cls.items():
        agg = by_stream.setdefault(
            stream, {c: 0.0 for c in CLASSES} | {"total": 0.0})
        for k in list(agg):
            agg[k] += d[k]
    lines.append(f"{'stream':<20} {'compute':>9} {'sem_wait':>9} "
                 f"{'dma_wait':>9} {'idle':>9}")
    for stream in sorted(by_stream):
        d = by_stream[stream]
        tot = max(d["total"], 1e-9)
        lines.append(
            f"{stream:<20} "
            + " ".join(f"{d[c] / tot:>8.1%}" for c in CLASSES))
    lines.append("")
    lines.append(f"{'stream/region':<28} {'time':>10} {'spans':>7} "
                 f"{'instants':>9}")
    for (stream, region), d in sorted(per_region(tl).items()):
        lines.append(f"{stream + '/' + region:<28} {d['time']:>10.0f} "
                     f"{d['spans']:>7} {d['instants']:>9}")
    return "\n".join(lines)


# -- chunked-A2A delivery replay ---------------------------------------------


def _delivery_replay(tl: Timeline, stream: str, send_region: str,
                     wait_region: str) -> Dict[int, np.ndarray]:
    """Shared delivery-wait reconstruction over a (send instant, wait
    span) region pair whose payload is the ring step / source offset:
    for each receiver-side wait span (payload=i, aux=sub-unit), arrival
    is the matching sender-side send instant on rank (q - i) mod n; the
    consumer cursor advances through max(ready, arrival), and the
    blocked amount accrues to offset i. Offset 0 (the local segment)
    never waits on a peer and reports 0. Used by the chunked-A2A replay
    and the flash-prefill per-segment replay."""
    ranks = tl.ranks(stream)
    n = len(ranks)
    if n == 0:
        return {}
    sends: Dict[tuple, float] = {}
    for e in tl.events:
        if (e.stream == stream and e.kind == ev.KIND_INSTANT
                and e.region == ev.REGIONS[send_region]):
            sends[(e.rank, e.payload, e.aux)] = e.t
    out: Dict[int, np.ndarray] = {}
    for q in ranks:
        waits = np.zeros(n, np.float64)
        cursor = 0.0
        spans = sorted(
            tl.spans_of(stream, rank=q, region=wait_region),
            key=lambda s: s.t0,
        )
        for s in spans:
            i, c = s.payload, s.aux
            if i == 0:
                continue
            src = ranks[(ranks.index(q) - i) % n]
            arrival = sends.get((src, i, c))
            if arrival is None:
                continue
            start = max(cursor, s.t0)
            waits[i] += max(0.0, arrival - start)
            cursor = max(start, arrival)
        out[q] = waits
    return out


def a2a_step_waits(tl: Timeline, stream: str) -> Dict[int, np.ndarray]:
    """Per receiver rank: reconstructed chunked-A2A delivery wait per
    ring step (see _delivery_replay; payload=step, aux=chunk)."""
    return _delivery_replay(tl, stream, "a2a.send", "a2a.wait")


def fp_seg_waits(tl: Timeline, stream: str) -> Dict[int, np.ndarray]:
    """Per receiver rank: reconstructed flash-prefill per-SEGMENT
    delivery wait (payload=source offset) — where prefill time goes
    when a producer straggles (see _delivery_replay; the SP flash
    kernel's fp.send/fp.wait records, kernels/flash_prefill.py)."""
    return _delivery_replay(tl, stream, "fp.send", "fp.wait")


# -- megakernel measured-vs-predicted ----------------------------------------


def wire_send_bytes(tl: Timeline, stream: str, region: str,
                    bytes_per_event: int) -> Dict[int, int]:
    """Per-rank WIRE bytes attributed to one transport region: the
    count of that region's records (spans and instants both — kernels
    mark sends as instants, delivery waits as spans) priced at
    `bytes_per_event`. With bytes_per_event =
    `wire.wire_row_bytes(h, fmt, dtype) * rows_per_transfer`, this is
    the per-format byte ledger of a transport leg: the SAME traced
    kernel run under native vs fp8 wire attributes bytes in exactly the
    packed ratio (the protocol — and therefore the event count — is
    format-invariant; only the per-event byte price moves). Returns
    {rank: bytes}."""
    rid = ev.region_id(region)
    out: Dict[int, int] = {}
    for s in tl.spans:
        if s.stream == stream and s.region == rid:
            out[s.rank] = out.get(s.rank, 0) + int(bytes_per_event)
    for e in tl.events:
        if (e.stream == stream and e.region == rid
                and e.kind == ev.KIND_INSTANT):
            out[e.rank] = out.get(e.rank, 0) + int(bytes_per_event)
    return out


def compare_predicted(sched, tl: Timeline, stream: str = "mega",
                      graph=None, tol: float = 0.1,
                      check: bool = True) -> List[dict]:
    """Diff the megakernel trace against the schedule, queue by queue.

    Structural checks (exact, any clock): every queue's traced task
    count equals its scheduled length, and tasks ran in queue order
    (aux carries the queue position).

    Stall check: measured scoreboard-wait fraction — sum of
    "mega.sb_wait" span time over (that + task-span time) per queue —
    must agree with the cost model's `sched.stall` fraction within
    `tol` (fractions, because the trace clock's units are ticks/cycles
    while `predicted_stalls` is in cost-model time; `graph` supplies
    the per-task costs for the predicted busy term and is required only
    when predicted stall is nonzero, i.e. multi-queue schedules). On
    the deterministic interpret clock a single-queue schedule measures
    exactly 0 == predicts exactly 0.

    Every rank executes the same schedule, so the comparison runs per
    (rank, queue) — one report row each; raises AssertionError on
    disagreement when `check`."""
    queues = sched.queues
    stall_pred = np.asarray(
        sched.stall if sched.stall is not None
        else np.zeros(len(queues)), np.float64)
    report: List[dict] = []
    for rank in (tl.ranks(stream) or [None]):
        for c, q in enumerate(queues):
            spans = tl.spans_of(stream, rank=rank, lane=c,
                                region="mega.task")
            spans.sort(key=lambda s: s.t0)
            busy = sum(s.dur for s in spans)
            sb = sum(s.dur for s in tl.spans_of(stream, rank=rank,
                                                lane=c,
                                                region="mega.sb_wait"))
            order_ok = all(s.aux < s2.aux
                           for s, s2 in zip(spans, spans[1:]))
            m_frac = sb / (sb + busy) if (sb + busy) > 0 else 0.0
            if graph is not None:
                busy_pred = float(sum(graph.tasks[t].cost for t in q))
            else:
                busy_pred = None
            if busy_pred is not None and stall_pred[c] + busy_pred > 0:
                p_frac = float(stall_pred[c]) / (stall_pred[c]
                                                 + busy_pred)
            else:
                # no graph (or an all-zero-cost queue): only a zero
                # prediction can be stated without the busy term
                p_frac = 0.0 if stall_pred[c] == 0 else None
            row = {
                "rank": rank,
                "queue": c,
                "n_tasks_scheduled": len(q),
                "n_tasks_traced": len(spans),
                "order_ok": order_ok,
                "measured_busy": busy,
                "measured_stall": sb,
                "measured_stall_frac": m_frac,
                "predicted_stall": float(stall_pred[c]),
                "predicted_stall_frac": p_frac,
            }
            report.append(row)
            if check:
                who = f"rank {rank} queue {c}"
                assert len(spans) == len(q), (
                    f"{who}: traced {len(spans)} task spans, schedule "
                    f"has {len(q)} — the trace does not cover the queue")
                assert order_ok, f"{who}: tasks traced out of order"
                assert p_frac is not None, (
                    f"{who}: predicted stall {stall_pred[c]} != 0 needs "
                    "`graph` for the predicted busy term")
                assert abs(m_frac - p_frac) <= tol, (
                    f"{who}: measured stall fraction {m_frac:.3f} vs "
                    f"predicted {p_frac:.3f} beyond tol {tol}")
    return report


def task_time_by_branch(tl: Timeline, branch_keys=None,
                        stream: str = "mega") -> Dict[object, dict]:
    """Per-branch megakernel task-time buckets: "mega.task" spans carry
    payload=branch id, so the traced step decomposes into matmul vs
    attention vs AR vs elementwise time — the first cut of the world=1
    ledger workflow (docs/performance.md): a latency regression names
    its branch before anyone stares at a timeline.

    branch_keys (CompiledMega.branch_keys) maps ids to keys; without it
    the buckets key on the raw branch id. Returns
    {key_or_id: {"time": total span time, "count": spans}}."""
    out: Dict[object, dict] = {}
    for s in tl.spans:
        if s.stream != stream or ev.region_name(s.region) != "mega.task":
            continue
        key = s.payload
        if branch_keys is not None and 0 <= s.payload < len(branch_keys):
            key = branch_keys[s.payload]
        d = out.setdefault(key, {"time": 0.0, "count": 0})
        d["time"] += s.dur
        d["count"] += 1
    return out


def prefetch_hit_rate(tl: Timeline,
                      stream: str = "mega") -> Optional[float]:
    """Fraction of prefetch-arena consumes that hit (payload > 0) among
    all "mega.pf" instants; None when the trace has none."""
    hits = total = 0
    for e in tl.events:
        if (e.stream == stream and e.kind == ev.KIND_INSTANT
                and e.region == ev.REGIONS["mega.pf"]):
            total += 1
            hits += 1 if e.payload > 0 else 0
    return (hits / total) if total else None


def guard_trips(tl: Timeline) -> List[dict]:
    """The guard-trip instants of a timeline (kernels built under BOTH
    trace.building and faults.guard.building emit one per watchdog
    trip): [{rank, site, slot, t}] rows, the trace-side view of the
    guard rows the host raised on — every recovery the degradation
    ladder performs is attributable next to the stalls that caused it
    (docs/robustness.md)."""
    from triton_dist_tpu.faults.guard import site_name

    rid = ev.REGIONS["guard.trip"]
    return [
        {"rank": e.rank, "site": site_name(e.payload), "slot": e.aux,
         "t": e.t}
        for e in tl.events
        if e.region == rid and e.kind == ev.KIND_INSTANT
    ]
