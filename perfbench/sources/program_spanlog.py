"""What the program's span log says (`triton_dist_tpu.obs.spans`): the
phases of every scheduling round and of the worker's step, written
inside the program on `perf_counter_ns`. The harness frees the
scheduler before a reader runs; the program keeps the log of the
scheduler built last (`default_log()`), and a run builds one. A
program without the log (a commit before it) reads as nothing.

The log's clock is tied to the profiler trace's through what the
harness records already: its `perfbench.worker_step` annotation opens
a few microseconds before the program's `worker.step` span of the same
step. Read by per-layer metrics only."""

from __future__ import annotations

import bisect
import dataclasses
import statistics
from typing import Dict, List, Optional, Tuple

from perfbench.sources import device_trace

WORKER_STEP_EVENT = device_trace.HOST_PREFIX + "worker_step"
OFFSET_SPREAD_LIMIT_S = 1e-3
OUTSIDE = "outside any span"
# the spans that partition the serving thread's time; request phases,
# retries and windows lie across them and are left out
STRUCTURAL = ("sched.", "worker.")


@dataclasses.dataclass
class Span:
    id: int
    parent: Optional[int]
    name: str
    t0: float  # seconds, perf_counter
    t1: float
    step: Optional[int]
    request: Optional[int]


def program_log():
    """The span log of the run's scheduler, or None where the program
    has none."""
    try:
        from triton_dist_tpu.obs.spans import default_log
    except ImportError:
        return None
    return default_log()


def spans_of(log, t0: float, t1: float) -> List[Span]:
    """The log's records that lie inside [t0, t1], as seconds."""
    out = []
    for r in log.records():
        a, b = r.t0_ns / 1e9, r.t1_ns / 1e9
        if t0 <= a and b <= t1:
            out.append(Span(r.id, r.parent, r.name, a, b, r.step, r.request))
    return out


def self_ms_by_name_per_step(spans: List[Span]) -> Dict[str, Dict[int, float]]:
    """name -> step -> milliseconds of self time: a span's duration
    less what the spans naming it as parent cover, summed over the
    spans of that name in the step."""
    covered: Dict[int, float] = {}
    for s in spans:
        if s.parent is not None:
            covered[s.parent] = covered.get(s.parent, 0.0) + (s.t1 - s.t0)
    out: Dict[str, Dict[int, float]] = {}
    for s in spans:
        if s.step is None:
            continue
        own = 1e3 * (s.t1 - s.t0 - covered.get(s.id, 0.0))
        by_step = out.setdefault(s.name, {})
        by_step[s.step] = by_step.get(s.step, 0.0) + own
    return out


def phase_p50_ms(run, names: List[str]) -> Optional[float]:
    """Median, over the window's steps that have such a span, of the
    self time of the spans named in `names` together; None without a
    log or such spans."""
    log = program_log()
    if log is None:
        return None
    per = self_ms_by_name_per_step(spans_of(log, run.t0, run.t1))
    steps = set().union(*(per.get(n, {}) for n in names))
    if not steps:
        return None
    return statistics.median(
        sum(per.get(n, {}).get(step, 0.0) for n in names) for step in steps)


def clock_offset(trace, spans: List[Span]) -> Optional[Tuple[float, float]]:
    """(offset, spread) in seconds: trace clock minus the log's clock,
    the median over the traced steps of the start of the harness's
    `perfbench.worker_step` event minus the start of the program's
    `worker.step` span of the same step, and the widest disagreement
    among those differences. The trace may hold an event more at
    either end than there are spans (or fewer): the shorter sequence
    is matched to the run of the longer whose differences agree best,
    which a server's uneven periods make the only one that agrees to
    microseconds. None when nothing can be matched or the best
    disagrees by over a millisecond."""
    events = sorted(a for n, a, _b in trace.host if n == WORKER_STEP_EVENT)
    starts = sorted(s.t0 for s in spans if s.name == "worker.step")
    n = min(len(events), len(starts))
    if n < 2:
        return None
    best = None
    for shift in range(max(len(events), len(starts)) - n + 1):
        e0, s0 = (shift, 0) if len(events) > n else (0, shift)
        diffs = [events[e0 + i] - starts[s0 + i] for i in range(n)]
        spread = max(diffs) - min(diffs)
        if best is None or spread < best[1]:
            best = (statistics.median(diffs), spread)
    return best if best[1] <= OFFSET_SPREAD_LIMIT_S else None


def self_intervals(spans: List[Span]) -> List[Tuple[float, float, str]]:
    """The structural spans cut to the stretches no child covers:
    disjoint (start, end, name), sorted — at every instant the
    innermost span."""
    kids: Dict[int, List[Span]] = {}
    for s in spans:
        if s.parent is not None:
            kids.setdefault(s.parent, []).append(s)
    out = []
    for s in spans:
        if not s.name.startswith(STRUCTURAL):
            continue
        at = s.t0
        for k in sorted(kids.get(s.id, []), key=lambda k: k.t0):
            if k.t0 > at:
                out.append((at, k.t0, s.name))
            at = max(at, k.t1)
        if s.t1 > at:
            out.append((at, s.t1, s.name))
    out.sort()
    return out


def idle_by_span(trace, spans: List[Span], offset: float) -> Dict[str, float]:
    """Idle seconds of the first device plane by program span: each
    gap between device operations is SPLIT by its overlap with the
    innermost spans it crosses (`OUTSIDE` for what no span covers).
    `offset` puts the log on the trace's clock."""
    if not trace.ops:
        return {}
    cuts = self_intervals(spans)
    ends = [b + offset for _a, b, _n in cuts]
    merged = device_trace.union(next(iter(trace.ops.values())))
    total: Dict[str, float] = {}
    for (_a0, gap0), (gap1, _b1) in zip(merged, merged[1:]):
        left = gap1 - gap0
        i = bisect.bisect_right(ends, gap0)
        while i < len(cuts) and cuts[i][0] + offset < gap1:
            a, b, name = cuts[i]
            part = min(b + offset, gap1) - max(a + offset, gap0)
            if part > 0:
                total[name] = total.get(name, 0.0) + part
                left -= part
            i += 1
        if left > 0:
            total[OUTSIDE] = total.get(OUTSIDE, 0.0) + left
    return total


def traced_idle_by_span(run) -> Optional[Dict[str, float]]:
    """`idle_by_span` of the run's traced window, the clocks tied by
    `clock_offset`; None without a trace, a log or a tie."""
    log = program_log()
    if log is None or run.trace is None or not run.trace_steps:
        return None
    first, last = run.trace_steps[0].t0, run.trace_steps[-1].t1
    # a step's history stamps lie just outside its worker.step span:
    # this window holds the traced steps' spans and no other step's
    tie = clock_offset(run.trace, spans_of(log, first - 1e-3, last + 1e-3))
    if tie is None:
        run.say("span log: no worker.step spans agree with the trace's "
                "perfbench.worker_step events to a millisecond")
        return None
    offset, spread = tie
    run.say(f"span log: trace clock = perf_counter + {offset:.6f}s, the "
            f"matched steps disagree by {1e6 * spread:.1f}us at most")
    # the gaps at the window's ends reach into the rounds beside it
    spans = spans_of(log, first - 1.0, last + 1.0)
    return idle_by_span(run.trace, spans, offset) or None


def counter_share_pct(run, part: str, whole: List[str]) -> Optional[float]:
    """100 x counter `part` over the sum of the counters `whole`; None
    where the program counts none of them."""
    if part not in run.counters:
        return None
    total = sum(run.counters.get(k, 0) for k in whole)
    return 100.0 * run.counters[part] / total if total > 0 else None
