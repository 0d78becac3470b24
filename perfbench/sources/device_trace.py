"""Reduction of a profiler trace to what the per-layer metrics read.

`load_xplane` turns the profiler's `.xplane.pb` into a plain structure
(`Trace`), `Trace.to_json` / `from_json` keep a small recorded one as
a fixture, and every reduction below works on that structure, so the
same code reads a fixture in tier-1 and a chip trace in a run.

A device plane's "XLA Ops" line holds one event per executed HLO
operation, containers (`while`, called computations) included: busy
time is the UNION of the intervals, and an operation's own time is its
duration less what its children cover.
"""

from __future__ import annotations

import dataclasses
import fnmatch
import json
import re
from typing import Dict, List, Tuple

Event = Tuple[str, float, float]  # name, start_s, end_s

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "perfbench."


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]       # device plane -> op events
    modules: Dict[str, List[Event]]   # device plane -> program runs
    host: List[Event]                 # the harness's own host spans

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @staticmethod
    def from_json(text: str) -> "Trace":
        d = json.loads(text)
        fix = lambda evs: [(n, float(a), float(b)) for n, a, b in evs]  # noqa: E731
        return Trace({k: fix(v) for k, v in d["ops"].items()},
                     {k: fix(v) for k, v in d["modules"].items()},
                     fix(d["host"]))


_HLO = re.compile(r"^%?(?P<name>[^\s=]+)\s*=\s*(?P<rest>.*)$", re.S)
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def short_name(text: str) -> str:
    """An operation's event carries its whole HLO instruction; keep the
    instruction's name and the shape it produces
    (`fusion.193 bf16[1024,12288]`, `_fp_local_kernel.6 bf16[8,128,4096]`)."""
    m = _HLO.match(text)
    if not m:
        return text[:80]
    shape = _SHAPE.search(m.group("rest")[:200])
    return m.group("name") + (" " + shape.group(0) if shape else "")


def load_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in data.planes:
        name = plane.name
        if name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                is_ops = line.name == OPS_LINE
                evs = [(short_name(e.name) if is_ops else e.name,
                        e.start_ns / 1e9,
                        (e.start_ns + e.duration_ns) / 1e9)
                       for e in line.events]
                (ops if is_ops else modules)[name] = evs
        elif name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend(
                    (e.name, e.start_ns / 1e9,
                     (e.start_ns + e.duration_ns) / 1e9)
                    for e in line.events
                    if e.name.startswith(HOST_PREFIX))
    host.sort(key=lambda e: e[1])
    return Trace(ops, modules, host)


def union(events: List[Event]) -> List[Tuple[float, float]]:
    """Merged busy intervals of `events`."""
    out: List[List[float]] = []
    for _n, a, b in sorted(events, key=lambda e: e[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_seconds(events: List[Event]) -> float:
    return sum(b - a for a, b in union(events))


def window_of(trace: Trace) -> Tuple[float, float]:
    """The traced window: from the first to the last device event."""
    evs = [e for v in trace.ops.values() for e in v]
    if not evs:
        return (0.0, 0.0)
    return (min(e[1] for e in evs), max(e[2] for e in evs))


def busy_and_window(trace: Trace) -> Tuple[float, float]:
    """(busy seconds averaged over the device planes, window seconds)."""
    if not trace.ops:
        return (0.0, 0.0)
    a, b = window_of(trace)
    busy = [busy_seconds(v) for v in trace.ops.values()]
    return (sum(busy) / len(busy), b - a)


def self_times(events: List[Event]) -> Dict[str, float]:
    """Own seconds by operation name: duration less the children's."""
    out: Dict[str, float] = {}
    stack: List[List] = []  # [name, end, own]

    def close():
        name, _end, own = stack.pop()
        out[name] = out.get(name, 0.0) + max(own, 0.0)

    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            close()
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([name, b, b - a])
    while stack:
        close()
    return out


def matching_seconds(events: List[Event], patterns: List[str]) -> float:
    """Seconds in events whose name matches one of the glob patterns
    (kernels are leaves, so durations simply add)."""
    return sum(b - a for n, a, b in events
               if any(fnmatch.fnmatchcase(n, p) for p in patterns))


def per_device_matching(trace: Trace, patterns: List[str]) -> List[float]:
    return [matching_seconds(v, patterns) for v in trace.ops.values()]


def module_runs(trace: Trace) -> int:
    """Executions of the STEP program in the trace, on the first device
    plane: the program that holds most of the device's time (the host
    also launches small programs of its own between steps)."""
    if not trace.modules:
        return 0
    total: Dict[str, float] = {}
    count: Dict[str, int] = {}
    for name, a, b in next(iter(trace.modules.values())):
        total[name] = total.get(name, 0.0) + (b - a)
        count[name] = count.get(name, 0) + 1
    return count[max(total, key=total.get)] if total else 0


def top_ops(trace: Trace, k: int = 10) -> List[list]:
    """The k operations with most own time, averaged over the chips."""
    total: Dict[str, float] = {}
    for evs in trace.ops.values():
        for name, s in self_times(evs).items():
            total[name] = total.get(name, 0.0) + s / len(trace.ops)
    return [[n, s] for n, s in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:k]]


def idle_gaps(trace: Trace, k: int = 10) -> List[list]:
    """Idle seconds of the first device plane, by the innermost harness
    host span that covers each gap's middle ("outside any span" where
    none does), the k largest totals."""
    if not trace.ops:
        return []
    evs = next(iter(trace.ops.values()))
    merged = union(evs)
    total: Dict[str, float] = {}
    for (_a0, b0), (a1, _b1) in zip(merged, merged[1:]):
        mid = (b0 + a1) / 2
        inside = [h for h in trace.host if h[1] <= mid <= h[2]]
        name = (min(inside, key=lambda h: h[2] - h[1])[0][len(HOST_PREFIX):]
                if inside else "outside any span")
        total[name] = total.get(name, 0.0) + (a1 - b0)
    return [[n, s] for n, s in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:k]]
