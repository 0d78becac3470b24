"""What the program's `worker.step` records COUNT
(`triton_dist_tpu.obs.spans`: a record's `counts`, here `width`, the
second dimension of the step's token block and so which compiled
program ran, and `rows`, the valid rows it held), and what its `jit.*`
records say of functions traced, lowered, compiled or loaded from the
cache. Read off the log of the run's scheduler
(`program_spanlog.program_log()`), cut to the window. A program whose
records carry no counts (a commit before them) reads as nothing.

The first reader of a run that comes here also says, in one line of
the run's log, how many `jit.*` records closed before the window
opened, with their seconds by kind, how many inside it and of which
functions (a compile inside the window is a fault of the warm-up),
and what each width's first call, a `worker.launch` of the warm-up,
held of them. Read by per-layer metrics only."""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

from perfbench.sources import program_spanlog
from perfbench.sources.host_clock import percentile

JIT_KINDS = ("jit.trace", "jit.lower", "jit.compile", "jit.cache_load")


@dataclasses.dataclass
class StepRecord:
    t0: float  # seconds, perf_counter
    t1: float
    step: Optional[int]
    width: int
    rows: int


def step_records(log, t0: float, t1: float) -> List[StepRecord]:
    """The log's `worker.step` records inside [t0, t1] that carry
    `width` and `rows`."""
    out = []
    for r in log.records():
        counts = getattr(r, "counts", None)
        if r.name != "worker.step" or not counts or "width" not in counts:
            continue
        a, b = r.t0_ns / 1e9, r.t1_ns / 1e9
        if t0 <= a and b <= t1:
            out.append(StepRecord(a, b, r.step, int(counts["width"]),
                                  int(counts.get("rows", 0))))
    return out


def jit_line(log, t0: float, t1: float) -> str:
    """One line from the `jit.*` records (a `jit.trace` is an outermost
    trace, so the seconds add up): before the window by kind, and
    inside it by kind with the functions' names."""
    before = {k: [0, 0.0, []] for k in JIT_KINDS}
    inside = {k: [0, 0.0, []] for k in JIT_KINDS}
    for r in log.records():
        if r.name not in before:
            continue
        end = r.t1_ns / 1e9
        if end > t1:
            continue
        into = (before if end < t0 else inside)[r.name]
        into[0] += 1
        into[1] += (r.t1_ns - r.t0_ns) / 1e9
        fun = (getattr(r, "counts", None) or {}).get("fun", "?")
        if fun not in into[2]:
            into[2].append(fun)
    n_inside = sum(n for n, _, _ in inside.values())
    return ("jit records: before the window "
            + ", ".join(f"{k[4:]} {n} in {s:.2f}s"
                        for k, (n, s, _) in before.items())
            + f"; inside it {n_inside}"
            + (" (" + "; ".join(
                f"{k[4:]} {n} in {s:.2f}s of {', '.join(funs[:4])}"
                for k, (n, s, funs) in inside.items() if n) + ")"
               if n_inside else "")
            + "".join(first_calls(log, t0)))


def first_calls(log, t0: float) -> List[str]:
    """For each `worker.launch` that closed before the window with
    `jit.*` records inside it (a width's first call, which traces and
    compiles or loads): its seconds and theirs by kind."""
    records = log.records()
    jits = [r for r in records if r.name in JIT_KINDS]
    widths = {r.step: (getattr(r, "counts", None) or {}).get("width")
              for r in records if r.name == "worker.step"}
    out = []
    for launch in records:
        if launch.name != "worker.launch" or launch.t1_ns / 1e9 >= t0:
            continue
        held = {}
        for j in jits:
            if launch.t0_ns <= j.t0_ns and j.t1_ns <= launch.t1_ns:
                held[j.name] = (held.get(j.name, 0.0)
                                + (j.t1_ns - j.t0_ns) / 1e9)
        if held:
            out.append(
                f"; step {launch.step} (width {widths.get(launch.step)}) "
                f"launch {(launch.t1_ns - launch.t0_ns) / 1e9:.2f}s holds "
                + " + ".join(f"{k[4:]} {held[k]:.2f}s"
                             for k in JIT_KINDS if k in held))
    return out


def window_steps(run) -> Optional[List[StepRecord]]:
    """The window's counted `worker.step` records, or None where the
    program has no log or counts nothing; says the `jit.*` line once a
    run."""
    log = program_spanlog.program_log()
    if log is None:
        return None
    if not getattr(run, "jit_line_said", False):
        run.jit_line_said = True
        if any(r.name in JIT_KINDS for r in log.records()):
            run.say(jit_line(log, run.t0, run.t1))
    return step_records(log, run.t0, run.t1) or None


def wide_and_narrow(run) -> Optional[Tuple[List[StepRecord],
                                           List[StepRecord]]]:
    """(the records of the largest width the window shows, those of
    any smaller one), or None without counted records."""
    steps = window_steps(run)
    if not steps:
        return None
    widest = max(s.width for s in steps)
    return ([s for s in steps if s.width == widest],
            [s for s in steps if s.width < widest])


def wall_p50_ms(steps: List[StepRecord]) -> Optional[float]:
    p = percentile([s.t1 - s.t0 for s in steps], 50)
    return None if p is None else 1e3 * p
