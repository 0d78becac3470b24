"""Shared arithmetic of the `<work>.roofline_pct` readers: the least
time a chip could take for the traced steps' work over the device time
of the events that implement it."""

from perfbench import work
from perfbench.sources import device_trace


def roofline_pct(run, work_name):
    if run.trace is None or not run.trace_steps:
        return None
    spec = work.load(run.root, work_name)
    per_chip = device_trace.per_device_matching(run.trace,
                                                work.patterns(spec))
    runs = device_trace.module_runs(run.trace)
    if not per_chip or max(per_chip) <= 0 or runs <= 0:
        return None  # the kernel is not on the path: say nothing
    least = 0.0
    tally = {}
    for s in run.trace_steps:
        need = work.step_needs(spec, run.sizes,
                               [(r.n, r.ctx, r.emits) for r in s.rows])
        t, bound = work.least_seconds(need, run.peaks)
        least += t
        tally[bound] = tally.get(bound, 0.0) + t
    least *= runs / len(run.trace_steps)
    run.say(f"roofline {work_name}: least {least:.4f}s over "
            f"{max(per_chip):.4f}s of device time in {runs} steps, bound "
            f"by {max(tally, key=tally.get)}")
    return 100.0 * least / max(per_chip)
