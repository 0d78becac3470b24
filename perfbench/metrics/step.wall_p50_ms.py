"""Median wall time of a serve step, t1 - t0 of `Scheduler.history`."""

from perfbench.sources.host_clock import percentile


def read(run):
    p = percentile([s.t1 - s.t0 for s in run.steps], 50)
    return None if p is None else 1e3 * p
