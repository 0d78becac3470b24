"""Median of a step's start minus the previous step's end, over steps
with a request active on both sides: what the host does between two
dispatches. Depth is cut on one chip, so this is a larger share of a
step than in a deployment."""

from perfbench.sources.host_clock import percentile


def read(run):
    gaps = [b.t0 - a.t1 for a, b in zip(run.steps, run.steps[1:])
            if a.rows and b.rows]
    p = percentile(gaps, 50)
    return None if p is None or p <= 0 else 1e3 * p
