"""1 minus the union of device-operation intervals over the traced
window, mean over the cell's chips. Depth is cut on one chip, so the
host's share is larger than in a deployment."""

from perfbench.sources import device_trace


def read(run):
    if run.trace is None:
        return None
    busy, window = device_trace.busy_and_window(run.trace)
    if window <= 0 or busy <= 0:
        return None
    return 100.0 * (1.0 - busy / window)
