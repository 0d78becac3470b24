"""Device time per step in operations that only communicate, on the
busiest chip."""

from perfbench.sources import device_trace

PATTERNS = ["*_ring_ag_kernel*", "all-reduce*", "all-gather*",
            "reduce-scatter*", "collective-permute*", "all-to-all*"]


def read(run):
    if run.trace is None:
        return None
    runs = device_trace.module_runs(run.trace)
    per_chip = device_trace.per_device_matching(run.trace, PATTERNS)
    if runs <= 0 or not per_chip or max(per_chip) <= 0:
        return None
    return 1e3 * max(per_chip) / runs
