"""The program's counters (`Scheduler.obs`, an `obs.registry.Registry`)
as a flat dict of name -> count. Read by per-layer metrics only."""

from __future__ import annotations


def counters_of(scheduler) -> dict:
    return dict(scheduler.obs.snapshot().get("counters", {}))
