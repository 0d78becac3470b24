"""Entries of a BENCHMARK.json found by their names, never by their
places: a later PR appends configurations, cells and per-layer entries
at the ends of the lists, and extends an accepted entry's `workloads`,
so a test that reads `bench["per_layer"][-1]`, or counts the entries
that list a cell, fails on what that PR adds and cannot mend.
`test_perfbench_by_name.py` holds every file here to that."""

import os

from perfbench import harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the per-layer entries every accepted cell lists
GENERIC = ("sched.row_occupancy_pct", "step.wall_p50_ms",
           "step.host_gap_p50_ms", "step.mfu_pct", "device.idle_pct",
           "sched.admit_p50_ms", "sched.assemble_p50_ms",
           "sched.keys_p50_ms", "sched.emit_p50_ms", "sched.observe_p50_ms",
           "worker.dispatch_p50_ms", "worker.wait_p50_ms",
           "device.idle_named_pct", "sched.prefill_rows_pct",
           "kv.gather_live_pct")


def load(root: str = REPO) -> dict:
    return harness.load_json(os.path.join(root, "BENCHMARK.json"))


def config(bench: dict, name: str) -> dict:
    return harness.find(bench["configs"], name, "configuration")


def cell(bench: dict, name: str) -> dict:
    return harness.find(bench["workloads"], name, "workload")


def metric(bench: dict, name: str) -> dict:
    return harness.find(bench["per_layer"], name, "per-layer metric")


def listing(bench: dict, cell_name: str) -> set:
    """The names of the per-layer entries read in a cell: those that
    list it, and any that lists no cells at all."""
    return {m["name"] for m in bench["per_layer"]
            if "workloads" not in m or cell_name in m["workloads"]}
