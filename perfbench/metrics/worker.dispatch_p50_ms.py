"""Median of `worker.put` + `worker.launch` over the window's steps:
the host-to-device puts of a step's arguments and the call of the
compiled step, which returns at enqueue (the program's span log)."""

from perfbench.sources import program_spanlog


def read(run):
    return program_spanlog.phase_p50_ms(run, ["worker.put", "worker.launch"])
