"""Median time of `worker.wait` over the window's steps: the device's
step and the readback of its tokens (the program's span log)."""

from perfbench.sources import program_spanlog


def read(run):
    return program_spanlog.phase_p50_ms(run, ["worker.wait"])
