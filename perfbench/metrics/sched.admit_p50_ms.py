"""Median self time of `sched.admit` over the window's steps: reaping
cancelled requests, the migration pump, admission (the program's span
log)."""

from perfbench.sources import program_spanlog


def read(run):
    return program_spanlog.phase_p50_ms(run, ["sched.admit"])
