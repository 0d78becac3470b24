"""Median time of `sched.emit` over the window's steps: the history
entry, the step's counters, tokens to their streams, retirements (the
program's span log)."""

from perfbench.sources import program_spanlog


def read(run):
    return program_spanlog.phase_p50_ms(run, ["sched.emit"])
