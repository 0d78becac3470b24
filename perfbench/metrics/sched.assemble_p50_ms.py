"""Median self time of `sched.assemble` over the window's steps: the
step's token, length and temperature arrays from the active slots, its
`sched.keys` child taken out (the program's span log)."""

from perfbench.sources import program_spanlog


def read(run):
    return program_spanlog.phase_p50_ms(run, ["sched.assemble"])
