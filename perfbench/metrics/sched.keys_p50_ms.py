"""Median time of `sched.keys` over the window's steps: drawing one
sampling key an emitting row (the program's span log)."""

from perfbench.sources import program_spanlog


def read(run):
    return program_spanlog.phase_p50_ms(run, ["sched.keys"])
