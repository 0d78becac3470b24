"""Median time of `sched.observe` over the window's steps: gauges, the
flight recorder's registry snapshot, the SLO feed (the program's span
log)."""

from perfbench.sources import program_spanlog


def read(run):
    return program_spanlog.phase_p50_ms(run, ["sched.observe"])
