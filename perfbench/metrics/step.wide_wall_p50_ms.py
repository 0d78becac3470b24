"""Median duration of the window's `worker.step` records whose `width`
is the largest the window shows: the step in which some slot prefills,
which sets `itl_p95_ms` (the program's span log, its records' counts).
Where every step has the one width it is `step.wall_p50_ms` less the
stamps around the span."""

from perfbench.sources import program_steplog


def read(run):
    split = program_steplog.wide_and_narrow(run)
    return None if split is None else program_steplog.wall_p50_ms(split[0])
