"""Median duration of the window's `worker.step` records of any width
under the largest: the decode-only step, whose period is the chat
cells' throughput. Nothing where no step ran narrow."""

from perfbench.sources import program_steplog


def read(run):
    split = program_steplog.wide_and_narrow(run)
    return None if split is None else program_steplog.wall_p50_ms(split[1])
