"""Prefill's share of the valid rows of the run's steps, warm-up and
drain included: `serve_rows{state=prefill}` over both states (the
program's counters)."""

from perfbench.sources import program_spanlog

PREFILL, DECODE = "serve_rows{state=prefill}", "serve_rows{state=decode}"


def read(run):
    return program_spanlog.counter_share_pct(run, PREFILL, [PREFILL, DECODE])
