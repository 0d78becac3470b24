"""Share of the run's steps, warm-up and drain included, that ran the
narrow compiled step (no row of more than one token) and not the
`(slots, chunk)` one: `serve_steps{shape=narrow}` over both shapes
(the program's counters). A program that counts neither reads as
nothing."""

from perfbench.sources import program_spanlog

NARROW, WIDE = "serve_steps{shape=narrow}", "serve_steps{shape=wide}"


def read(run):
    return program_spanlog.counter_share_pct(run, NARROW, [NARROW, WIDE])
