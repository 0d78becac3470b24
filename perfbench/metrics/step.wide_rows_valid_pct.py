"""Share of the widest step's rows that hold a token: the sum of
`rows` over `slots x width` of the window's `worker.step` records of
the largest width. What packing the mixed step moves;
`sched.row_occupancy_pct` divides by `slots x chunk` for the narrow
steps too."""

from perfbench.sources import program_steplog


def read(run):
    split = program_steplog.wide_and_narrow(run)
    if split is None:
        return None
    wide = split[0]
    share = 100.0 * sum(s.rows for s in wide) / (
        len(wide) * run.slots * wide[0].width)
    return share if share > 0 else None
