"""Valid token rows over the rows the fixed (slots, chunk) step
computes, summed over the window's steps (`Scheduler.history`)."""


def read(run):
    if not run.steps:
        return None
    valid = sum(row.n for s in run.steps for row in s.rows)
    share = 100.0 * valid / (len(run.steps) * run.slots * run.chunk)
    return share if share > 0 else None
