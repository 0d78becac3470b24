"""Of the valid rows' (token, choice) pairs, the share whose expert
this chip holds: `moe_pairs{held=here}` over here + absent, counted by
the step on the device, the run's steps together. Near the share of
the experts held while the routing is over all of them; a layer that
routed among its own experts only would read 100."""

from perfbench.sources import program_spanlog

HERE, ABSENT = "moe_pairs{held=here}", "moe_pairs{held=absent}"


def read(run):
    return program_spanlog.counter_share_pct(run, HERE, [HERE, ABSENT])
