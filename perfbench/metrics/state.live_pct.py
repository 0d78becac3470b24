"""Of the per-slot recurrent and convolution state a step reads and
writes (every slot's), the share that belongs to slots with a valid
row: `serve_state_bytes_live` over `serve_state_bytes_moved`, the
run's steps together."""

from perfbench.sources import program_spanlog

LIVE, MOVED = "serve_state_bytes_live", "serve_state_bytes_moved"


def read(run):
    return program_spanlog.counter_share_pct(run, LIVE, [MOVED])
