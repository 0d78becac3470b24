"""Of the window blocks' per-slot tails a step reads and writes (every
slot's), the share that belongs to slots with a valid row:
`serve_window_bytes_live` over `serve_window_bytes_moved`, the run's
steps together. A program without window blocks counts neither and
says nothing."""

from perfbench.sources import program_spanlog

LIVE, MOVED = "serve_window_bytes_live", "serve_window_bytes_moved"


def read(run):
    return program_spanlog.counter_share_pct(run, LIVE, [MOVED])
