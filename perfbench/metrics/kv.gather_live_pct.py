"""Live KV positions over the positions the steps' dense view of the
paged pool gathered, the run's steps together: `serve_kv_tokens_live`
over `serve_kv_tokens_gathered` (the program's counters)."""

from perfbench.sources import program_spanlog

LIVE, GATHERED = "serve_kv_tokens_live", "serve_kv_tokens_gathered"


def read(run):
    return program_spanlog.counter_share_pct(run, LIVE, [GATHERED])
