"""Pool bytes a step's views gather for each live position, all page
layers together: `serve_kv_bytes_gathered` over `serve_kv_tokens_live`,
the run's steps together. What a live position costs the step, in one
unit for a key-value page and a latent one. A program without the
byte counters says nothing."""

GATHERED, LIVE = "serve_kv_bytes_gathered", "serve_kv_tokens_live"


def read(run):
    live = run.counters.get(LIVE, 0)
    if GATHERED not in run.counters or live <= 0:
        return None
    return run.counters[GATHERED] / live
