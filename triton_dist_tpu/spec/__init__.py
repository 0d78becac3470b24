"""triton_dist_tpu.spec — speculative decoding on the paged serve plane.

Memory-bound decode pays a whole weight stream per emitted token; the
serve step's fixed (slots, chunk) geometry means the SAME stream could
score k+1 tokens for nearly the same cost. This package proposes k
tokens per decoding slot (`spec.draft` — self-drafting n-gram /
prompt-lookup over the request's own emitted tokens; the `Draft`
protocol lets a small model slot in later), verifies them in ONE
batched fixed-geometry step (`models/engine.make_serve_step(...,
per_pos=True)` — every column sampled under its own per-(seed,
token-index) key), and accepts the longest proposed prefix the model
agrees with (`spec.verify`).

The acceptance oracle is the serve plane's bit-identity discipline
(docs/serving.md): column j of the verify step is BITWISE the token
sequential decode would emit after the row's first j+1 tokens — greedy
and sampled alike — so the emitted stream (accepted draft tokens plus
the bonus token) is always bitwise equal to plain sequential decode;
rejection merely degenerates to the normal one-token step. k=0 turns
the whole plane off (`perf_model.choose_spec_k` picks k from the
observed acceptance rate).

Wired through `serve.Scheduler(spec=SpecConfig(...))`: verify slots mix
with prefill/decode slots in the heterogeneous step.
"""

from triton_dist_tpu.spec.draft import Draft, NgramDraft  # noqa: F401
from triton_dist_tpu.spec.verify import (  # noqa: F401
    SpecConfig,
    accept_tokens,
    verify_keys,
)
