"""Spec-verify: batched draft scoring + the longest-accepted-prefix
rule (ISSUE 14).

One verify step for a decoding slot feeds the row

    [last_token, d_1, ..., d_k]          (n_valid = k + 1)

through the per-position serve step (`models/engine.make_serve_step
(..., per_pos=True)`). Column j's sampled token o_j is — by the serve
plane's bit-identity discipline and the per-(seed, token-index) key
stream — BITWISE the token sequential decode would emit after history
+ d_1..d_j. The longest-accepted-prefix rule therefore never has to
compare distributions: accept while o_{j-1} == d_j, and the emitted
tokens are o_0..o_a (the accepted drafts ARE the model's own tokens,
plus the bonus token o_a). Every emitted token is bitwise what plain
sequential decode would have produced, greedy and sampled alike;
a == 0 degenerates to the normal one-token step.

KV bookkeeping: the verify step wrote KV for ALL k+1 fed positions;
only the first a+1 are real history, so the pool length advances by
the EMITTED count (len(accept_tokens(...))) — rejected positions hold
garbage beyond the valid length (causally masked, overwritten by the
next step exactly like post-eviction stale pages). The scheduler owns
that advance (serve/worker.py step_spec/advance_lengths).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import numpy as np

from triton_dist_tpu.spec.draft import Draft, NgramDraft


@dataclasses.dataclass
class SpecConfig:
    """Speculative-decoding policy for `serve.Scheduler(spec=...)`.

    k        max draft tokens verified per step (k=0 disables; the
             verify row needs k+1 <= chunk columns —
             perf_model.choose_spec_k picks k from the acceptance
             rate).
    draft    the proposer (defaults to prompt-lookup NgramDraft).
    adaptive feed the LIVE acceptance rate (an EWMA over the
             scheduler's spec_accept_rate observations) back into
             perf_model.choose_spec_k, so the draft width decays to 0
             on non-self-similar traffic and recovers when acceptance
             returns (ROADMAP item 4 follow-up). `k` stays the hard
             cap (the k+1 <= chunk check is made for it);
             adaptation only narrows rows. Emitted tokens are
             bitwise unaffected — k changes what is PROPOSED, and
             every accepted token is the model's own emission.
    ewma_alpha  weight of the newest verify step in the EWMA.
    """

    k: int = 4
    draft: Draft = dataclasses.field(default_factory=NgramDraft)
    adaptive: bool = False
    ewma_alpha: float = 0.2

    def __post_init__(self):
        assert self.k >= 0, f"spec k must be >= 0, got {self.k}"
        assert 0.0 < self.ewma_alpha <= 1.0, (
            f"ewma_alpha must be in (0, 1], got {self.ewma_alpha}")


def draft_cap(k: int, chunk: int, history_len: int, n_out: int,
              max_new: int, t_max: int) -> int:
    """How many draft tokens a verify row may carry right now: bounded
    by the configured k, the row width (k+1 <= chunk), the output
    budget (emitting more than max_new - n_out tokens is wasted), and
    the pool horizon (the row's last KV write lands at position
    history_len - 1 + k < t_max)."""
    return max(0, min(k, chunk - 1, max_new - n_out - 1,
                      t_max - history_len))


def verify_keys(seed: int, n_out: int, width: int,
                cols: int) -> np.ndarray:
    """The verify row's per-column sampling keys (cols=chunk wide,
    first `width` columns populated): column j emits output-token index
    n_out + j, so its key is THE key stream's fold_in(PRNGKey(seed),
    n_out + j) — the same derivation sequential decode uses for that
    token index (serve.worker.sampling_keys, one host call a row)."""
    # serve.scheduler imports this module: import at call time
    from triton_dist_tpu.serve.worker import sampling_keys

    keys = np.zeros((cols, 2), np.uint32)
    keys[:width] = sampling_keys(seed, n_out + np.arange(width))
    return keys


def accept_tokens(proposed: Sequence[int], row_tokens,
                  eos_id: Optional[int] = None,
                  max_emit: Optional[int] = None) -> List[int]:
    """Longest-accepted-prefix rule over one verify row's per-position
    tokens. `row_tokens` are o_0..o_k (columns 0..len(proposed) of the
    per-position step output for this slot); returns the tokens to
    emit, in order: o_0..o_a where a is the longest prefix with
    o_{j-1} == proposed[j-1], truncated at the first eos and at
    `max_emit` (the request's remaining output budget) — exactly where
    sequential decode would have stopped."""
    row = [int(t) for t in row_tokens]
    a = 0
    while a < len(proposed) and row[a] == int(proposed[a]):
        a += 1
    out = row[:a + 1]
    if eos_id is not None and eos_id in out:
        out = out[:out.index(eos_id) + 1]
    if max_emit is not None:
        out = out[:max(max_emit, 0)]
    return out
