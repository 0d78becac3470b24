"""End-to-end arithmetic on the benchmark's own stamps.

Every stamp is `time.perf_counter()` seconds taken by the load
generator: `due` when a request was due to be sent, `tokens` when the
client read each output token. Nothing here reads the program.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Stamps:
    """One request as the client saw it."""

    index: int
    due: float
    sent: float
    prompt_len: int
    max_new: int
    tokens: List[float] = dataclasses.field(default_factory=list)
    ended: Optional[float] = None  # stream closed
    failed: bool = False           # ended without all its tokens, not by us
    cancelled: bool = False        # cut by the harness after the window


def percentile(values, q: float) -> Optional[float]:
    """The q-th percentile (0-100) by linear interpolation; None when
    there is nothing to take it of."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, float), q))


def ttfts(reqs: List[Stamps], t0: float, t1: float) -> List[float]:
    """Seconds to the first token for every request DUE in [t0, t1),
    from its due time. A failed request counts as the window's length;
    one still waiting at t1 counts with what it has waited so far."""
    out = []
    for r in reqs:
        if not t0 <= r.due < t1:
            continue
        if r.failed and not r.tokens:
            out.append(t1 - t0)
        elif r.tokens and r.tokens[0] <= t1:
            out.append(r.tokens[0] - r.due)
        else:
            out.append(t1 - r.due)
    return out


def gaps(reqs: List[Stamps], t0: float, t1: float) -> List[float]:
    """Every gap between consecutive output tokens of one request that
    ends in [t0, t1], and the open gap of each request that is waiting
    for its next token at t1 (a stall lengthens every gap it covers)."""
    out = []
    for r in reqs:
        toks = r.tokens
        for a, b in zip(toks, toks[1:]):
            if t0 <= a and b <= t1:
                out.append(b - a)
        inside = [t for t in toks if t <= t1]
        waiting = (len(inside) < r.max_new and not r.failed
                   and (r.ended is None or r.ended > t1))
        if inside and waiting and inside[-1] >= t0:
            out.append(t1 - inside[-1])
    return out


def output_tokens(reqs: List[Stamps], t0: float, t1: float) -> int:
    return sum(1 for r in reqs for t in r.tokens if t0 <= t <= t1)


def prompt_tokens(reqs: List[Stamps], t0: float, t1: float) -> float:
    """Prompt tokens consumed in [t0, t1] as a client can tell: a
    request's prompt is credited at a uniform rate between its due time
    and its first token, so a prompt that straddles an edge of the
    window counts by the part of that span inside it. A request with no
    first token yet credits nothing."""
    total = 0.0
    for r in reqs:
        if not r.tokens:
            continue
        a, b = r.due, r.tokens[0]
        span = max(b - a, 1e-9)
        total += r.prompt_len * max(0.0, min(b, t1) - max(a, t0)) / span
    return total


def lateness(reqs: List[Stamps]) -> List[float]:
    return [r.sent - r.due for r in reqs]
