"""What the program's own spans say: `Scheduler.history` (one entry a
step: t0, t1 in perf_counter_ns, slot -> (request id, state, rows)) and
`Request.phase_ns`. Read by per-layer metrics only."""

from __future__ import annotations

import dataclasses
from typing import Dict, List


@dataclasses.dataclass
class Row:
    request: int
    state: str   # "prefill" | "decode"
    n: int       # valid token rows of this slot in this step
    ctx: int     # tokens of the request already in the cache
    emits: bool  # the row samples an output token


@dataclasses.dataclass
class Step:
    t0: float  # seconds, perf_counter
    t1: float
    rows: List[Row]


def steps_of(history: List[dict], prompt_lens: Dict[int, int]) -> List[Step]:
    """Steps with each row's context length, accumulated per request
    (a fully provisioned pool never evicts, so a request's rows simply
    add up). `prompt_lens`: request id -> prompt length, to tell the
    prefill chunk that emits."""
    seen: Dict[int, int] = {}
    out = []
    for h in history:
        if h.get("kind") != "step":
            continue
        rows = []
        for _slot, (rid, state, n) in sorted(h["slots"].items()):
            ctx = seen.get(rid, 0)
            emits = (state == "decode"
                     or ctx + n >= prompt_lens.get(rid, 1 << 62))
            rows.append(Row(rid, state, int(n), ctx, emits))
            seen[rid] = ctx + int(n)
        out.append(Step(h["t0"] / 1e9, h["t1"] / 1e9, rows))
    return out


def in_window(steps: List[Step], t0: float, t1: float) -> List[Step]:
    return [s for s in steps if t0 <= s.t0 and s.t1 <= t1]
