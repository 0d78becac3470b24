"""Request objects for the serving plane: lifecycle, streaming,
incremental detokenization.

TPU-native analog of the reference's per-connection request handling in
the megakernel model server (ref: mega_triton_kernel/test/models/
model_server.py:112-193 + chat.py): there a socket request owns a whole
blocking `serve` call; here a Request is a unit of SCHEDULING — it moves
through queued -> prefill -> decode (possibly bouncing back to queued on
eviction) while the scheduler interleaves it with other requests, and
its tokens stream out incrementally through a callback or iterator.
"""

from __future__ import annotations

import dataclasses
import enum
import queue as _queue
import time
from typing import Callable, List, Optional, Tuple


class RequestState(enum.Enum):
    QUEUED = "queued"        # waiting in the RequestQueue (or requeued)
    PREFILL = "prefill"      # chunked prompt (re)processing on a slot
    DECODE = "decode"        # one token per scheduler step
    FINISHED = "finished"    # eos / max_new_tokens reached
    CANCELLED = "cancelled"  # dropped by the client
    FAILED = "failed"        # quarantined by the degradation ladder
    # (the scheduler attributed a repeated step failure to this request
    # and retired it so the survivors could proceed — docs/robustness.md)


_END = object()  # stream sentinel


class TokenStream:
    """Blocking iterator over a request's generated tokens (the serving
    analog of the chat client's incremental read loop). Yields
    (token_id, piece) pairs; `piece` is the detokenized text fragment
    when the scheduler has a detokenizer, else None. Iteration ends at
    completion or cancellation."""

    def __init__(self):
        self._q: _queue.Queue = _queue.Queue()

    def _push(self, tok: int, piece: Optional[str]):
        self._q.put((tok, piece))

    def _close(self):
        self._q.put(_END)

    def __iter__(self):
        while True:
            item = self._q.get()
            if item is _END:
                return
            yield item

    def get(self, timeout: Optional[float] = None):
        """One (token, piece) pair or None at end-of-stream."""
        item = self._q.get(timeout=timeout)
        return None if item is _END else item


@dataclasses.dataclass
class Request:
    """One generation request plus its scheduling state and metrics.

    `history()` is the token sequence a (re-)prefill must process:
    prompt + already-generated tokens — after an eviction the request
    re-enters PREFILL over its full history, and because the serve step
    geometry is fixed, the resumed generation is bitwise identical to an
    uninterrupted run (models/engine.make_serve_step)."""

    prompt: List[int]
    max_new_tokens: int
    priority: int = 0          # higher runs first
    temperature: float = 0.0   # <=0: greedy (the bit-identity regime)
    seed: int = 0
    eos_id: Optional[int] = None
    on_token: Optional[Callable[["Request", int, Optional[str]], None]] \
        = None
    stream: Optional[TokenStream] = None

    # -- scheduler-owned state ------------------------------------------
    request_id: int = -1
    state: RequestState = RequestState.QUEUED
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    pos: int = 0               # prefill cursor into history()
    slot: int = -1             # pool slot while active, else -1
    seq: int = -1              # queue arrival order (priority tie-break)
    admit_seq: int = -1        # admission order (eviction victim order)
    last_active_step: int = -1
    n_evictions: int = 0
    finish_reason: Optional[str] = None  # "eos" | "length" | "cancelled"

    # -- metrics (perf_counter_ns) --------------------------------------
    t_submit: int = 0
    t_first_token: int = 0
    t_finish: int = 0          # stamped by _finish (ledger wall clock)
    token_times: List[int] = dataclasses.field(default_factory=list)

    # -- request-scoped attribution (ISSUE 13) --------------------------
    # accumulated wall time per lifecycle phase (queued/prefill/decode;
    # an evicted request re-accumulates queued+prefill) — the scheduler
    # folds each closed phase span in here, so TTFT/TPOT decompose per
    # request without replaying the span log (trace/ledger.py)
    phase_ns: dict = dataclasses.field(default_factory=dict)
    n_device_steps: int = 0    # serve steps this request rode
    n_prefill_chunks: int = 0  # prefill chunk steps among them
    # -- prefix + spec planes (ISSUE 14) --------------------------------
    prefix_len: int = 0        # prompt tokens served from the prefix
    # cache at the LAST admission (prefill skipped straight past them)
    n_spec_steps: int = 0      # device steps that ran a spec-verify row
    spec_verify_ns: int = 0    # wall share of those steps — a
    # SUB-BUCKET of the decode phase (trace/ledger.py), never added to
    # the close sum

    def history(self) -> List[int]:
        return self.prompt + self.out_tokens

    @property
    def length(self) -> int:
        """Current sequence length once fully (re-)prefilled."""
        return len(self.prompt) + len(self.out_tokens)

    @property
    def done(self) -> bool:
        return self.state in (RequestState.FINISHED,
                              RequestState.CANCELLED,
                              RequestState.FAILED)

    # -- latency metrics ------------------------------------------------

    def ttft_us(self) -> Optional[float]:
        """Time-to-first-token: submit -> first generated token."""
        if not self.token_times:
            return None
        return (self.token_times[0] - self.t_submit) / 1e3

    def tpot_us(self) -> Optional[float]:
        """Mean time-per-output-token over the decode phase (excludes
        the first token, which TTFT owns)."""
        if len(self.token_times) < 2:
            return None
        return ((self.token_times[-1] - self.token_times[0])
                / (len(self.token_times) - 1) / 1e3)

    def _emit(self, tok: int, piece: Optional[str]):
        self.out_tokens.append(tok)
        now = time.perf_counter_ns()
        if not self.token_times:
            self.t_first_token = now
        self.token_times.append(now)
        if self.on_token is not None:
            self.on_token(self, tok, piece)
        if self.stream is not None:
            self.stream._push(tok, piece)

    def _finish(self, reason: str, state: RequestState):
        self.state = state
        self.finish_reason = reason
        self.t_finish = time.perf_counter_ns()
        if self.stream is not None:
            self.stream._close()


class Detokenizer:
    """Incremental detokenization hook. The framework carries no real
    tokenizer (models are random-weight reproductions), so this is the
    minimal streaming contract: `piece(tok)` returns the text fragment
    one new token appends. Backed by a vocab list/dict or any
    id->str callable (a real BPE detokenizer slots in here)."""

    def __init__(self, vocab):
        if callable(vocab):
            self._fn = vocab
        else:
            self._fn = lambda t: vocab[t]

    def piece(self, tok: int) -> str:
        return str(self._fn(tok))

    def text(self, toks) -> str:
        return "".join(self.piece(t) for t in toks)


# fine-grained latency buckets: growth=1.05 bounds the quantile error
# at ~2.5% — tight enough for the bench artifact's p99 columns while
# keeping the merge-exactly property of fixed log buckets
LATENCY_BUCKETS = (10.0, 1e8, 1.05)  # us span: 10us .. 100s


def summarize(requests) -> dict:
    """Aggregate serving metrics over finished requests: tokens/s over
    the span, p50/p99 TTFT and TPOT in microseconds — the bench.py
    serving schema (docs/serving.md has the methodology). Quantiles run
    on `obs.registry.Histogram` (fixed log buckets, the always-on
    plane's one quantile definition) instead of the bespoke
    np.percentile math this function used to carry — so an offline
    summary and a live `Scheduler.metrics()` read of the same traffic
    agree by construction."""
    from triton_dist_tpu.obs.registry import Histogram, log_buckets

    done = [r for r in requests if r.state == RequestState.FINISHED
            and r.token_times]
    if not done:
        return {"n": 0, "tokens_per_s": 0.0}
    t0 = min(r.t_submit for r in done)
    t1 = max(r.token_times[-1] for r in done)
    n_tok = sum(len(r.out_tokens) for r in done)
    bounds = log_buckets(*LATENCY_BUCKETS)
    ttft, tpot = Histogram(bounds), Histogram(bounds)
    for r in done:
        ttft.observe(r.ttft_us())
        if r.tpot_us() is not None:
            tpot.observe(r.tpot_us())

    def pct(h, q):
        return round(h.quantile(q), 2) if h.total else 0.0

    return {
        "n": len(done),
        "tokens_per_s": round(n_tok / max((t1 - t0) / 1e9, 1e-9), 2),
        "ttft_p50_us": pct(ttft, 0.50),
        "ttft_p99_us": pct(ttft, 0.99),
        "tpot_p50_us": pct(tpot, 0.50),
        "tpot_p99_us": pct(tpot, 0.99),
    }


Span = Tuple[str, int, int]  # host-span triple (trace.collect.Timeline)
