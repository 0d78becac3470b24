"""Scheduler — continuous (in-flight) batching over the serve step.

Each step assembles a HETEROGENEOUS batch: new requests' prefill chunks
ride next to in-flight requests' decode steps in the same
(slots, width) token block, so admission never waits for the running
batch to drain (the reference serves one blocking request at a time
over its socket — model_server.py:112-193; this is the production shape
of that loop). The width is the step's own: the narrowest of the
worker's compiled widths (`Engine.serve_widths`: 1 and `chunk`) that
holds the step's longest row, so a step of decode rows alone runs the
(slots, 1) program. Policies:

  admission   — priority order off the RequestQueue; a new request
                needs a free slot + pages for its history
                (allocate-on-admit). A STRICTLY higher-priority arrival
                may evict the most-victimizable active request.
  eviction    — victim order is (priority asc, least-recently-active,
                youngest admission): "LRU/priority". Mid-flight page
                exhaustion evicts only requests younger-or-lower than
                the one needing room (a strict total order — no
                thrash cycles); if every slot stalls, the most-
                victimizable is evicted to guarantee progress. Evicted
                requests requeue with their original arrival order and
                re-prefill their full history — bit-identical to an
                uninterrupted run at one step width, and equal to
                rounding across widths (engine.make_serve_step).
  completion  — eos_id or max_new_tokens; the slot and its pages free
                immediately (free-on-finish).
  degradation — a step failure (faults.FaultError: a guard watchdog's
                DeadlineExceeded, a WireIntegrityError, an injected
                chaos fault) never kills the batch: the step retries
                with bounded exponential backoff; when retries exhaust,
                the most recently admitted request in the failing step
                is QUARANTINED (retired as FAILED — the newest arrival
                is the most likely poisoner, the survivors were running
                fine before it) and the survivors continue next step.
                Every retry and quarantine lands in the host-span
                timeline, so recoveries are attributable in Perfetto
                (docs/robustness.md "degradation ladder").

Tokens stream per request (callback/iterator, incremental
detokenization) and every lifecycle phase is recorded as a host span
(queued/prefill/decode — plus migrate/admit on the disaggregated
roles, eviction instants) exportable to Perfetto via `timeline()` —
the serving extension of the trace/ subsystem.

Disaggregated prefill/decode (ISSUE 18, docs/serving.md): with
`role="prefill"` the scheduler runs prefill only and, at the moment a
request would emit its first token, streams its KV pages out through
`migrate_to` as a checksummed wire image (xslice/migrate.py) — the
first token TRAVELS in the record instead of being emitted locally,
so the decode slice is the stream's single producer. With
`role="decode"` verified arrivals admit straight into DECODE via
`admit_from` (admission gates on `decode_pages` passing — a corrupted
image NACKs for a re-encode/resend, never admits). The pair's emitted
tokens are bitwise the single-slice (`role="both"`) scheduler's.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import List, Optional

import numpy as np

from triton_dist_tpu.faults.errors import FaultError
from triton_dist_tpu.obs.health import SLOMonitor
from triton_dist_tpu.obs.recorder import FlightRecorder
from triton_dist_tpu.obs.registry import Registry
from triton_dist_tpu.obs.spans import new_default_log
from triton_dist_tpu.serve.kv_pool import KVPool, PoolExhausted, pages_for
from triton_dist_tpu.serve.prefix import PrefixCache
from triton_dist_tpu.serve.queue import QueueFull, RequestQueue
from triton_dist_tpu.serve.request import (
    LATENCY_BUCKETS,
    Detokenizer,
    Request,
    RequestState,
    TokenStream,
    summarize,
)
from triton_dist_tpu.serve.worker import Worker, sampling_keys
from triton_dist_tpu.spec.verify import accept_tokens, draft_cap


def _default_page(max_len: int) -> int:
    for p in (64, 32, 16, 8, 4, 2, 1):
        if max_len % p == 0:
            return p
    return 1


class Scheduler:
    def __init__(
        self,
        engine,
        slots: int = 2,
        chunk: Optional[int] = None,
        page: Optional[int] = None,
        max_pages: Optional[int] = None,
        total_pages: Optional[int] = None,
        max_active: Optional[int] = None,
        queue: Optional[RequestQueue] = None,
        detokenizer: Optional[Detokenizer] = None,
        max_step_retries: int = 2,
        retry_backoff_s: float = 0.005,
        registry: Optional[Registry] = None,
        recorder: Optional[FlightRecorder] = None,
        slo: Optional[SLOMonitor] = None,
        spec=None,
        prefix_cache=False,
        prefix_block: Optional[int] = None,
        role: str = "both",
        migrate_to=None,
        admit_from=None,
        migration_format=None,
        max_migration_retries: int = 3,
        migration_resend_after: int = 8,
    ):
        page = page or _default_page(engine.max_len)
        # -- the host-span log (obs/spans.py): every round's phases,
        # the worker's put/launch/wait and each request's lifecycle
        # phases, always on. One a scheduler, like the registry;
        # obs.spans.default_log() is the newest scheduler's
        self.spans = new_default_log()
        if engine.cfg.is_hybrid:
            # what would have to copy, verify or ship what a slot
            # carries beside its pages, and cannot yet (docs/serving.md)
            from triton_dist_tpu.models.hybrid import slot_state

            for asked, what in (
                    (prefix_cache, "prefix_cache: a cached prefix is "
                     "pages, and a delta-net or state-space block's "
                     "state or a window block's tail after the prefix is "
                     "kept nowhere"),
                    (spec is not None and getattr(spec, "k", 0) > 0,
                     "spec: a rejected draft would have to roll that "
                     "state back, and the step keeps no per-column "
                     "state"),
                    (role != "both" or migrate_to is not None
                     or admit_from is not None,
                     "xslice migration (role / migrate_to / admit_from):"
                     " the wire image holds pages only")):
                if asked:
                    raise NotImplementedError(
                        f"a configuration whose slots carry "
                        f"{slot_state(engine.cfg)} beside their pages "
                        f"cannot be served with {what}")
        self.pool = KVPool(engine, slots, page, max_pages=max_pages,
                           total_pages=total_pages)
        if chunk is None:
            from triton_dist_tpu.kernels.flash_prefill import (
                flash_prefill_native_ok,
            )
            from triton_dist_tpu.perf_model import choose_chunk_for

            cfg = engine.cfg
            n = int(engine.mesh.shape[engine.axis])
            # price the chunk's attention at the impl the step will
            # actually run (the flash-prefill switch, layers/attention):
            # the kernel's missing f32-logits term keeps the pick wide
            attn_impl = (
                "flash" if flash_prefill_native_ok(
                    cfg.num_q_heads // n, cfg.num_kv_heads // n,
                    cfg.head_dim) else "xla")
            # from the family's own sizes (perf_model.choose_chunk_for)
            chunk = choose_chunk_for(cfg, n, slots, self.pool.t_max,
                                     attn_impl)
            chunk = max(1, min(chunk, self.pool.t_max))
        self.chunk = chunk
        # -- speculative decoding (ISSUE 14, triton_dist_tpu.spec): a
        # SpecConfig turns decoding slots into k-token verify rows of
        # the per-position serve step. k=0 (or spec=None) is OFF.
        self.spec = spec if (spec is not None
                             and getattr(spec, "k", 0) > 0) else None
        if self.spec is not None:
            assert self.spec.k + 1 <= self.chunk, (
                f"spec k={self.spec.k} needs k+1 <= chunk "
                f"({self.chunk}): the verify row is [last, d_1..d_k]")
        # -- adaptive spec-k (ISSUE 17 satellite): an EWMA over the
        # observed per-step acceptance rate, folded back through
        # perf_model.choose_spec_k so the LIVE draft width decays to 0
        # on non-self-similar traffic and recovers when acceptance
        # does. spec.k stays the hard cap (the k+1 <= chunk assert is
        # made for it, so adaptation may only narrow rows). Emitted
        # tokens are bitwise unchanged — k widens/narrows what is
        # PROPOSED, and every accepted token is the model's own
        # emission.
        self._spec_ewma: Optional[float] = None
        self._spec_k_live: Optional[int] = None
        self._spec_geom: Optional[dict] = None
        if self.spec is not None and getattr(self.spec, "adaptive",
                                             False):
            cfg = engine.cfg
            n = int(engine.mesh.shape[engine.axis])
            self._spec_geom = dict(
                num_layers=cfg.num_layers, hidden=cfg.hidden_size,
                inter_loc=cfg.intermediate_size // n,
                hq_loc=cfg.num_q_heads // n,
                hkv_loc=cfg.num_kv_heads // n, head_dim=cfg.head_dim,
                vocab_loc=cfg.vocab_size // n, slots=slots,
                kv_tokens=self.pool.t_max, dtype=cfg.dtype)
        # -- radix prefix cache (ISSUE 14, serve/prefix.py): admission
        # matches the prompt against cached token blocks and skips
        # prefill for the hit (KVPool.share — copy-on-write refcounted
        # pages); finished prefills index their prompt blocks back in
        self.prefix = None
        if prefix_cache:
            if isinstance(prefix_cache, PrefixCache):
                # a PrefixCache is bound to its pool, and this
                # scheduler's pool was just constructed above — no
                # caller-built instance can reference it
                raise ValueError(
                    "pass prefix_cache=True (+ prefix_block) and let "
                    "the scheduler build the cache over its own pool")
            if prefix_block is None:
                from triton_dist_tpu.perf_model import (
                    choose_prefix_block,
                )

                cfg = engine.cfg
                n = int(engine.mesh.shape[engine.axis])
                prefix_block = choose_prefix_block(
                    cfg.num_layers, cfg.hidden_size,
                    cfg.intermediate_size // n,
                    cfg.num_q_heads // n, cfg.num_kv_heads // n,
                    cfg.head_dim, cfg.vocab_size // n,
                    page=page, t_max=self.pool.t_max,
                    dtype=cfg.dtype)
            self.prefix = PrefixCache(self.pool, block=prefix_block)
        self.worker = Worker(engine, self.pool, chunk,
                             per_pos=self.spec is not None,
                             spans=self.spans)
        # `queue or ...` would silently DISCARD a custom queue that is
        # currently empty (RequestQueue defines __len__, and an empty
        # queue is falsy) — the admission-control settings a caller
        # configured (max_pending backpressure) would vanish
        self.queue = queue if queue is not None else RequestQueue()
        # -- the fusion plan (ISSUE 17): the scheduler holds the SAME
        # memoized Plan object the engine's decode step executes under
        # (Engine.plan_for -> plan.planner's lru cache), so metrics()
        # and traces can tie serve throughput to the routing the
        # planner chose. None for engine doubles without plan_for.
        self.plan = (engine.plan_for(slots, self.chunk)
                     if hasattr(engine, "plan_for") else None)
        self.max_active = max_active or slots
        self.detok = detokenizer
        self.active: dict = {}  # slot -> Request
        # every request submitted, for metrics() and the ledger.
        # Bounded like `history`: past the cap the oldest RETIRED
        # request is dropped (counted); a live one never is
        self.requests: List[Request] = []
        self.requests_cap = 8192
        self.requests_dropped = 0
        self._requests_lock = threading.Lock()
        self.quarantined: List[Request] = []
        self.max_step_retries = max_step_retries
        self.retry_backoff_s = retry_backoff_s
        self.n_step_retries = 0
        self._admit_seq = 0
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        # -- always-on telemetry (docs/observability.md): the metrics
        # registry every policy decision streams into, the flight
        # recorder that ships context with every faults-plane trip,
        # and the optional SLO monitor feeding the degradation ladder
        self.obs = registry if registry is not None else Registry()
        self.obs.declare_histogram("serve_ttft_us", *LATENCY_BUCKETS)
        self.obs.declare_histogram("serve_tpot_us", *LATENCY_BUCKETS)
        # per-request latency DECOMPOSITION (ISSUE 13): where each
        # retired request's wall time went — streamed at retirement so
        # the /metrics scrape carries the breakdown live
        for name in ("serve_req_queued_us", "serve_req_prefill_us",
                     "serve_req_decode_us", "serve_req_migrate_us",
                     "serve_req_admit_us"):
            self.obs.declare_histogram(name, *LATENCY_BUCKETS)
        # -- disaggregated prefill/decode (ISSUE 18, xslice/migrate):
        # a "prefill" slice runs prefill only and streams finished KV
        # pages out as checksummed wire images; a "decode" slice admits
        # verified arrivals straight into DECODE. "both" (default) is
        # the classic single-slice scheduler — the bit-identity
        # reference the disaggregated pair is measured against.
        assert role in ("both", "prefill", "decode"), role
        self.role = role
        assert role != "prefill" or migrate_to is not None, (
            "role='prefill' needs a migrate_to channel")
        assert role != "decode" or admit_from is not None, (
            "role='decode' needs an admit_from channel")
        self.migrate_to = migrate_to
        self.admit_from = admit_from
        self.migration_format = migration_format
        self.max_migration_retries = max_migration_retries
        self.migration_resend_after = migration_resend_after
        self._mig_seq = 0
        self._mig_pump_round = 0
        # prefill side: seq -> in-flight entry (req, slot, record,
        # retries, sent_step). The slot's pool pages stay HELD until
        # the ack — resend/re-encode needs the source of truth.
        self._migrating: dict = {}
        # decode side: verified-arrival records waiting for capacity,
        # and the seqs already admitted (dedupe of crossed resends)
        self._pending_migrations: deque = deque()
        self._admitted_migrations: set = set()
        # spec acceptance-rate histogram (ISSUE 14): one observation
        # per verify step, accepted/proposed in [0, 1] (a 0.0 lands in
        # the first bucket — the ladder's lo is the resolution floor)
        self.obs.declare_histogram("spec_accept_rate", 0.01, 1.0, 1.25)
        # -- request-scoped attribution (ISSUE 13): per-step
        # slot->request history, the substrate trace/ledger.py
        # folds device time through. Bounded: a long-running server
        # drops the oldest entries (counted) rather than growing
        self.history: List[dict] = []
        self.history_cap = 8192
        self.history_dropped = 0
        self.recorder = recorder if recorder is not None \
            else FlightRecorder(cap=64)
        self.slo = slo
        self.last_flight_dump: Optional[str] = None

    # -- client API -----------------------------------------------------

    def submit(self, prompt, max_new_tokens: int, priority: int = 0,
               temperature: float = 0.0, seed: int = 0,
               eos_id: Optional[int] = None, on_token=None,
               stream: bool = False) -> Request:
        """Enqueue one request (admission control may raise QueueFull).
        Returns the live Request; read req.out_tokens after completion
        or consume req.stream incrementally."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        total = len(prompt) + max_new_tokens
        if total > self.pool.t_max:
            raise ValueError(
                f"prompt+max_new_tokens={total} exceeds the pool "
                f"horizon {self.pool.t_max}"
            )
        if pages_for(total, self.pool.page) > min(self.pool.max_pages,
                                                 self.pool.capacity):
            raise ValueError(
                f"request needs {pages_for(total, self.pool.page)} "
                "pages, beyond what this pool can ever hold"
            )
        req = Request(prompt=prompt, max_new_tokens=max_new_tokens,
                      priority=priority, temperature=temperature,
                      seed=seed, eos_id=eos_id, on_token=on_token,
                      stream=TokenStream() if stream else None)
        # stamp the queued phase BEFORE the request becomes visible to a
        # background serving thread — stamping after queue.submit could
        # overwrite a prefill phase the scheduler thread already opened
        # (a QueueFull rejection leaves only the stamp, never a span)
        self._begin_phase(req, "queued")
        try:
            self.queue.submit(req)
        except QueueFull:
            self.obs.inc("serve_rejected", site="queue_full")
            raise
        self.obs.inc("serve_submitted")
        self._keep_request(req)
        return req

    def _keep_request(self, req: Request) -> None:
        """Append to `requests`; past `requests_cap`, drop the oldest
        retired one. Clients submit from threads of their own, and the
        scan-then-delete must not interleave."""
        with self._requests_lock:
            self.requests.append(req)
            if len(self.requests) > self.requests_cap:
                for i, old in enumerate(self.requests):
                    if old.done:
                        del self.requests[i]
                        self.requests_dropped += 1
                        break

    def cancel(self, req: Request) -> None:
        """Cancel queued or active; the slot frees on the next step."""
        if req.done:
            return
        if req.state is RequestState.QUEUED and self.queue.cancel(req):
            return
        # active — or queue.cancel lost the race with a concurrent
        # admission (threaded mode): flag it for the next step
        if not req.done:
            req.finish_reason = "cancel_requested"  # handled in step()

    # -- the step -------------------------------------------------------

    def step(self) -> bool:
        """One scheduling round: admit, assemble, run ONE device step,
        postprocess. Returns False when there was nothing to do.

        A round is one `sched.step` span whose children name
        what the host was doing (docs/observability.md "Span log"):
        `sched.admit`, `sched.assemble` (with `sched.keys`),
        `worker.step` (`worker.put` / `.launch` / `.wait`),
        `sched.emit`, `sched.observe`."""
        if self._nothing_to_do():
            return False
        step_idx = self.worker.n_steps
        span = self.spans.span
        with span("sched.step", step=step_idx):
            with span("sched.admit", step=step_idx):
                self._reap_cancelled()
                # prefill role: drain acks/nacks and drive the resend
                # ladder BEFORE admitting — an ack frees a slot's pages
                # this round
                mig_busy = self._pump_migration()
                self._admit()
            if not self.active:
                return mig_busy

            with span("sched.assemble", step=step_idx):
                tokens, n_valid, temps, keys, plans = \
                    self._assemble(step_idx)

            if not plans:
                # every slot stalled on pages: evict the most-
                # victimizable to guarantee progress (its pages feed
                # the others)
                victim = min(self.active.values(),
                             key=self._victim_order)
                self._evict(victim, site="progress")
                with span("sched.observe", step=step_idx):
                    self._observe_step()
                return True

            toks = self._run_step(tokens, n_valid, temps, keys, plans)
            if toks is not None:
                with span("sched.emit", step=step_idx):
                    self._fold_step(step_idx, toks, n_valid, plans,
                                    tokens.shape[1])
            # toks None: the step failed beyond its retry budget; the
            # poisoning request is quarantined — survivors rerun next
            # step from unchanged pool state (Worker.step's failure
            # contract)
            with span("sched.observe", step=step_idx):
                self._observe_step()
            return True

    def _nothing_to_do(self) -> bool:
        """A round that could only return False: no slot
        busy, nothing queued, no migration in flight or arrived. Such
        a round leaves no span — an idle server's log holds one
        `sched.idle` for the stretch (start()'s loop), not a record
        every 2 ms. A decode-role slice polls its channel here, and
        parks what it finds for the round's `_admit_migrated`."""
        if (self.active or self._migrating
                or self.queue.peek() is not None):
            return False
        if self.role == "decode" and not self._pending_migrations:
            rec = self.admit_from.recv()
            if rec is not None:
                self._pending_migrations.append(rec)
        return not self._pending_migrations

    def _assemble(self, step_idx: int):
        """The step's arguments from the active slots: (tokens,
        n_valid, temps, keys, plans). A plan is
        (slot, req, n, emits, drafts). `tokens` is (K, W), W the
        narrowest of the worker's widths that holds the longest row
        that stayed in the step: 1 when every row is a decode row, a
        one-token prefill tail or empty; `chunk` when any slot
        prefills more than one token or verifies drafts."""
        spec_on = self.spec is not None
        K, C = self.pool.slots, self.chunk
        n_valid = np.zeros((K,), np.int32)
        temps = np.zeros((K,), np.float32)
        plans, rows = [], {}

        for slot in sorted(self.active):
            req = self.active.get(slot)
            if req is None:  # evicted by an earlier slot's _room call
                continue
            hist = req.history()
            drafts: list = []
            if req.state is RequestState.PREFILL:
                n = min(C, len(hist) - req.pos)
                if not self._room(slot, req, req.pos + n):
                    continue  # stalled this step
                rows[slot] = hist[req.pos:req.pos + n]
                emits = req.pos + n == len(hist)
            else:  # DECODE — possibly a spec-verify row (ISSUE 14)
                if spec_on:
                    cap = draft_cap(self._live_spec_k(), C, len(hist),
                                    len(req.out_tokens),
                                    req.max_new_tokens, self.pool.t_max)
                    if cap > 0:
                        drafts = [int(t) for t in
                                  self.spec.draft.propose(hist, cap)
                                  ][:cap]
                n = 1 + len(drafts)
                if not self._room(slot, req, len(hist) + n):
                    continue
                rows[slot] = [hist[-1]] + drafts
                emits = True
            n_valid[slot] = n
            if emits:
                temps[slot] = req.temperature
            plans.append((slot, req, n, emits, drafts))

        # a later slot's page demand may have evicted an earlier,
        # already-planned request (_room): scrub its row from the step
        plans = [p for p in plans if self.active.get(p[0]) is p[1]]
        live = {p[0] for p in plans}
        for slot in range(K):
            if slot not in live:
                n_valid[slot] = 0
        # the narrowest compiled width that holds the rows that stayed
        longest = int(n_valid.max())
        W = next(w for w in self.worker.widths if w >= longest)
        tokens = np.zeros((K, W), np.int32)
        for slot in live:
            tokens[slot, :n_valid[slot]] = rows[slot]
        keys = np.zeros((K, W, 2) if spec_on else (K, 2), np.uint32)

        # one sampling key an emitted token, all of the step's in one
        # host call, drawn for the rows that stayed in the step. With
        # spec on a row's column base + j emits output index n_out + j
        # (a prefill tail is the one column n - 1)
        with self.spans.span("sched.keys", step=step_idx):
            rows, cols, seeds, idx = [], [], [], []
            for slot, req, n, emits, drafts in plans:
                if not emits:
                    continue
                width = len(drafts) + 1 if spec_on else 1
                rows += [slot] * width
                cols += range(n - width, n)
                seeds += [req.seed] * width
                n_out = len(req.out_tokens)
                idx += range(n_out, n_out + width)
            if rows:
                drawn = sampling_keys(seeds, idx)
                if spec_on:
                    keys[rows, cols] = drawn
                else:
                    keys[rows] = drawn
        return tokens, n_valid, temps, keys, plans

    def _fold_step(self, step_idx: int, toks, n_valid, plans,
                   width: int) -> None:
        """A successful device step back into request state: the
        history entry, the step's counters, accepted drafts, emitted
        tokens, retirements."""
        spec_on = self.spec is not None
        # history walls come from the SUCCESSFUL attempt only — retry
        # walls and backoff sleeps must not inflate the ledger's
        # device-time split (retries are separately visible as
        # step.retry spans + counters)
        t0, t1 = self._attempt_span
        self._record_history({
            "kind": "step", "step": step_idx, "t0": t0, "t1": t1,
            "width": width,
            "slots": {s: (r.request_id, r.state.value, n)
                      for s, r, n, _e, _d in plans},
        })

        emit_plan: dict = {}
        if spec_on:
            # the per-position step did not advance lengths: apply the
            # longest-accepted-prefix rule first, advance by the
            # EMITTED count per verify row (n_valid for prefill rows),
            # then stream the emissions
            advance = np.array(n_valid, np.int32)
            for slot, req, n, emits, drafts in plans:
                if req.state is RequestState.PREFILL:
                    continue
                out = accept_tokens(
                    drafts, toks[slot, :n], eos_id=req.eos_id,
                    max_emit=req.max_new_tokens - len(req.out_tokens))
                emit_plan[slot] = out
                advance[slot] = len(out)
                if drafts:
                    acc = max(len(out) - 1, 0)
                    req.n_spec_steps += 1
                    self.obs.inc("spec_proposed", len(drafts))
                    self.obs.inc("spec_accepted", acc)
                    self.obs.observe("spec_accept_rate",
                                     acc / len(drafts))
                    self._note_accept_rate(acc / len(drafts))
            self.worker.advance_lengths(advance)
        self._count_step(plans, width)

        for slot, req, n, emits, drafts in plans:
            req.last_active_step = self.worker.n_steps
            req.n_device_steps += 1
            if req.state is RequestState.PREFILL:
                req.n_prefill_chunks += 1
                req.pos += n
                if emits:
                    if self.prefix is not None:
                        self._prefix_insert(req, slot)
                    first = int(toks[slot, n - 1] if spec_on
                                else toks[slot])
                    if self.role == "prefill":
                        # THE handoff point: the request would emit its
                        # first token here — instead its KV pages and
                        # that token leave for a decode slice
                        self._migrate_out(req, slot, first)
                    else:
                        self._phase(req, "decode")
                        req.state = RequestState.DECODE
                        self._emit(req, first)
            elif spec_on:
                if drafts:
                    # the verify step's wall, split across the step's
                    # occupants — the ledger's spec_verify sub-bucket
                    # of decode (trace/ledger.py)
                    req.spec_verify_ns += int(
                        (t1 - t0) / max(len(plans), 1))
                for t in emit_plan[slot]:
                    if req.done:
                        break  # eos/length retired mid-batch
                    self._emit(req, int(t))
            else:
                self._emit(req, int(toks[slot]))

    def _count_step(self, plans, width: int) -> None:
        """What one device step worked on, as counters: integers that
        repeat exactly for a seed. Called once the pool's lengths hold
        the step's advance and before any of its requests retires."""
        # which compiled step ran: `wide` is the chunk's, `narrow`
        # any other of the worker's widths
        self.obs.inc("serve_steps",
                     shape="wide" if width == self.chunk else "narrow")
        # rows the step took through the final norm and the head: one
        # a slot, or every column of the per-position (spec) step
        self.obs.inc("serve_head_rows",
                     self.pool.slots * (width if self.worker.per_pos else 1))
        rows = {"prefill": 0, "decode": 0}
        for _slot, req, n, _emits, _drafts in plans:
            rows[req.state.value] += n
        for state, n in rows.items():
            self.obs.inc("serve_rows", n, state=state)
        live = self.pool.live_tokens(p[0] for p in plans)
        gathered = self.pool.dense_view_tokens()
        self.obs.inc("serve_kv_tokens_live", live)
        self.obs.inc("serve_kv_tokens_gathered", gathered)
        # the same positions in pool bytes, all page layers together:
        # a latent row and a key-value row read in one unit
        per_token = self.pool.kv_bytes_per_token
        self.obs.inc("serve_kv_bytes_live", per_token * live)
        self.obs.inc("serve_kv_bytes_gathered", per_token * gathered)
        cfg = self.pool.engine.cfg
        if not cfg.is_hybrid:
            return
        # the hybrid family: what a slot carries beside the pages (the
        # step reads and writes every slot's) and the expert layer's
        # routing, counted by the step on the device
        for name, per_slot in (
                ("state", self.pool.state_bytes_per_slot),
                ("window", self.pool.window_bytes_per_slot)):
            if per_slot:
                self.obs.inc(f"serve_{name}_bytes_live",
                             per_slot * len(plans))
                self.obs.inc(f"serve_{name}_bytes_moved",
                             per_slot * self.pool.slots)
        self.obs.inc("serve_state_resets", sum(
            1 for slot, _r, n, _e, _d in plans
            if int(self.pool.lengths[slot]) == n))
        # {} from a pattern without an expert block: the counters read
        # 0 and the step spends nothing on them
        stats = self.worker.last_stats
        self.obs.inc("moe_pairs", stats.get("moe_pairs_here", 0),
                     held="here")
        self.obs.inc("moe_pairs", stats.get("moe_pairs_absent", 0),
                     held="absent")
        for name in ("moe_gmm_tile_rows", "moe_combine_rows",
                     "moe_combine_pairs"):
            self.obs.inc(name, stats.get(name, 0))
        self.obs.inc("moe_expert_steps",
                     cfg.num_moe_layers * cfg.num_experts_held)

    def _attempt_with_backoff(self, retry_span, body):
        """The retrying half of the degradation ladder: run `body` with
        bounded exponential-backoff retries, streaming the retry
        bookkeeping (retry counters by fault class, guard-trip
        counters by site, one `retry_span` record a failed attempt)
        every attempt. Returns
        (result, None) on success or (None, last_err) on exhaustion —
        what exhaustion MEANS (quarantine a victim) stays with the
        caller. Only FaultError is degradable — a programming error
        stays loud."""
        delay = self.retry_backoff_s
        last_err = None
        for attempt in range(self.max_step_retries + 1):
            t0 = time.perf_counter_ns()
            try:
                result = body()
                # the ATTEMPT's own wall (no backoff sleeps, no earlier
                # failed attempts) — what the ledger's device-time
                # split may honestly call device time
                self._attempt_span = (t0, time.perf_counter_ns())
                return result, None
            except FaultError as e:
                self._attempt_span = (t0, time.perf_counter_ns())
                last_err = e
                self.n_step_retries += 1
                self.obs.inc("serve_retries", site=type(e).__name__)
                self._count_guard_trips(e)
                self.spans.add(retry_span, t0, time.perf_counter_ns(),
                               step=self.worker.n_steps)
                if attempt < self.max_step_retries:
                    time.sleep(delay)
                    delay = min(delay * 2, 0.25)
        return None, last_err

    def _run_step(self, tokens, n_valid, temps, keys, plans):
        """The degradation ladder around the device step: bounded
        exponential-backoff retries, then quarantine of the suspected
        poisoner. Returns the per-slot tokens, or None when the step
        was abandoned this round (survivors rerun next step)."""
        body = (self.worker.step_spec if self.worker.per_pos
                else self.worker.step)
        toks, err = self._attempt_with_backoff(
            "step.retry", lambda: body(tokens, n_valid, temps, keys))
        if err is None:
            return toks
        victim = max((req for _slot, req, _n, _e, _d in plans),
                     key=lambda r: r.admit_seq)
        self._quarantine(victim, err)
        return None

    def _record_history(self, entry: dict) -> None:
        self.history.append(entry)
        if len(self.history) > self.history_cap:
            del self.history[0]
            self.history_dropped += 1

    # -- adaptive spec-k (ISSUE 17 satellite) ---------------------------

    def _note_accept_rate(self, rate: float) -> None:
        """Fold one verify row's acceptance into the adaptive-k EWMA
        (a no-op unless SpecConfig.adaptive)."""
        if self._spec_geom is None:
            return
        a = self.spec.ewma_alpha
        prev = self._spec_ewma
        self._spec_ewma = rate if prev is None else (
            a * rate + (1.0 - a) * prev)
        self._spec_k_live = None  # re-priced lazily at next draft_cap

    def _live_spec_k(self) -> int:
        """The draft width the NEXT verify row may carry: spec.k until
        the EWMA has evidence, then choose_spec_k(accept_rate=ewma)
        capped at spec.k (the chunk assert is made for spec.k —
        adaptation only narrows).
        choose_spec_k is monotone in accept_rate, so sustained
        non-self-similar traffic decays the live k to 0 (spec
        effectively OFF) and self-similar traffic restores it."""
        if self._spec_geom is None or self._spec_ewma is None:
            return self.spec.k
        if self._spec_k_live is None:
            from triton_dist_tpu.perf_model import choose_spec_k

            self._spec_k_live = min(self.spec.k, choose_spec_k(
                accept_rate=self._spec_ewma, k_max=self.spec.k,
                **self._spec_geom))
        return self._spec_k_live

    def _count_guard_trips(self, err) -> None:
        """Guard-trip counters by wait site (the decoded rows a
        DeadlineExceeded carries; a trip-less FaultError counts at its
        class name, so injected host-level faults are visible too)."""
        trips = getattr(err, "trips", None) or []
        if not trips:
            self.obs.inc("serve_guard_trips", site=type(err).__name__)
            return
        for t in trips:
            self.obs.inc("serve_guard_trips", site=t.site_label)

    def _quarantine(self, req: Request, err) -> None:
        """Retire the suspected poisoner as FAILED (stream closes, the
        client unblocks with a structured reason); its pages feed the
        survivors. The flight recorder dumps here: every quarantine
        ships the ring of step snapshots — registry deltas, gauges,
        scheduler state, and the decoded guard rows of the fatal error
        — so the trip arrives with its context (docs/observability.md
        "Flight recorder")."""
        now = time.perf_counter_ns()
        self.spans.add("req.quarantined", now, now,
                       step=self.worker.n_steps, request=req.request_id)
        self.quarantined.append(req)
        self.obs.inc("serve_quarantined")
        self._retire(req, f"quarantined: {err!r}", RequestState.FAILED)
        self.recorder.record(registry=self.obs,
                             scheduler_state=self._state_summary(),
                             error=err, step=self.worker.n_steps)
        try:
            self.last_flight_dump = self.recorder.dump(
                reason=f"quarantine req{req.request_id}: {err!r}"[:200])
        except OSError:
            pass  # an unwritable dump dir must not kill the batch

    def run(self, max_steps: int = 100_000) -> None:
        """Drive steps until queue and slots drain."""
        for _ in range(max_steps):
            if not self.step() and self.queue.peek() is None:
                return
        raise RuntimeError(f"scheduler did not drain in {max_steps} steps")

    def start(self) -> None:
        """Background serving thread (the socket-server mode,
        examples/11). A step failure must not strand streaming clients:
        the loop fails every live request (closing its stream) and
        parks the error on `self.error` instead of dying silently."""
        assert self._thread is None, "already started"
        self._stop.clear()
        self.error: Optional[BaseException] = None

        def loop():
            # one `sched.idle` span a STRETCH of rounds with nothing to
            # do, closed when the next round finds work (or at stop())
            idle_t0 = None
            while not self._stop.is_set():
                t_round = time.perf_counter_ns()
                try:
                    idle = not self.step()
                except BaseException as e:  # noqa: BLE001 — see docstring
                    self.error = e
                    # the thread is dying: ship the flight-recorder
                    # context (ring + this error's guard rows) before
                    # the clients are failed — a dump failure must not
                    # mask the original error
                    try:
                        self.recorder.record(
                            registry=self.obs,
                            scheduler_state=self._state_summary(),
                            error=e, step=self.worker.n_steps)
                        self.last_flight_dump = self.recorder.dump(
                            reason=f"scheduler error: {e!r}"[:200])
                    except OSError:
                        pass
                    self._fail_all(f"scheduler error: {e!r}")
                    return
                if idle:
                    if idle_t0 is None:
                        idle_t0 = t_round
                    time.sleep(0.002)
                elif idle_t0 is not None:
                    self.spans.add("sched.idle", idle_t0, t_round)
                    idle_t0 = None
            if idle_t0 is not None:
                self.spans.add("sched.idle", idle_t0,
                               time.perf_counter_ns())

        self._thread = threading.Thread(target=loop, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        if self._thread is not None:
            self._stop.set()
            self._thread.join(timeout=30)
            self._thread = None
            if getattr(self, "error", None) is not None:
                raise RuntimeError(
                    "serving thread died on an error"
                ) from self.error

    def _fail_all(self, reason: str) -> None:
        """Retire every live request (streams close, clients unblock)."""
        for slot in list(self.active):
            self._retire(self.active[slot], reason,
                         RequestState.CANCELLED)
        for seq in list(self._migrating):
            ent = self._migrating.pop(seq)
            self.pool.release(ent["slot"])
            if not ent["req"].done:
                ent["req"]._finish(reason, RequestState.CANCELLED)
        req = self.queue.pop()
        while req is not None:
            req._finish(reason, RequestState.CANCELLED)
            req = self.queue.pop()

    # -- metrics / observability ---------------------------------------

    def _state_summary(self) -> dict:
        """The scheduler-state block of a flight-recorder snapshot."""
        return {
            "n_steps": self.worker.n_steps,
            "active": {int(s): r.request_id
                       for s, r in self.active.items()},
            "queue_depth": len(self.queue),
            "step_retries": self.n_step_retries,
            "quarantined": len(self.quarantined),
            "role": self.role,
            "migrating": len(self._migrating),
        }

    def _observe_step(self) -> None:
        """Per-step telemetry: pressure gauges, the step counter, one
        flight-recorder ring entry, and the SLO evaluation that feeds
        the degradation ladder. O(registry size) host work — the
        always-on budget."""
        self.obs.inc("serve_steps")
        self.obs.set_gauge("serve_queue_depth", len(self.queue))
        self.obs.set_gauge("serve_active_slots", len(self.active))
        self.obs.set_gauge("serve_pool_free_pages",
                           self.pool.free_pages())
        self.obs.set_gauge("serve_pool_used_pages",
                           self.pool.used_pages())
        self.obs.set_gauge(
            "serve_pool_occupancy",
            self.pool.used_pages() / max(self.pool.capacity, 1))
        self.recorder.record(registry=self.obs,
                             scheduler_state=self._state_summary(),
                             step=self.worker.n_steps)
        if self.slo is not None:
            self.slo.feed(self.obs)

    def metrics(self) -> dict:
        """The serving metrics schema (docs/observability.md pins the
        key families; tests/test_serve.py pins keys-travel-together and
        counter monotonicity). Latency summary keys come from
        `summarize` — whose quantiles now run on the same registry
        Histogram definition — plus the registry's policy counters and
        pressure gauges, and the SLO health block when a monitor is
        attached."""
        out = summarize(self.requests)
        out["quarantined"] = len(self.quarantined)
        out["step_retries"] = self.n_step_retries
        snap = self.obs.snapshot()["counters"]
        for key, name in (
            ("submitted", "serve_submitted"),
            ("rejected", "serve_rejected{site=queue_full}"),
            ("admitted", "serve_admitted"),
            ("evicted", "serve_evicted"),
            ("preempted", "serve_evicted{site=preemption}"),
            ("retries", "serve_retries"),
            ("guard_trips", "serve_guard_trips"),
            ("steps", "serve_steps"),
            ("tokens_out", "serve_tokens_out"),
        ):
            base, _, _ = name.partition("{")
            if "{" in name:
                out[key] = snap.get(name, 0)
            else:
                out[key] = sum(v for k, v in snap.items()
                               if k == base or k.startswith(base + "{"))
        out["queue_depth"] = len(self.queue)
        out["active_slots"] = len(self.active)
        out["pool_free_pages"] = self.pool.free_pages()
        out["pool_used_pages"] = self.pool.used_pages()
        # prefix + spec planes (ISSUE 14) — always present (0 when the
        # plane is off) so dashboards never lose the keys
        out["prefix_hits"] = snap.get("serve_prefix_hits", 0)
        out["prefix_misses"] = snap.get("serve_prefix_misses", 0)
        out["prefix_pages_shared"] = snap.get(
            "serve_prefix_pages_shared", 0)
        out["prefix_blocks"] = (self.prefix.n_blocks()
                                if self.prefix is not None else 0)
        out["spec_proposed"] = snap.get("spec_proposed", 0)
        out["spec_accepted"] = snap.get("spec_accepted", 0)
        out["spec_accept_rate"] = round(
            out["spec_accepted"] / out["spec_proposed"], 4
        ) if out["spec_proposed"] else 0.0
        # the LIVE draft width (adaptive spec-k, ISSUE 17): equals the
        # configured k until the EWMA has evidence or when adaptation
        # is off; 0 when the spec plane is off entirely
        out["spec_k_live"] = (self._live_spec_k()
                              if self.spec is not None else 0)
        # disaggregated prefill/decode plane (ISSUE 18) — always
        # present (0 when role="both") so dashboards keep the keys
        out["role"] = self.role
        out["migrations_out"] = snap.get("serve_migrations_out", 0)
        out["migrations_in"] = snap.get("serve_migrations_in", 0)
        out["migrations_acked"] = snap.get("serve_migrations_acked", 0)
        out["migrations_nacked"] = snap.get("serve_migrations_nacked",
                                            0)
        out["migrations_resent"] = snap.get("serve_migrations_resent",
                                            0)
        out["migrations_failed"] = snap.get("serve_migrations_failed",
                                            0)
        out["migrations_rejected"] = sum(
            v for k, v in snap.items()
            if k.startswith("serve_migrations_rejected"))
        out["migrations_inflight"] = len(self._migrating)
        out["migrations_pending_admit"] = len(self._pending_migrations)
        if self.plan is not None:
            out["plan_id"] = self.plan.plan_id
            # tune-cache winners riding this plan (site -> config via
            # Plan.applied_configs); 0 = every kernel on default tiles
            out["plan_applied_configs"] = len(self.plan.applied_configs())
        if self.slo is not None and self.slo.last is not None:
            out["health"] = self.slo.last.to_dict()
        return out

    def timeline(self):
        """The span log as a trace.Timeline (host spans only: request
        lifecycle phases, each round's phases, retries) —
        write_trace() exports it to Perfetto beside the in-kernel
        traces."""
        from triton_dist_tpu.trace.collect import Timeline

        return Timeline(events=[], spans=[], drops={},
                        host_spans=self.spans.triples(), label="serve")

    def ledger(self, tol: float = 0.05):
        """The per-request attribution ledger (ISSUE 13): TTFT/TPOT
        decomposed per retired request — queued / prefill / decode
        wall, device-step share — built from
        the phase accumulators plus the slot history. See
        trace/ledger.py for the close contract (phase sums vs wall
        within `tol`)."""
        from triton_dist_tpu.trace.ledger import build_ledger

        return build_ledger(self, tol=tol)

    # -- internals ------------------------------------------------------

    def _room(self, slot: int, req: Request, upto: int) -> bool:
        if self.pool.ensure(slot, upto):
            return True
        if self.prefix is not None:
            # pool pressure reclaims UNSHARED cached blocks before any
            # live request is evicted; blocks whose pages a live slot
            # still reads are skipped (the refcount>1 refusal —
            # serve/prefix.py, chaos cell pool_pressure_shared).
            # Reclaim only the DEFICIT beyond the free list — the
            # admission paths' rule — so mild pressure never thrashes
            # the whole cache
            need = (pages_for(upto, self.pool.page)
                    - self.pool.used_pages(slot)
                    - self.pool.free_pages())
            if self.prefix.reclaim(need) > 0 \
                    and self.pool.ensure(slot, upto):
                return True
        victim = self._pick_victim(req)
        while victim is not None:
            self._evict(victim, site="growth")
            if self.pool.ensure(slot, upto):
                return True
            victim = self._pick_victim(req)
        return False

    def _prefix_insert(self, req: Request, slot: int) -> None:
        """Index a freshly completed prefill's prompt blocks (the
        PREFILL -> DECODE transition): the trie increfs the slot's
        pages — no copy — so the next templated prompt admission
        shares them."""
        self.prefix.insert(req.prompt, self.pool.table[slot])

    @staticmethod
    def _victim_order(a: Request):
        # most victimizable first: lowest priority, least recently
        # active (LRU), youngest admission
        return (a.priority, a.last_active_step, -a.admit_seq)

    def _pick_victim(self, requester: Request) -> Optional[Request]:
        """Strictly 'younger-or-lower' victims relative to the
        requester — a total order (admit_seq is unique), so two slots
        can never evict each other in turns."""
        cands = [
            a for a in self.active.values()
            if a is not requester
            and (a.priority < requester.priority
                 or (a.priority == requester.priority
                     and a.admit_seq > requester.admit_seq))
        ]
        return min(cands, key=self._victim_order) if cands else None

    def _match_prefix(self, req: Request):
        """Trie lookup for an admission: (matched tokens, shared
        pages) — (0, []) without a cache. The hit/miss accounting
        happens at the ADMISSION that uses the match (not here — a
        stalled admission retries the lookup every round)."""
        if self.prefix is None:
            return 0, []
        return self.prefix.match(req.history())

    def _reclaim_and_rematch(self, req: Request, total: int):
        """The prefix-cache pressure valve of admission: match, and if
        the fresh-page need outruns the free list, reclaim the DEFICIT
        from unshared cached blocks and RE-match — the reclaim may
        have dropped nodes on the matched path itself (an unshared hit
        is a valid LRU victim), and stale mpages would share freed
        pages. Returns (m, mpages,
        fresh_need) for a `total`-token allocation."""
        m, mpages = self._match_prefix(req)
        need = max(pages_for(total, self.pool.page), 1) - len(mpages)
        if self.prefix is not None and self.pool.free_pages() < need:
            self.prefix.reclaim(need - self.pool.free_pages())
            m, mpages = self._match_prefix(req)
            need = max(pages_for(total, self.pool.page),
                       1) - len(mpages)
        return m, mpages, need

    def _note_prefix(self, m: int, mpages) -> None:
        """Hit/miss accounting for one successful admission."""
        if self.prefix is None:
            return
        if m > 0:
            self.prefix.hits += 1
            self.prefix.tokens_reused += m
            self.obs.inc("serve_prefix_hits")
            self.obs.inc("serve_prefix_pages_shared", len(mpages))
        else:
            self.prefix.misses += 1
            self.obs.inc("serve_prefix_misses")

    # -- disaggregated prefill/decode (ISSUE 18) ------------------------

    def _migrate_out(self, req: Request, slot: int,
                     first_token: int) -> None:
        """Prefill-role handoff: encode the slot's KV pages as a
        checksummed wire image and ship them (+ the first token) to the
        decode slice. The slot leaves `active` but its pool pages stay
        HELD until the ack — the resend/re-encode ladder reads them. A
        request that RETIRES on its first token (max_new_tokens == 1 or
        eos) has no decode work to hand off: it finishes locally,
        bitwise the single-slice run."""
        from triton_dist_tpu.xslice.migrate import (
            MigrationRecord, encode_pages,
        )

        if req.max_new_tokens <= 1 or (req.eos_id is not None
                                       and first_token == req.eos_id):
            self._phase(req, "decode")
            req.state = RequestState.DECODE
            self._emit(req, first_token)  # retires via _emit
            return
        self._phase(req, "migrate")
        n_tokens = len(req.prompt)
        k, v = self.pool.export_pages(slot, n_tokens)
        payload = encode_pages(k, v, self.migration_format)
        seq = self._mig_seq
        self._mig_seq += 1
        rec = MigrationRecord(
            seq=seq, request_id=req.request_id,
            prompt=tuple(req.prompt), n_tokens=n_tokens,
            first_token=first_token, payload=payload,
            meta=dict(max_new_tokens=req.max_new_tokens,
                      temperature=req.temperature, seed=req.seed,
                      eos_id=req.eos_id, priority=req.priority),
            req=req)
        del self.active[slot]
        self._migrating[seq] = dict(req=req, slot=slot, record=rec,
                                    retries=0,
                                    sent_step=self._mig_pump_round)
        self.migrate_to.send(rec)
        self.obs.inc("serve_migrations_out")

    def _pump_migration(self) -> bool:
        """Prefill-role ack pump + resend ladder. An ack releases the
        held pages; a nack RE-ENCODES from the still-held pages and
        resends; an unacked record resends after `resend_after` own
        steps; the retry budget exhausting fails the request loudly
        (never silently). Returns True while migrations are in
        flight (keeps step() reporting work to do)."""
        if self.role != "prefill" or not self._migrating:
            return bool(self._migrating)
        # resend aging counts PUMP rounds, not device steps — an
        # otherwise-idle prefill slice (nothing left to prefill) never
        # advances worker.n_steps, and the ladder must still fire
        self._mig_pump_round += 1
        for verb, seq in self.migrate_to.pump_acks():
            ent = self._migrating.get(seq)
            if ent is None:
                continue  # duplicate ack after a resend race
            if verb == "ack":
                self._migrating.pop(seq)
                self.pool.release(ent["slot"])
                self.obs.inc("serve_migrations_acked")
            else:  # nack: corrupted arrival — re-encode and resend
                self.obs.inc("serve_migrations_nacked")
                self._mig_resend(seq, ent, reencode=True)
        for seq, ent in list(self._migrating.items()):
            if (self._mig_pump_round - ent["sent_step"]
                    >= self.migration_resend_after):
                self._mig_resend(seq, ent, reencode=False)
        return bool(self._migrating)

    def _mig_resend(self, seq: int, ent: dict, reencode: bool) -> None:
        from triton_dist_tpu.xslice.migrate import encode_pages

        ent["retries"] += 1
        if ent["retries"] > self.max_migration_retries:
            self._migrating.pop(seq)
            self.pool.release(ent["slot"])
            req = ent["req"]
            req._finish(
                f"migration failed after {self.max_migration_retries} "
                "retries", RequestState.FAILED)
            self.obs.inc("serve_migrations_failed")
            return
        if reencode:
            rec = ent["record"]
            k, v = self.pool.export_pages(ent["slot"], rec.n_tokens)
            rec.payload = encode_pages(k, v, self.migration_format)
        ent["sent_step"] = self._mig_pump_round
        self.migrate_to.send(ent["record"])
        self.obs.inc("serve_migrations_resent")

    def _admit_migrated(self) -> None:
        """Decode-role admission: verified arrivals first (they already
        spent a prefill slice's work), then the local queue. Admission
        GATES on decode_pages — a corrupted image NACKs and is dropped
        here; capacity shortfall parks the verified record until pages
        free."""
        from triton_dist_tpu.xslice.migrate import (
            MigrationError, decode_pages,
        )

        if self.role != "decode":
            return
        while len(self.active) < self.max_active:
            if self._pending_migrations:
                rec = self._pending_migrations.popleft()
            else:
                rec = self.admit_from.recv()
                if rec is None:
                    return
            if rec.seq in self._admitted_migrations:
                # a resend crossed our ack in flight: re-ack, drop dup
                self.admit_from.ack(rec.seq)
                continue
            slot = self.pool.free_slot()
            if slot is None or self.pool.free_pages() < max(
                    pages_for(rec.n_tokens, self.pool.page), 1):
                self._pending_migrations.appendleft(rec)
                return
            # the passenger (in-process pair) moves phases now; a
            # cross-process record has no req yet — it is only built
            # once the image VERIFIES (no zombie on the nack path)
            if rec.req is not None:
                self._phase(rec.req, "admit")
            try:
                kp, vp = decode_pages(rec.payload)
            except MigrationError as e:
                # detected, never admitted: the prefill slice re-encodes
                if rec.req is not None:
                    self._phase(rec.req, "migrate")  # back in flight
                self.admit_from.nack(rec.seq)
                self.obs.inc("serve_migrations_rejected",
                             site=type(e).__name__)
                continue
            req = rec.req
            if req is None:
                req = Request(
                    prompt=list(rec.prompt),
                    max_new_tokens=rec.meta["max_new_tokens"],
                    priority=rec.meta["priority"],
                    temperature=rec.meta["temperature"],
                    seed=rec.meta["seed"], eos_id=rec.meta["eos_id"])
                req.request_id = rec.request_id  # keep the origin id
                req.t_submit = time.perf_counter_ns()
                self._keep_request(req)
                self._begin_phase(req, "admit")
            try:
                self.pool.install(slot, kp, vp, rec.n_tokens)
            except PoolExhausted:
                self._pending_migrations.appendleft(rec)
                return
            req.slot = slot
            req.pos = rec.n_tokens
            req.state = RequestState.DECODE
            req.admit_seq = self._admit_seq
            self._admit_seq += 1
            self.active[slot] = req
            self.obs.inc("serve_admitted")
            self.obs.inc("serve_migrations_in")
            self._phase(req, "decode")
            # the traveling first token: emitted HERE, single producer
            self._emit(req, int(rec.first_token))
            self.admit_from.ack(rec.seq)
            self._admitted_migrations.add(rec.seq)

    def _admit(self) -> None:
        self._admit_migrated()
        while len(self.active) < self.max_active:
            req = self.queue.peek()
            if req is None:
                return
            slot = self.pool.free_slot()
            m, mpages, need = 0, [], 1
            if slot is not None:
                # the prefix match + cache pressure valve (reclaim
                # unshared blocks before touching live requests)
                m, mpages, need = self._reclaim_and_rematch(
                    req, len(req.history()))
            if slot is None or self.pool.free_pages() < need:
                # a strictly higher-priority arrival may preempt
                cands = [a for a in self.active.values()
                         if a.priority < req.priority]
                if not cands:
                    return
                self._evict(min(cands, key=self._victim_order),
                            site="preemption")
                continue
            self.queue.pop()
            try:
                if m > 0:
                    self.pool.share(slot, mpages, len(req.history()))
                else:
                    self.pool.admit(slot, len(req.history()))
            except PoolExhausted:  # raced with nothing; be safe
                self.queue.requeue(req)
                return
            req.slot = slot
            # a prefix hit resumes prefill AFTER the cached coverage:
            # the shared pages already hold positions [0, m), and the
            # emitted stream stays bitwise a cold run's (docs/
            # serving.md "Prefix reuse" — the tier-1-pinned property)
            req.pos = m
            req.prefix_len = m
            req.state = RequestState.PREFILL
            req.admit_seq = self._admit_seq
            self._admit_seq += 1
            self.active[slot] = req
            self.obs.inc("serve_admitted")
            self._note_prefix(m, mpages)
            self._phase(req, "prefill")

    def _evict(self, req: Request, site: str = "growth") -> None:
        self.pool.release(req.slot)
        del self.active[req.slot]
        req.slot = -1
        req.pos = 0
        req.prefix_len = 0  # re-admission re-matches the trie
        req.n_evictions += 1
        self.obs.inc("serve_evicted", site=site)
        now = time.perf_counter_ns()
        self.spans.add("req.evicted", now, now, step=self.worker.n_steps,
                       request=req.request_id)
        self._phase(req, "queued")
        self.queue.requeue(req)

    def _emit(self, req: Request, tok: int) -> None:
        piece = self.detok.piece(tok) if self.detok else None
        req._emit(tok, piece)
        self.obs.inc("serve_tokens_out")
        if (req.eos_id is not None and tok == req.eos_id) \
                or len(req.out_tokens) >= req.max_new_tokens:
            reason = ("eos" if req.eos_id is not None
                      and tok == req.eos_id else "length")
            self._retire(req, reason, RequestState.FINISHED)

    def _observe_retired(self, req: Request) -> None:
        """TTFT/TPOT stream into the registry histograms at retirement
        — the live (continuously mergeable) form of what `summarize`
        computes offline over the finished list."""
        if req.state is not RequestState.FINISHED or not req.token_times:
            return
        self.obs.observe("serve_ttft_us", req.ttft_us())
        if req.tpot_us() is not None:
            self.obs.observe("serve_tpot_us", req.tpot_us())
        # the latency DECOMPOSITION histograms (ISSUE 13): where the
        # retired request's wall time went, by lifecycle phase — the
        # live form of the request ledger's phase columns
        for phase, name in (("queued", "serve_req_queued_us"),
                            ("prefill", "serve_req_prefill_us"),
                            ("migrate", "serve_req_migrate_us"),
                            ("admit", "serve_req_admit_us"),
                            ("decode", "serve_req_decode_us")):
            ns = req.phase_ns.get(phase)
            if ns is not None:
                self.obs.observe(name, ns / 1e3)

    def _retire(self, req: Request, reason: str, state) -> None:
        self.pool.release(req.slot)
        del self.active[req.slot]
        req.slot = -1
        self._end_phase(req)
        req._finish(reason, state)
        self._observe_retired(req)

    def _reap_cancelled(self) -> None:
        for slot in list(self.active):
            req = self.active[slot]
            if req.finish_reason == "cancel_requested":
                self._retire(req, "cancelled", RequestState.CANCELLED)

    # -- span bookkeeping ----------------------------------------------

    def _begin_phase(self, req: Request, name: str) -> None:
        req._phase = (name, time.perf_counter_ns())

    def _end_phase(self, req: Request) -> None:
        ph = getattr(req, "_phase", None)
        if ph is not None:
            name, t0 = ph
            now = time.perf_counter_ns()
            self.spans.add("req." + name, t0, now,
                           request=req.request_id)
            # accumulate into the per-request phase ledger (ISSUE 13):
            # an evicted request re-accumulates queued/prefill, so the
            # sum over phases closes against submit->finish wall time
            req.phase_ns[name] = req.phase_ns.get(name, 0) + (now - t0)
            req._phase = None

    def _phase(self, req: Request, name: str) -> None:
        self._end_phase(req)
        self._begin_phase(req, name)
