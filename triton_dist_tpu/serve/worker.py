"""Worker — replays the engine's jit'd serve steps over the pool, one
compiled step a width.

The scheduler/worker split of the Engine (ROADMAP item 1): the
Scheduler decides WHAT runs each step (which slots, which tokens, how
many are real, and so how wide the step is); the Worker is the only
component that touches the device — it materializes the step
arguments, replays the compiled executable `engine.make_serve_step`
built for the step's width (the CUDA-graph-replay analog: a closed set
of shapes, `engine.serve_widths(chunk)`, fixed at construction; the
same shapes every step of one width, whatever the batch mixes), and
folds the results back into the pool. A request's tokens are bitwise
a function of its history and of the widths of the steps that
computed them; across widths the logits agree to the tolerance of two
correct formulations in the model's precision (docs/serving.md).

`ResidentWorker` is the megakernel-resident form (ISSUE 12): instead
of one device dispatch per step, the scheduler's decisions travel as
work-injection ring records (mega.ring) and the Worker launches the
device-RESIDENT window `engine.make_resident_loop` compiled — up to W
steps per dispatch, decode self-fed on device, completions drained
from the mirrored output ring afterwards. The Worker is the ring
producer (admit/retire records) AND the output-ring consumer; every
window launch is a bounded watchdog wait — an abandoned ring (starved
window) or a windows-long stretch with zero progress raises a
structured `DeadlineExceeded` guard trip, never a hang.
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from triton_dist_tpu.faults import plan as _fplan
from triton_dist_tpu.faults.errors import DeadlineExceeded
from triton_dist_tpu.mega import ring as mring
from triton_dist_tpu.obs.spans import SpanLog
from triton_dist_tpu.serve.kv_pool import KVPool


# Threefry-2x32 rotations (Salmon et al. 2011) as (left, right) shift
# pairs, in the two alternating groups of four rounds jax._src.prng
# applies them in
_ROTATIONS = tuple(
    tuple((np.uint32(r), np.uint32(32 - r)) for r in group)
    for group in ((13, 15, 26, 6), (17, 29, 16, 24)))


def check_prng_impl() -> None:
    """`sampling_keys` and the step's `(K, 2) uint32` keys are
    threefry2x32 key data: refuse to build a worker under any other
    default PRNG implementation, where the host's keys and the traced
    derivation (mega.ring) would part ways in silence."""
    impl = jax.config.jax_default_prng_impl
    if impl != "threefry2x32":
        raise RuntimeError(
            "serve sampling keys are threefry2x32 key data, but "
            f"jax_default_prng_impl is {impl!r}")


def sampling_keys(seeds, token_indices) -> np.ndarray:
    """Per-(request, token) sampling keys, `(..., 2) uint32` for integer
    arrays of any equal (or broadcastable) shape: derived from the
    request seed and the OUTPUT TOKEN INDEX only, so sampled tokens —
    like greedy ones — are invariant to scheduling and eviction. THE
    single host derivation: host-loop Worker, ResidentWorker and
    spec-verify all come through here, and the device key stream
    (mega.ring) reproduces it traced.

    Bitwise `jax.random.fold_in(jax.random.PRNGKey(seed), index)` under
    threefry2x32 (`check_prng_impl`), computed in numpy with no JAX
    call, no device dispatch and no readback: one threefry-2x32 block
    with key words [0, seed mod 2**32] (PRNGKey of a 32-bit seed) and
    counter words [0, index mod 2**32] (fold_in's threefry_seed)."""
    k1 = np.asarray(seeds, np.int64).astype(np.uint32)  # the low 32 bits
    ks = (np.uint32(0), k1, k1 ^ np.uint32(0x1BD11BDA))
    x1 = np.asarray(token_indices, np.int64).astype(np.uint32) + k1
    # The two words are views of the result, updated in place by few
    # kinds of numpy call: the host comes to this cold after a device
    # step, where the first call of each kind costs more than all the
    # rounds of a step's handful of keys (PERF.md, PR 28).
    out = np.empty(x1.shape + (2,), np.uint32)
    out[..., 0] = 0  # counter word 0 + ks[0]
    out[..., 1] = x1  # counter word 1 + ks[1]
    x0, x1 = out[..., 0], out[..., 1]
    for i in range(5):
        for left, right in _ROTATIONS[i % 2]:
            x0 += x1
            high = x1 << left
            x1 >>= right
            x1 |= high
            x1 ^= x0
        x0 += ks[(i + 1) % 3]
        x1 += ks[(i + 2) % 3]
        x1 += np.uint32(i + 1)
    return out


def sampling_key(seed: int, token_index: int) -> np.ndarray:
    """One key of `sampling_keys`, `(2,) uint32`."""
    return sampling_keys(seed, token_index)


class Worker:
    def __init__(self, engine, pool: KVPool, chunk: int,
                 per_pos: bool = False,
                 spans: Optional[SpanLog] = None):
        self.engine = engine
        self.pool = pool
        self.chunk = chunk
        self.per_pos = per_pos
        check_prng_impl()
        # one compiled step a width, all built here and first called
        # in the caller's warm-up (nothing compiles lazily behind a
        # step's shape). `_fn` is the WIDEST (`chunk`) and is looked
        # up on the instance at call time, so a wrapper set on the
        # attribute sees the wide calls (perfbench's StepListing)
        self.widths = tuple(engine.serve_widths(chunk))
        assert self.widths[-1] == chunk, (self.widths, chunk)
        steps = {w: engine.make_serve_step(pool.slots, w, pool.page,
                                           pool.max_pages,
                                           per_pos=per_pos)
                 for w in self.widths}
        self._fn = steps.pop(chunk)
        self._narrow = steps
        self.n_steps = 0
        # what the newest step counted on the device (the step's
        # `stats` result as host ints; {} for the dense family)
        self.last_stats: dict = {}
        # the scheduler hands its log over; a worker driven alone
        # keeps one of its own
        self.spans = spans if spans is not None else SpanLog()

    key_for = staticmethod(sampling_key)

    def step(self, tokens: np.ndarray, n_valid: np.ndarray,
             temps: np.ndarray, keys: np.ndarray) -> np.ndarray:
        """One serve step. tokens (K, W) i32, W one of `widths` /
        n_valid (K,) i32 / temps (K,) f32 / keys (K, 2) u32. Advances
        pool lengths by
        n_valid and returns the per-slot next token (K,) i32 — only
        slots whose chunk just completed (prefill tail or decode) carry
        a meaningful token; the scheduler knows which.

        Failure contract: raises BEFORE touching pool state (lengths
        advance only on success), so a failed step is safely retryable
        — the scheduler's degradation ladder depends on it. An active
        FaultPlan's FailStep(at_step=n_steps) injects the failure here
        (n_steps counts SUCCESSFUL steps, so `times` controls how many
        consecutive retries the injected fault survives)."""
        assert not self.per_pos, (
            "a per-position (spec) worker runs step_spec + "
            "advance_lengths — the scheduler owns the accepted-count "
            "advance")
        step = self.n_steps
        with self.spans.span("worker.step", step=step):
            tok = self._dispatch(step, tokens, n_valid, temps, keys)
            self.pool.lengths = self.pool.lengths + np.asarray(n_valid,
                                                               np.int32)
            self.n_steps += 1
            return self._wait(step, tok)

    def step_spec(self, tokens: np.ndarray, n_valid: np.ndarray,
                  temps: np.ndarray, keys: np.ndarray) -> np.ndarray:
        """The per-position (spec-capable) step: keys (K, W, 2) — one
        per column of a (K, W) token block — and the return is the
        full (K, W) per-position token matrix (ISSUE 14,
        spec/verify.py). Pool LENGTHS ARE NOT
        ADVANCED: a verify row's valid advance is its ACCEPTED count,
        which only the scheduler can compute from the returned matrix
        — it calls `advance_lengths` after applying the
        longest-accepted-prefix rule. Same failure contract as step():
        raises before touching pool state, so retries are safe (the
        draft proposer is deterministic in the unchanged history, so a
        retried step rebuilds the identical row — no double
        emission)."""
        assert self.per_pos, "built without per_pos=True"
        step = self.n_steps
        with self.spans.span("worker.step", step=step):
            tok = self._dispatch(step, tokens, n_valid, temps, keys)
            self.n_steps += 1
            return self._wait(step, tok)

    def _dispatch(self, step: int, tokens, n_valid, temps, keys):
        """Both steps' device half: the injected fault, the six
        host-to-device puts (`worker.put`) and the call of the step
        compiled for the token block's width, which returns at
        enqueue (`worker.launch`)."""
        plan = _fplan.active()
        if plan is not None:
            err = plan.step_fault(step)
            if err is not None:
                raise err
        pool = self.pool
        with self.spans.span("worker.put", step=step):
            tokens = jnp.asarray(tokens, jnp.int32)
            table = jnp.asarray(pool.table)
            lengths = jnp.asarray(pool.lengths)
            n_valid = jnp.asarray(n_valid, jnp.int32)
            temps = jnp.asarray(temps, jnp.float32)
            keys = jnp.asarray(keys, jnp.uint32)
        width = tokens.shape[1]
        fn = self._fn if width == self.chunk else self._narrow[width]
        with self.spans.span("worker.launch", step=step):
            tok, _logits, pool.state, self._stats = fn(
                self.engine.params, tokens, pool.state, table,
                lengths, n_valid, temps, keys)
        return tok

    def _wait(self, step: int, tok) -> np.ndarray:
        """The device's step and the readback (`worker.wait`)."""
        with self.spans.span("worker.wait", step=step):
            tok = np.asarray(tok)
            self.last_stats = {k: int(v) for k, v in self._stats.items()}
            return tok

    def advance_lengths(self, advance: np.ndarray) -> None:
        """Fold a step_spec's per-slot length advance into the pool
        (the emitted count per slot — n_valid for prefill rows,
        accepted + 1 for verify rows)."""
        self.pool.lengths = self.pool.lengths + np.asarray(advance,
                                                           np.int32)


class ResidentWorker:
    """Ring producer / output consumer around the device-resident
    window (`engine.make_resident_loop`). Device loop state —
    slot_state, page table, lengths, the ring's consumed cursor —
    round-trips through each window launch, so windows chain without
    the host ever reassembling a step.

    Failure contract (mirrors Worker.step): `run_window` raises BEFORE
    advancing any host-visible state — an injected FailStep fires
    before the launch, and a starved window's outputs are folded in
    (the device DID run those steps) before the DeadlineExceeded is
    raised, so a retry resumes from truth. `guard_trip_site` for every
    ring watchdog trip is "inject" (faults.guard.SITES)."""

    def __init__(self, engine, pool: KVPool, chunk: int,
                 window: int = 16, ring_cap: Optional[int] = None,
                 poll_budget: int = 8, max_stuck_windows: int = 3,
                 spec_k: int = 0):
        self.engine = engine
        self.pool = pool
        self.chunk = chunk
        self.window = window
        self.poll_budget = poll_budget
        self.max_stuck_windows = max_stuck_windows
        self.spec_k = spec_k
        check_prng_impl()
        cap = ring_cap if ring_cap is not None else max(4 * pool.slots,
                                                        16)
        self.ring = mring.InjectionRing(cap, pool.max_pages, pool.t_max,
                                        chunk)
        self._spec_pins: List[object] = []
        # the build contexts active NOW decide the loop's trailing
        # telemetry outputs (the trace/obs construction-time
        # discipline, ISSUE 13): a trace build adds the serve.* mark
        # stream, an obs build the resident-window stat rows
        from triton_dist_tpu.obs import stats as _ost
        from triton_dist_tpu.trace import events as _tev

        self._traced = _tev.active_build() is not None
        self._metered = _ost.active_build() is not None
        self._fn = engine.make_resident_loop(
            pool.slots, chunk, pool.page, pool.max_pages, window,
            ring_cap=cap, prompt_cap=pool.t_max,
            poll_budget=poll_budget, spec_k=spec_k)
        # newest window's telemetry (None until a window ran / when the
        # matching build was off at construction)
        self.last_window_stats = None
        self.last_window_trace = None
        self.slot_state = np.zeros((pool.slots, mring.SS_WIDTH),
                                   np.int32)
        # the DEVICE's page-table/length view, installed by record
        # consumption — kept apart from pool.table/pool.lengths (the
        # host allocator's view, which may already carry rows for
        # admissions whose records the device has not consumed yet)
        self._table = np.zeros_like(pool.table)
        self._lengths = np.zeros((pool.slots,), np.int32)
        self.n_steps = 0    # executed device steps (all windows)
        self.n_windows = 0  # successful window launches
        self._stuck = 0     # consecutive zero-progress windows
        self._ring_dev = None       # cached device copy of ring.buf
        self._ring_dev_version = -1  # ring.version it mirrors

    # -- ring producer (the scheduler's injection API) -------------------

    key_for = staticmethod(sampling_key)

    def admit(self, slot: int, prompt, max_new: int, temperature: float,
              seed: int, eos_id, req_id: int, at_step: int = 0,
              prefix: int = 0) -> None:
        """Write the admission record: the slot's FULL page-table row
        (the resident mode allocates a request's whole lifetime at
        admission — the device never grows an allocation mid-loop) plus
        the prompt the device streams prefill chunks from. `prefix` is
        the prefix-cache hit length (serve/prefix.py): the device
        starts prefill and the slot length there — the table row's
        leading pages already carry that KV (KVPool.share)."""
        self.ring.admit(slot, prompt, max_new, temperature, seed,
                        eos_id, req_id,
                        self.pool.table[slot, :self.pool.max_pages],
                        at_step=at_step, prefix=prefix)

    def retire(self, slot: int, req_id: int, at_step: int = 0) -> None:
        self.ring.retire(slot, req_id, at_step=at_step)

    def inject_verify(self, slot: int, req_id: int, n_out: int,
                      drafts, at_step: int = 0) -> None:
        """Stage a KIND_VERIFY record (ISSUE 14): `drafts` proposed at
        exactly `n_out` emitted tokens. The record's row is pinned
        until the window that rode it returns (the device reads the
        draft tokens from the row at its verify step)."""
        assert self.spec_k > 0, "loop built without spec_k"
        assert 1 <= len(drafts) <= self.spec_k, (len(drafts),
                                                 self.spec_k)
        self._spec_pins.append(
            self.ring.verify(slot, req_id, n_out, drafts,
                             at_step=at_step))

    def can_inject(self) -> bool:
        """Room in the ring for one more record (see
        InjectionRing.can_claim) — the scheduler's backpressure probe:
        admissions and retirements defer to a later round instead of
        overflowing."""
        return self.ring.can_claim()

    def unpin(self, req_id: int) -> None:
        """Release a request's admission row (prefill complete or
        retired — the device no longer streams from it)."""
        self.ring.unpin(req_id)

    def pending_records(self) -> int:
        return self.ring.pending()

    # -- the window ------------------------------------------------------

    def run_window(self) -> List[mring.OutRecord]:
        """Launch one resident window; returns the drained output
        records in seq order. Raises DeadlineExceeded (with a
        structured "inject"-site guard trip) on a starved ring or
        after `max_stuck_windows` consecutive windows with zero
        progress (no step executed, no record consumed) while work is
        pending — the host-side bound on the device's ring poll."""
        # reset the telemetry slots BEFORE any fault can fire: a window
        # that raises pre-launch must not leave the PREVIOUS window's
        # stats behind for the scheduler to re-fold (double-counted
        # ring polls — the stale-stats class)
        self.last_window_stats = None
        self.last_window_trace = None
        plan = _fplan.active()
        if plan is not None:
            err = plan.step_fault(self.n_windows)
            if err is not None:
                raise err
            if plan.ring_abandons(self.n_windows):
                self.ring.abandon()
        pool = self.pool
        consumed0 = self.ring.consumed
        # upload the ring buffer only when the producer mutated it —
        # steady-state decode windows (no records) re-use the cached
        # device copy instead of paying a cap x width host->device
        # transfer on the exact dispatch path the mode exists to shave
        if self._ring_dev is None \
                or self._ring_dev_version != self.ring.version:
            self._ring_dev = jnp.asarray(self.ring.buf)
            self._ring_dev_version = self.ring.version
        res = self._fn(
            self.engine.params,
            self._ring_dev,
            jnp.asarray(self.ring.published, jnp.int32),
            jnp.asarray(consumed0, jnp.int32),
            jnp.asarray(self.n_steps, jnp.int32),
            jnp.asarray(self.slot_state),
            jnp.asarray(self._table),
            jnp.asarray(self._lengths),
            pool.k, pool.v,
        )
        # the device call returned: any verify rows staged for this
        # window are no longer read — release their pins (a pre-launch
        # fault above left them pinned for the retry, which relaunches
        # with the records still pending)
        for pin in self._spec_pins:
            self.ring.unpin(pin)
        self._spec_pins.clear()
        # strip the trailing telemetry outputs, stats outermost (the
        # documented strip order): primary, trace mark stream, window
        # stat rows
        if self._metered:
            self.last_window_stats = np.asarray(res[-1])
            res = res[:-1]
        if self._traced:
            self.last_window_trace = np.asarray(res[-1])
            res = res[:-1]
        (consumed, executed, ss, table, lengths, pool.k, pool.v,
         out_ring, out_count, starved) = res
        # fold the window's truth back in BEFORE any raise: the device
        # really ran `executed` steps — a retry must not replay them
        consumed = int(consumed)
        executed = int(executed)
        self.slot_state = np.asarray(ss)
        self._table = np.asarray(table)
        self._lengths = np.asarray(lengths)
        # mirror device lengths into the pool so mid-flight exports
        # (to_dense / as_mega_cache) read the device truth; retired
        # slots read 0 (their device row is stale until re-admission)
        pool.lengths = np.where(
            self.slot_state[:, mring.SS_ACTIVE] > 0,
            self._lengths, 0).astype(np.int32)
        self.ring.ack(consumed)
        self.n_steps += executed
        self.n_windows += 1
        records = mring.decode_out_ring(out_ring, int(out_count))
        progressed = executed > 0 or consumed > consumed0
        self._stuck = 0 if progressed else self._stuck + 1
        if int(starved):
            self._trip(consumed, "abandoned ring: head record "
                       f"{consumed + 1} published but never committed",
                       records)
        if (not progressed and self.ring.pending() > 0
                and self._stuck >= self.max_stuck_windows):
            self._trip(consumed, f"{self._stuck} consecutive windows "
                       "with pending records and zero progress",
                       records)
        return records

    def _trip(self, consumed: int, detail: str, records=None):
        from triton_dist_tpu.faults import guard

        trip = guard.GuardTrip(
            rank=0, site=guard.SITES["inject"],
            slot=consumed % self.ring.cap, progress=consumed,
            expected=consumed + 1,
            observed=int(self.ring.buf[consumed % self.ring.cap,
                                       mring.IR_SEQ]),
            seq=self.n_windows)
        err = DeadlineExceeded(
            f"resident window watchdog: {detail} ({trip})",
            trips=[trip])
        # the window DID run before the watchdog fired: its drained
        # output records ride the exception so the scheduler folds the
        # emitted tokens in before handling the trip — a trip must
        # never eat completions (that would be the silent-wrong class)
        err.out_records = records or []
        raise err

    def active_slots(self) -> np.ndarray:
        return np.flatnonzero(self.slot_state[:, mring.SS_ACTIVE])
