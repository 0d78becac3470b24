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
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from triton_dist_tpu.faults import plan as _fplan
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
    default PRNG implementation, where the host's keys and
    `jax.random.fold_in` would part ways in silence."""
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
    single derivation: plain and spec-verify steps both come through
    here.

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
        with self._step_span(step, tokens, n_valid):
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
        with self._step_span(step, tokens, n_valid):
            tok = self._dispatch(step, tokens, n_valid, temps, keys)
            self.n_steps += 1
            return self._wait(step, tok)

    def _step_span(self, step: int, tokens, n_valid):
        """The `worker.step` span of either step, with the counts its
        boundary knows: `width`, the token block's second dimension
        (which compiled program runs), and `rows`, the valid rows."""
        self.spans.step = step
        return self.spans.span("worker.step", step=step, counts={
            "width": int(np.shape(tokens)[1]),
            "rows": int(np.sum(n_valid))})

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

