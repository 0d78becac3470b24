"""Serving-plane tests: continuous batching over the serve step.

The load-bearing property (ISSUE 6 acceptance, restated by ISSUE 31):
per-request outputs are the same (temperature 0, and — via per-(seed,
index) keys — at temperature > 0 too) between the continuous-batching
scheduler and sequential `Engine.serve(..., slots=, chunk=)` runs,
including across an eviction/requeue. At ONE step width a row's
numerics are independent of batch composition, slot placement, and
chunk alignment, so a scheduler held to the `(slots, chunk)` step is
BIT-IDENTICAL, tokens and logits, to the sequential runs. With the
worker's two widths (a step of decode rows alone runs the `(slots, 1)`
program) a token is bitwise a function of its request's history and of
the widths of the steps that computed it: across width sequences the
tokens are equal on these float32 sizes and the logits agree to a
tolerance (tests/_widths.py; the `step_widths` fixture runs a test
under both statements). These tests pin that end to end, plus the
choice of a step's width and its dispatch, the KVPool allocator
invariants, queue policies, streaming, the megakernel paged-decode
bridge, and the step roofline.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from triton_dist_tpu.models import Engine, ModelConfig
from triton_dist_tpu.runtime import make_mesh
from triton_dist_tpu.serve import (
    Detokenizer,
    KVPool,
    PoolExhausted,
    QueueFull,
    Request,
    RequestQueue,
    RequestState,
    Scheduler,
    pages_for,
)
from triton_dist_tpu.serve.worker import (
    Worker,
    check_prng_impl,
    sampling_key,
    sampling_keys,
)

from _widths import ACROSS_WIDTHS_ATOL, record_logits

GEO = dict(slots=3, chunk=4, page=8)  # one compiled step for the module


@pytest.fixture(scope="module")
def mesh1():
    return make_mesh(mesh_shape=(1,), axis_names=("tp",))


@pytest.fixture(scope="module")
def eng1(mesh1):
    cfg = ModelConfig.tiny(num_q_heads=4, num_kv_heads=2,
                           max_positions=64)
    return Engine(cfg, mesh1, decode_mode="ar", max_len=64,
                  donate_cache=False)


@pytest.fixture(scope="module")
def prompts(eng1):
    rng = np.random.default_rng(1)
    v = eng1.cfg.vocab_size
    return [list(map(int, rng.integers(0, v, n))) for n in (12, 10, 9)]


def _sequential(eng, prompts, gen, **kw):
    """One request at a time through Engine.serve's stepwise path —
    the sequential baseline of the acceptance criterion."""
    return [
        list(map(int, np.asarray(
            eng.serve(np.asarray([p], np.int32), gen, slots=GEO["slots"],
                      chunk=GEO["chunk"], page=GEO["page"], **kw))[0]))
        for p in prompts
    ]


# ---------- KVPool allocator ----------


def test_pages_for():
    assert [pages_for(n, 8) for n in (1, 8, 9, 16, 17)] == [1, 1, 2, 2, 3]


def test_pool_ragged_admission_page_counts(eng1):
    pool = KVPool(eng1, slots=3, page=8)
    for slot, n in enumerate((5, 17, 8)):
        pool.admit(slot, n)
        assert pool.used_pages(slot) == pages_for(n, 8)
    assert pool.used_pages() == 1 + 3 + 1
    pool.check()
    # table rows point at distinct non-null pages
    used = pool.table[pool.table > 0]
    assert len(set(used.tolist())) == len(used)


def test_pool_double_free_and_leak_guards(eng1):
    pool = KVPool(eng1, slots=2, page=8, total_pages=4)
    pool.admit(0, 10)
    pool.release(0)
    with pytest.raises(AssertionError, match="double free"):
        pool.release(0)
    pool.check()
    assert pool.free_pages() == 4  # all pages back — no leak
    # a leaked page trips check()
    pool.admit(0, 3)
    pool._free.append(pool._pages[0][0])  # alias a held page
    with pytest.raises(AssertionError, match="aliased"):
        pool.check()


def test_pool_exhaustion_backpressure(eng1):
    pool = KVPool(eng1, slots=3, page=8, total_pages=2)
    pool.admit(0, 16)  # 2 pages — pool now empty
    with pytest.raises(PoolExhausted):
        pool.admit(1, 1)
    assert not pool.ensure(0, 17)  # growth also backpressured
    assert pool.used_pages(0) == 2  # all-or-nothing: nothing changed
    pool.release(0)
    pool.admit(1, 1)  # freed pages are reusable
    pool.check()


# ---------- RequestQueue ----------


def _req(prio=0, seed=0):
    return Request(prompt=[1, 2], max_new_tokens=2, priority=prio,
                   seed=seed)


def test_queue_priority_then_fifo():
    q = RequestQueue()
    a, b, c = _req(0), _req(5), _req(0)
    for r in (a, b, c):
        q.submit(r)
    assert q.pop() is b  # highest priority first
    assert q.pop() is a  # FIFO within a priority
    assert q.pop() is c


def test_queue_full_is_admission_control():
    q = RequestQueue(max_pending=2)
    q.submit(_req())
    q.submit(_req())
    with pytest.raises(QueueFull):
        q.submit(_req())


def test_queue_cancel_and_requeue_order():
    q = RequestQueue()
    a, b = _req(), _req()
    q.submit(a)
    q.submit(b)
    assert q.cancel(a)
    assert q.pop() is b
    # an evicted request keeps its arrival seq: resumes ahead of later
    # same-priority arrivals
    q.submit(a := _req())
    q.submit(b := _req())
    first = q.pop()
    assert first is a
    q.requeue(first)
    assert q.pop() is a and q.pop() is b


# ---------- continuous batching: bit-identity ----------


def _served_with_logits(eng, prompts, gen, together: bool):
    """(tokens, logits) a request, in the prompts' order: through one
    scheduler together, or each through a scheduler of its own (what
    `_sequential` does through Engine.serve)."""
    toks, logits = [], []
    for group in ([prompts] if together else [[p] for p in prompts]):
        sch = Scheduler(eng, **GEO)
        logits_of = record_logits(sch)
        reqs = [sch.submit(p, max_new_tokens=gen) for p in group]
        sch.run()
        got = logits_of({r.request_id: len(r.prompt) for r in reqs})
        toks += [r.out_tokens for r in reqs]
        logits += [got[r.request_id] for r in reqs]
    return toks, logits


def test_batched_bit_identical_to_sequential(eng1, prompts, step_widths):
    sch = Scheduler(eng1, **GEO)
    reqs = [sch.submit(p, max_new_tokens=6) for p in prompts]
    sch.run()
    assert [r.out_tokens for r in reqs] == _sequential(eng1, prompts, 6)
    assert all(r.finish_reason == "length" for r in reqs)
    sch.pool.check()
    assert sch.pool.used_pages() == 0  # free-on-finish
    shapes = sch.obs.snapshot()["counters"]
    if step_widths == "wide":
        assert "serve_steps{shape=narrow}" not in shapes
    else:  # batched, decode rows rode both programs
        assert shapes["serve_steps{shape=narrow}"] > 0
        assert shapes["serve_steps{shape=wide}"] > 0


def test_batched_logits_against_sequential(eng1, prompts, step_widths):
    """The logits behind the tokens: bitwise at one width sequence
    (every step the wide one), to ACROSS_WIDTHS_ATOL where a request
    alone decodes through the narrow step and, batched, beside a
    prefilling slot through the wide one."""
    toks, batched = _served_with_logits(eng1, prompts, 6, together=True)
    toks_alone, alone = _served_with_logits(eng1, prompts, 6,
                                            together=False)
    assert toks == toks_alone
    for b, a in zip(batched, alone):
        assert b.shape == a.shape == (6, eng1.cfg.vocab_size)
        if step_widths == "wide":
            np.testing.assert_array_equal(b, a)
        else:
            np.testing.assert_allclose(b, a, atol=ACROSS_WIDTHS_ATOL,
                                       rtol=0)


def test_a_decode_step_agrees_across_widths(eng1, prompts):
    """The same pool state and the same decode rows through both
    compiled steps: the same first choice, logits within the
    tolerance, and the pool's K/V rows written bit for bit alike (a
    projection row does not depend on the other rows' count in
    float32; the cell's bf16 reading is in docs/serving.md)."""
    sch = Scheduler(eng1, **GEO)
    reqs = [sch.submit(p, max_new_tokens=4) for p in prompts]
    while any(r.state is not RequestState.DECODE for r in reqs):
        sch.step()
    tokens, n_valid, temps, keys, plans = sch._assemble(sch.worker.n_steps)
    assert tokens.shape == (GEO["slots"], 1) and len(plans) == 3
    w, pool = sch.worker, sch.pool
    wide = np.zeros((GEO["slots"], GEO["chunk"]), np.int32)
    wide[:, :1] = tokens
    args = (jnp.asarray(pool.table), jnp.asarray(pool.lengths),
            jnp.asarray(n_valid), jnp.asarray(temps), jnp.asarray(keys))
    tok_n, last_n, state_n, _ = w._narrow[1](
        eng1.params, jnp.asarray(tokens), pool.state, *args)
    tok_w, last_w, state_w, _ = w._fn(
        eng1.params, jnp.asarray(wide), pool.state, *args)
    np.testing.assert_array_equal(np.asarray(tok_n), np.asarray(tok_w))
    np.testing.assert_allclose(np.asarray(last_n), np.asarray(last_w),
                               atol=ACROSS_WIDTHS_ATOL, rtol=0)
    assert float(np.abs(np.asarray(last_w)).max()) > 0.1  # not all zero
    # page 0 is the null page: the wide step's padding columns land there
    for got, want in zip(state_n, state_w):
        np.testing.assert_array_equal(np.asarray(got)[:, :, 1:],
                                      np.asarray(want)[:, :, 1:])


def test_eviction_requeue_bit_identical(eng1, prompts, step_widths):
    # 4 allocatable pages for three requests growing to 3 pages each:
    # mid-flight growth must evict younger slots, which requeue and
    # re-prefill their full history (through the wide step, whatever
    # width computed the token the first time: "wide" is bitwise,
    # "per-step" equal tokens on these float32 sizes)
    sch = Scheduler(eng1, total_pages=4, **GEO)
    reqs = [sch.submit(p, max_new_tokens=12) for p in prompts]
    sch.run()
    assert sum(r.n_evictions for r in reqs) > 0, (
        "pool was not constrained enough to exercise eviction"
    )
    assert [r.out_tokens for r in reqs] == _sequential(eng1, prompts, 12)
    sch.pool.check()


def test_sampled_generation_scheduling_invariant(eng1, prompts,
                                                 step_widths):
    def run(total_pages):
        sch = Scheduler(eng1, total_pages=total_pages, **GEO)
        reqs = [sch.submit(p, max_new_tokens=8, temperature=0.9,
                           seed=41 + i) for i, p in enumerate(prompts)]
        sch.run()
        return [r.out_tokens for r in reqs], reqs

    constrained, creqs = run(4)
    relaxed, _ = run(None)
    assert sum(r.n_evictions for r in creqs) > 0
    assert constrained == relaxed
    # distinct seeds actually diverge (the keys are per-request)
    assert len({tuple(t) for t in relaxed}) > 1


def test_a_request_admitted_mid_decode_reads_what_it_reads_alone(
        eng1, prompts, step_widths):
    """Admission time is scheduling, never numerics: a request that
    arrives while the others decode prefills beside their decode rows
    and still reads what a run of its own reads."""
    sch = Scheduler(eng1, **GEO)
    early = [sch.submit(p, max_new_tokens=10) for p in prompts[:2]]
    while any(r.state is not RequestState.DECODE for r in early):
        assert sch.step()
    for _ in range(2):
        assert sch.step()
    arrived_at = sch.worker.n_steps
    late = sch.submit(prompts[2], max_new_tokens=6)
    sch.run()
    assert [r.out_tokens for r in early] == _sequential(eng1, prompts[:2],
                                                        10)
    assert late.out_tokens == _sequential(eng1, prompts[2:], 6)[0]
    # its prefill chunks rode steps in which the others decoded
    beside = [h for h in sch.history if h["step"] >= arrived_at
              and {state for _rid, state, _n in h["slots"].values()}
              == {"prefill", "decode"}]
    assert len(beside) >= 2
    sch.pool.check()


def test_slots_reused_many_times_read_the_sequential_run(eng1,
                                                         step_widths):
    """More requests than slots: a long prompt prefills chunk by chunk
    while short requests turn the other slots over, every slot serves
    three requests or more, and each request reads what it reads
    alone (a reused slot's pages and lengths carry nothing over)."""
    rng = np.random.default_rng(23)
    v = eng1.cfg.vocab_size
    ps = [list(map(int, rng.integers(0, v, 40)))] + [
        list(map(int, rng.integers(0, v, 5))) for _ in range(10)]
    sch = Scheduler(eng1, **GEO)
    reqs = [sch.submit(p, max_new_tokens=3) for p in ps]
    sch.run()
    assert [r.out_tokens for r in reqs] == _sequential(eng1, ps, 3)
    served = {}
    for h in sch.history:
        for slot, (rid, _state, _n) in h["slots"].items():
            served.setdefault(slot, set()).add(rid)
    assert sorted(served) == list(range(GEO["slots"]))
    assert all(len(rids) >= 3 for rids in served.values()), served
    sch.pool.check()
    assert sch.pool.used_pages() == 0


def test_the_scheduler_has_one_loop(eng1, prompts):
    """One `step()`, one worker, one kind of `history` entry: there is
    no device-resident window beside the host loop (docs/serving.md
    "Why there is no device-resident loop")."""
    import inspect

    import triton_dist_tpu.serve as serve

    params = inspect.signature(Scheduler).parameters
    assert not {"resident", "window", "ring_cap"} & set(params)
    assert not hasattr(serve, "ResidentWorker")
    assert not hasattr(Engine, "make_resident_loop")
    sch = Scheduler(eng1, **GEO)
    assert type(sch.worker) is Worker
    for p in prompts:
        sch.submit(p, max_new_tokens=4)
    sch.run()
    assert sch.history
    for h in sch.history:
        assert h["kind"] == "step" and h["width"] in sch.worker.widths


# ---------- a step's width: the choice and the dispatch (ISSUE 31) ----------


def _decoding(eng, prompts, **kw):
    """A scheduler whose requests have all reached DECODE."""
    sch = Scheduler(eng, **{**GEO, **kw})
    reqs = [sch.submit(p, max_new_tokens=8) for p in prompts]
    while any(r.state is not RequestState.DECODE for r in reqs):
        assert sch.step()
    return sch, reqs


def test_worker_holds_one_compiled_step_a_width(eng1):
    sch = Scheduler(eng1, **GEO)
    w = sch.worker
    assert w.widths == eng1.serve_widths(GEO["chunk"]) == (1, GEO["chunk"])
    max_pages = sch.pool.max_pages
    assert w._fn is eng1.make_serve_step(3, 4, 8, max_pages)  # the widest
    assert w._narrow == {1: eng1.make_serve_step(3, 1, 8, max_pages)}
    assert eng1.serve_widths(1) == (1,)  # nothing narrower to hold


def test_rows_of_one_token_or_none_take_the_narrow_step(eng1, prompts):
    """Decode rows, an empty slot, and a one-token prefill tail."""
    sch, reqs = _decoding(eng1, prompts[:2])
    tokens, n_valid, *_ = sch._assemble(sch.worker.n_steps)
    assert tokens.shape == (3, 1) and list(n_valid) == [1, 1, 0]
    assert [int(t) for t in tokens[:2, 0]] == [r.out_tokens[-1]
                                              for r in reqs]
    # chunk + 1 tokens: the tail of the prompt is one row
    tail = sch.submit(prompts[2][:GEO["chunk"] + 1], max_new_tokens=2)
    sch.step()
    assert tail.state is RequestState.PREFILL and tail.pos == GEO["chunk"]
    tokens, n_valid, _t, _k, plans = sch._assemble(sch.worker.n_steps)
    assert tokens.shape == (3, 1) and list(n_valid) == [1, 1, 1]
    assert plans[2][1] is tail and plans[2][3]  # it emits
    assert int(tokens[2, 0]) == tail.prompt[-1]


def test_a_prefill_row_of_two_tokens_takes_the_wide_step(eng1, prompts):
    sch, _reqs = _decoding(eng1, prompts[:2])
    new = sch.submit(prompts[2][:2], max_new_tokens=2)
    sch._admit()
    tokens, n_valid, *_ = sch._assemble(sch.worker.n_steps)
    assert tokens.shape == (3, GEO["chunk"]) and list(n_valid) == [1, 1, 2]
    assert list(tokens[2]) == new.prompt + [0, 0]
    assert not tokens[:2, 1:].any()


def test_a_verify_row_takes_the_wide_step(eng1, prompts):
    """Speculative decoding: a decode row with drafts is 1 + drafts
    tokens; without a draft it is one row again."""
    from triton_dist_tpu.spec import NgramDraft, SpecConfig

    spec = SpecConfig(k=2, draft=NgramDraft())
    sch, reqs = _decoding(eng1, prompts[:1], spec=spec)
    assert sch.worker.widths == (1, GEO["chunk"])
    req = reqs[0]
    spec.draft.propose = lambda hist, cap: [7, 9][:cap]
    tokens, n_valid, _t, keys, plans = sch._assemble(sch.worker.n_steps)
    assert tokens.shape == (3, GEO["chunk"]) and n_valid[0] == 3
    assert list(tokens[0, :3]) == [req.out_tokens[-1], 7, 9]
    assert keys.shape == (3, GEO["chunk"], 2) and plans[0][4] == [7, 9]
    spec.draft.propose = lambda hist, cap: []
    tokens, n_valid, _t, keys, plans = sch._assemble(sch.worker.n_steps)
    assert tokens.shape == (3, 1) and keys.shape == (3, 1, 2)
    assert n_valid[0] == 1 and keys[0, 0].any()


def test_an_evicted_row_is_scrubbed_before_the_width_is_taken(
        eng1, prompts, monkeypatch):
    """A later slot's page demand evicts an earlier slot whose prefill
    chunk was already planned: the step that is left holds one decode
    row, and runs narrow."""
    sch = Scheduler(eng1, **GEO)
    first = sch.submit(prompts[2][:2], max_new_tokens=1)  # frees slot 0
    keeper = sch.submit(prompts[1], max_new_tokens=8)
    while keeper.state is not RequestState.DECODE:
        assert sch.step()
    assert first.done
    victim = sch.submit(prompts[0], max_new_tokens=4)
    sch._admit()
    assert sch.active[0] is victim and sch.active[1] is keeper
    tokens, n_valid, *_ = sch._assemble(sch.worker.n_steps)
    assert tokens.shape == (3, GEO["chunk"])
    assert list(n_valid) == [GEO["chunk"], 1, 0]
    room = sch._room

    def evicting_room(slot, req, upto):
        if req is keeper:
            sch._evict(victim)
        return room(slot, req, upto)

    monkeypatch.setattr(sch, "_room", evicting_room)
    tokens, n_valid, _t, _k, plans = sch._assemble(sch.worker.n_steps)
    assert victim.n_evictions == 1 and [p[1] for p in plans] == [keeper]
    assert tokens.shape == (3, 1) and list(n_valid) == [0, 1, 0]
    assert int(tokens[1, 0]) == keeper.out_tokens[-1]


def test_a_wrapper_on_fn_sees_the_first_wide_call(eng1, prompts):
    """perfbench's StepListing contract: `Worker._fn` is the widest
    step, looked up at call time, so a wrapper set on the attribute
    (after construction) is what the first wide step calls; the narrow
    steps do not pass through it."""
    sch = Scheduler(eng1, **GEO)
    fn, calls = sch.worker._fn, []

    def first_call(*a):
        sch.worker._fn = fn
        calls.append(a[1].shape)
        return fn(*a)

    sch.worker._fn = first_call
    req = sch.submit(prompts[0], max_new_tokens=4)
    sch.run()
    assert calls == [(GEO["slots"], GEO["chunk"])]
    assert sch.worker._fn is fn
    assert req.out_tokens == _sequential(eng1, prompts[:1], 4)[0]


def test_the_step_counter_names_each_step_s_shape(eng1, prompts):
    sch = Scheduler(eng1, **GEO)
    reqs = [sch.submit(p, max_new_tokens=5) for p in prompts]
    sch.run()
    shapes = sch.obs.snapshot()["counters"]
    narrow = shapes["serve_steps{shape=narrow}"]
    wide = shapes["serve_steps{shape=wide}"]
    assert narrow + wide == sch.worker.n_steps == len(sch.history)
    widths = [h["width"] for h in sch.history]
    assert widths.count(1) == narrow and widths.count(GEO["chunk"]) == wide
    for h in sch.history:  # the narrowest width that holds the rows
        longest = max(n for _rid, _state, n in h["slots"].values())
        assert h["width"] == (1 if longest <= 1 else GEO["chunk"])
    # prompts of 12, 10 and 9 tokens at chunk 4: three wide steps, then
    # the 9's one-token tail beside two decode rows and decode alone
    assert widths[:3] == [4, 4, 4] and set(widths[3:]) == {1}
    assert all(len(r.out_tokens) == 5 for r in reqs)


# ---------- the host's key derivation (ISSUE 28) ----------

KEY_SEEDS = (0, 1, 41, 12345, 2**31 - 1, -1, -5, 2**32 + 7)
KEY_INDICES = (0, 1, 3, 255, 100000)


def _fold_in_key(seed, index):
    """The derivation `sampling_keys` must reproduce bit for bit."""
    return np.asarray(jax.random.fold_in(jax.random.PRNGKey(seed), index))


@pytest.mark.parametrize("index", KEY_INDICES)
@pytest.mark.parametrize("seed", KEY_SEEDS)
def test_sampling_key_is_fold_in_bitwise(seed, index):
    key = sampling_key(seed, index)
    assert key.dtype == np.uint32 and key.shape == (2,)
    np.testing.assert_array_equal(key, _fold_in_key(seed, index))
    np.testing.assert_array_equal(key,
                                  sampling_keys([seed], [index])[0])


@pytest.mark.parametrize("shape", [(8,), (4, 10)])
def test_sampling_keys_shapes(shape):
    n = int(np.prod(shape))
    seeds = np.resize(np.array(KEY_SEEDS), n).reshape(shape)
    idx = np.resize(np.array(KEY_INDICES), n).reshape(shape)
    keys = sampling_keys(seeds, idx)
    assert keys.dtype == np.uint32 and keys.shape == shape + (2,)
    for at in np.ndindex(*shape):
        np.testing.assert_array_equal(
            keys[at], _fold_in_key(int(seeds[at]), int(idx[at])))


@pytest.mark.parametrize("impl", ["rbg", "unsafe_rbg"])
def test_worker_refuses_non_threefry_default(eng1, impl):
    check_prng_impl()  # the default passes
    pool = KVPool(eng1, slots=GEO["slots"], page=GEO["page"])
    with jax.default_prng_impl(impl):
        with pytest.raises(RuntimeError, match="threefry2x32"):
            check_prng_impl()
        with pytest.raises(RuntimeError, match="threefry2x32"):
            Worker(eng1, pool, GEO["chunk"])


def test_priority_preemption_and_completion(eng1, prompts):
    # two low-priority requests hold every page; a high-priority arrival
    # preempts the most-victimizable one, which requeues and completes
    sch = Scheduler(eng1, total_pages=2, **GEO)
    low = [sch.submit(p, max_new_tokens=4, priority=0)
           for p in prompts[:2]]
    for _ in range(2):
        sch.step()
    high = sch.submit(prompts[2], max_new_tokens=4, priority=5)
    sch.run()
    assert sum(r.n_evictions for r in low) > 0
    assert high.n_evictions == 0
    # the preempted run still matches the sequential baseline
    assert [r.out_tokens for r in low + [high]] == _sequential(
        eng1, prompts, 4)
    # and the high-priority request finished before the victim
    victim = max(low, key=lambda r: r.n_evictions)
    assert high.token_times[-1] < victim.token_times[-1]


def test_eos_stops_early(eng1, prompts):
    full = _sequential(eng1, prompts[:1], 6)[0]
    eos = full[2]
    sch = Scheduler(eng1, **GEO)
    req = sch.submit(prompts[0], max_new_tokens=6, eos_id=eos)
    sch.run()
    assert req.out_tokens == full[:3]
    assert req.finish_reason == "eos"
    sch.pool.check()


def test_cancellation_frees_slot(eng1, prompts):
    sch = Scheduler(eng1, **GEO)
    a = sch.submit(prompts[0], max_new_tokens=12)
    b = sch.submit(prompts[1], max_new_tokens=4)
    for _ in range(3):
        sch.step()
    sch.cancel(a)
    sch.run()
    assert a.state is RequestState.CANCELLED
    assert b.state is RequestState.FINISHED
    assert b.out_tokens == _sequential(eng1, prompts[1:2], 4)[0]
    assert sch.pool.used_pages() == 0
    sch.pool.check()


def test_streaming_callback_iterator_and_detok(eng1, prompts):
    got = []
    sch = Scheduler(eng1, detokenizer=Detokenizer(lambda t: f"<{t}>"),
                    **GEO)
    req = sch.submit(prompts[0], max_new_tokens=5, stream=True,
                     on_token=lambda r, t, piece: got.append((t, piece)))
    sch.run()
    streamed = list(req.stream)
    assert [t for t, _ in streamed] == req.out_tokens == [t for t, _ in got]
    assert all(p == f"<{t}>" for t, p in streamed)
    # latency metrics populated
    assert req.ttft_us() > 0 and req.tpot_us() > 0
    m = sch.metrics()
    assert m["n"] == 1 and m["tokens_per_s"] > 0


def test_background_thread_serving(eng1, prompts):
    sch = Scheduler(eng1, **GEO)
    sch.start()
    try:
        req = sch.submit(prompts[1], max_new_tokens=4, stream=True)
        toks = [t for t, _ in req.stream]  # blocks until completion
    finally:
        sch.stop()
    assert toks == _sequential(eng1, prompts[1:2], 4)[0]


def test_background_thread_failure_unblocks_streams(eng1, prompts):
    """A step failure in threaded mode must CLOSE in-flight streams
    (the 'client never hangs' envelope) and resurface on stop()."""
    sch = Scheduler(eng1, **GEO)
    req = sch.submit(prompts[0], max_new_tokens=8, stream=True)
    orig = sch.worker.step
    calls = {"n": 0}

    def boom(*a, **kw):
        calls["n"] += 1
        if calls["n"] > 2:
            raise RuntimeError("injected device fault")
        return orig(*a, **kw)

    sch.worker.step = boom
    sch.start()
    toks = [t for t, _ in req.stream]  # must terminate, not hang
    assert len(toks) < 8
    assert req.state is RequestState.CANCELLED
    with pytest.raises(RuntimeError, match="serving thread died"):
        sch.stop()
    assert sch.pool.used_pages() == 0
    sch.pool.check()


def test_submit_validation(eng1):
    sch = Scheduler(eng1, **GEO)
    with pytest.raises(ValueError, match="empty prompt"):
        sch.submit([], max_new_tokens=2)
    with pytest.raises(ValueError, match="exceeds the pool"):
        sch.submit([1] * 60, max_new_tokens=10)
    with pytest.raises(ValueError, match="max_new_tokens"):
        sch.submit([1], max_new_tokens=0)


def test_trace_spans_and_perfetto_export(eng1, prompts, tmp_path):
    from triton_dist_tpu import trace

    sch = Scheduler(eng1, total_pages=4, **GEO)
    reqs = [sch.submit(p, max_new_tokens=10) for p in prompts]
    sch.run()
    tl = sch.timeline()
    names = [n for n, _, _ in tl.host_spans]
    for rid in (reqs[0].request_id, reqs[1].request_id):
        assert f"req{rid}/queued" in names
        assert f"req{rid}/prefill" in names
        assert f"req{rid}/decode" in names
    assert any(n.endswith("/evicted") for n in names)
    # phase spans are well-ordered
    for n, t0, t1 in tl.host_spans:
        assert t1 >= t0
    path = trace.write_trace(tl, str(tmp_path / "serve.trace.json"))
    assert trace.load_trace_json(path)["traceEvents"]


def test_serve_step_executable_shared_and_bounded(eng1):
    fn1 = eng1.make_serve_step(3, 4, 8, 8)
    fn2 = eng1.make_serve_step(3, 4, 8, 8)
    assert fn1 is fn2  # Worker + Engine.serve replay ONE executable
    for i in range(12):
        eng1.make_serve_step(3, 4, 8, 8 - i % 2)
    assert len(eng1._serve_cache) <= eng1._gen_cache_max


def test_moe_engine_serves_stepwise(mesh1, step_widths):
    cfg = ModelConfig.tiny_moe(num_q_heads=4, num_kv_heads=2,
                               num_experts=4)
    eng = Engine(cfg, mesh1, decode_mode="ar", max_len=64,
                 donate_cache=False)
    rng = np.random.default_rng(5)
    ps = [list(map(int, rng.integers(0, cfg.vocab_size, n)))
          for n in (6, 9)]
    sch = Scheduler(eng, **GEO)
    reqs = [sch.submit(p, max_new_tokens=3) for p in ps]
    sch.run()
    seq = _sequential(eng, ps, 3)
    assert [r.out_tokens for r in reqs] == seq


# ---------- perf model ----------


def test_serve_step_model_amortizes_weights():
    from triton_dist_tpu.perf_model import CHIPS, estimate_serve_step_ms

    chip = CHIPS["TPU v5 lite"]
    dims = dict(num_layers=36, hidden=4096, inter_loc=1536, hq_loc=4,
                hkv_loc=1, head_dim=128, vocab_loc=18992, chip=chip)
    t1 = estimate_serve_step_ms(n_tokens=1, **dims)
    t8 = estimate_serve_step_ms(n_tokens=8, **dims)
    t4096 = estimate_serve_step_ms(n_tokens=4096, **dims)
    # monotone, and the weight-bound region is nearly flat (the
    # continuous-batching amortization the scheduler exploits)
    assert t1 <= t8 <= t4096
    assert t8 < 1.1 * t1
    assert t4096 > 2 * t1  # eventually compute-bound


def test_choose_prefill_chunk_budget_monotone():
    from triton_dist_tpu.perf_model import CHIPS, choose_prefill_chunk

    chip = CHIPS["TPU v5 lite"]
    dims = dict(num_layers=36, hidden=4096, inter_loc=1536, hq_loc=4,
                hkv_loc=1, head_dim=128, vocab_loc=18992, slots=4,
                chip=chip)
    tight = choose_prefill_chunk(stall_budget=1.05, **dims)
    loose = choose_prefill_chunk(stall_budget=4.0, **dims)
    assert 1 <= tight <= loose
    # the HBM-bound 8B shard step barely notices a whole chunk column:
    # the model should pick a sizeable chunk even at a tight budget
    assert tight >= 16


# ---------- bench schema ----------


def _serve_result():
    lvl = {"n": 10, "tokens_per_s": 50.0, "ttft_p50_us": 1e5,
           "ttft_p99_us": 2e5, "tpot_p50_us": 9e4, "tpot_p99_us": 1e5}
    return {
        "metric": "mega_decode_qwen3_8b_ms", "value": 1.0, "unit": "ms",
        "vs_baseline": 0.5,
        "serve_tokens_per_s": 50.0, "serve_seq_tokens_per_s": 14.0,
        "serve_vs_seq_tokens": 3.57,
        "serve_ttft_p50_us": 1e5, "serve_ttft_p99_us": 2e5,
        "serve_tpot_p50_us": 9e4, "serve_tpot_p99_us": 1e5,
        "serve_levels": {"qps1": {"batched": dict(lvl),
                                  "sequential": dict(lvl)},
                         "qps4": {"batched": dict(lvl),
                                  "sequential": dict(lvl)}},
        "prefill_us": 12000.0,
        "prefill_raw": {"diffs_ms": [12.0, 12.1], "k": (1, 21),
                        "p25_ms": 12.0, "min_ms": 12.0},
    }


def test_check_result_accepts_serving_schema():
    import bench

    assert bench.check_result(_serve_result()) == []


def test_check_result_serving_keys_travel_together():
    import bench

    bad = _serve_result()
    del bad["serve_ttft_p99_us"]
    assert any("travel together" in p for p in bench.check_result(bad))
    # fewer than two QPS levels is malformed
    bad = _serve_result()
    bad["serve_levels"] = {"qps4": bad["serve_levels"]["qps4"]}
    assert any(">= 2 QPS levels" in p for p in bench.check_result(bad))
    # a level missing an arm, or an arm missing a tail stat, is caught
    bad = _serve_result()
    del bad["serve_levels"]["qps1"]["sequential"]
    assert any("missing the 'sequential'" in p
               for p in bench.check_result(bad))
    bad = _serve_result()
    del bad["serve_levels"]["qps4"]["batched"]["tpot_p99_us"]
    assert any("tpot_p99_us" in p for p in bench.check_result(bad))
    # prefill chain metrics obey the round-5 tail-stat rule
    bad = _serve_result()
    del bad["prefill_raw"]["p25_ms"]
    assert any("p25_ms" in p for p in bench.check_result(bad))


def test_drive_poisson_batched_beats_sequential(eng1, prompts):
    """The bench harness loop on a tiny engine: instantaneous Poisson
    burst, batched vs max_active=1 — batched must finish in fewer
    worker steps (the tokens/s win the acceptance criterion tracks,
    counted in steps so the assertion is noise-free on CPU)."""
    import bench

    arrivals = np.zeros(len(prompts))

    def arm(max_active):
        sch = Scheduler(eng1, max_active=max_active, **GEO)
        m = bench.drive_poisson(sch, prompts, arrivals, gen_len=6)
        return m, sch.worker.n_steps

    m_b, steps_b = arm(GEO["slots"])
    m_s, steps_s = arm(1)
    assert m_b["n"] == m_s["n"] == len(prompts)
    assert steps_b < steps_s
    for m in (m_b, m_s):
        for k in ("tokens_per_s", "ttft_p50_us", "ttft_p99_us",
                  "tpot_p50_us", "tpot_p99_us"):
            assert m[k] > 0


def test_prefill_chain_metric_shape(eng1, mesh1):
    """The bench prefill chain on the tiny engine: positive latency +
    the mandatory tail stats (the real 8B-shard arm runs only on the
    driver)."""
    import bench

    ms, raw = bench._bench_prefill_chain(mesh1, eng1, seq_len=16,
                                         k_hi=5, pairs=3)
    assert ms > 0
    assert {"diffs_ms", "p25_ms", "min_ms"} <= set(raw)


# ---------- distributed (mesh8) + megakernel bridge ----------


@pytest.fixture(scope="module")
def eng8(mesh8):
    cfg = ModelConfig.tiny(max_positions=32)
    return Engine(cfg, mesh8, decode_mode="ar", max_len=32,
                  donate_cache=False)


def test_distributed_serve_bit_identical(eng8, step_widths):
    rng = np.random.default_rng(2)
    ps = [list(map(int, rng.integers(0, eng8.cfg.vocab_size, n)))
          for n in (6, 9)]
    sch = Scheduler(eng8, slots=2, chunk=4, page=8)
    reqs = [sch.submit(p, max_new_tokens=4) for p in ps]
    sch.run()
    seq = [
        list(map(int, np.asarray(
            eng8.serve(np.asarray([p], np.int32), 4, slots=2, chunk=4,
                       page=8))[0]))
        for p in ps
    ]
    assert [r.out_tokens for r in reqs] == seq


def test_mega_paged_decode_runs_over_pool_export(eng8):
    """The pool IS megakernel state: a mid-flight serve-pool snapshot
    exports as PagedMegaKVCache and the megakernel's paged decode over
    it is bitwise equal to decoding over the equivalent
    paged_cache_from_dense layout (page identity is allocation policy,
    not numerics)."""
    from triton_dist_tpu.mega.qwen3 import MegaQwen3

    rng = np.random.default_rng(3)
    ps = [list(map(int, rng.integers(0, eng8.cfg.vocab_size, n)))
          for n in (6, 9)]
    sch = Scheduler(eng8, slots=2, chunk=4, page=8)
    reqs = [sch.submit(p, max_new_tokens=20) for p in ps]
    for _ in range(6):
        sch.step()  # mid-flight: both slots decoding, pool populated
    assert all(r.state is RequestState.DECODE for r in reqs)

    mega = MegaQwen3(eng8.cfg, eng8.mesh, batch=2, s_max=sch.pool.t_max,
                     params=eng8.params, donate_cache=False, paged=True,
                     page_size=sch.pool.page,
                     total_pages=1 + sch.pool.capacity)
    pc_pool = sch.pool.as_mega_cache()
    pc_ref = mega.paged_cache_from_dense(sch.pool.to_dense())
    tok = jnp.asarray([r.out_tokens[-1] for r in reqs], jnp.int32)
    lg_pool, _ = mega.decode_step(tok, pc_pool)
    lg_ref, _ = mega.decode_step(tok, pc_ref)
    np.testing.assert_array_equal(np.asarray(lg_pool),
                                  np.asarray(lg_ref))


def _dense_from_mega(pc, lengths):
    """Reconstruct each sequence's valid prefix from a
    PagedMegaKVCache through ITS page table (numpy gather)."""
    k = np.asarray(pc.k)
    tbl = np.asarray(pc.table)
    page = k.shape[3]
    out = []
    for b, ln in enumerate(lengths):
        rows = [k[:, :, tbl[b, i // page], i % page] for i in range(ln)]
        out.append(np.stack(rows, axis=2) if rows
                   else np.zeros(k.shape[:2] + (0, k.shape[-1]),
                                 k.dtype))
    return out


def test_pool_mega_export_bitwise_under_churn(eng1, prompts):
    """Allocate/grow/evict/re-admit churn: at every checkpoint the
    pool's as_mega_cache export reconstructs (through its own table)
    bitwise the same sequences as paged_cache_from_dense of the dense
    view, and unallocated table entries stay on the null page 0."""
    sch = Scheduler(eng1, total_pages=4, **GEO)  # tight: forces churn
    reqs = [sch.submit(p, max_new_tokens=12) for p in prompts]
    checked = 0
    for _ in range(40):
        if not sch.step() and sch.queue.peek() is None:
            break
        if not sch.active:
            continue
        sch.pool.check()
        pc = sch.pool.as_mega_cache()
        lens = [int(x) for x in np.asarray(pc.length)]
        # null-page discipline: no allocated position maps to page 0,
        # and unallocated table entries are exactly 0
        from triton_dist_tpu.mega.qwen3 import PagedMegaKVCache
        from triton_dist_tpu.serve import pages_for

        tbl = np.asarray(pc.table)
        for s, ln in enumerate(lens):
            held = sch.pool.used_pages(s)  # may run AHEAD of length
            # (ensure() allocates the next chunk before the step runs)
            assert held >= (pages_for(ln, sch.pool.page) if ln else 0)
            assert (tbl[s, :held] > 0).all()
            assert (tbl[s, held:] == 0).all()
        pc_ref = PagedMegaKVCache.from_dense(
            sch.pool.to_dense(), sch.pool.page, 1 + sch.pool.capacity,
            sch.pool.max_pages)
        got = _dense_from_mega(pc, lens)
        want = _dense_from_mega(pc_ref, lens)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        checked += 1
    assert sum(r.n_evictions for r in reqs) > 0, "churn never evicted"
    assert checked >= 5


# ---------- failure paths (ISSUE 10 satellites) ----------
# The happy paths above pin bit-identity; these pin the UNHAPPY ones:
# QueueFull backpressure under a burst arrival trace, cancel while a
# request is mid-prefill, and eviction-then-requeue ordering while an
# injected stalled step exercises the retry ladder concurrently.


def test_queue_full_backpressure_under_burst(eng1, prompts):
    """A burst beyond max_pending must 429 (QueueFull) — and draining
    the queue must restore admission, with every admitted request still
    bit-identical to its sequential run."""
    q = RequestQueue(max_pending=2)
    sch = Scheduler(eng1, queue=q, **GEO)
    admitted = [sch.submit(prompts[0], max_new_tokens=3),
                sch.submit(prompts[1], max_new_tokens=3)]
    with pytest.raises(QueueFull):
        sch.submit(prompts[2], max_new_tokens=3)
    # the rejection left no span residue and no scheduler state
    assert len(sch.requests) == 2
    sch.run()
    late = sch.submit(prompts[2], max_new_tokens=3)  # drained: admitted
    sch.run()
    toks = [r.out_tokens for r in admitted + [late]]
    assert toks == _sequential(eng1, prompts, 3)


def test_cancel_during_prefill_frees_slot(eng1, prompts):
    """Cancel a request whose prompt is mid-prefill (pos > 0, chunk
    boundary not reached): the slot and pages free on the next step and
    the other request is unaffected bit-for-bit."""
    sch = Scheduler(eng1, **GEO)
    victim = sch.submit(prompts[0], max_new_tokens=3)   # 12 tokens > chunk
    keeper = sch.submit(prompts[1], max_new_tokens=3)
    sch.step()  # one chunk of prefill each
    assert victim.state is RequestState.PREFILL and victim.pos > 0
    used_before = sch.pool.used_pages()
    sch.cancel(victim)
    sch.run()
    assert victim.state is RequestState.CANCELLED
    assert victim.out_tokens == []
    assert sch.pool.used_pages() < used_before
    sch.pool.check()
    assert keeper.out_tokens == _sequential(eng1, [prompts[1]], 3)[0]


def test_evict_requeue_ordering_under_stalled_step(eng1, prompts):
    """Page pressure forces an eviction; the evicted request requeues
    with its ORIGINAL arrival seq (ahead of later same-priority
    arrivals) while an injected stalled step exercises the retry ladder
    mid-flight — and every completion stays bit-identical."""
    from triton_dist_tpu import faults

    total = eng1.max_len  # 64 tokens / page 8 = 8 pages shared
    sch = Scheduler(eng1, slots=2, chunk=GEO["chunk"], page=GEO["page"],
                    total_pages=5, max_step_retries=2,
                    retry_backoff_s=0.0005)
    # A (12 + 14 = 26 tokens -> 4 pages) outgrows the 5-page pool while
    # B (10 + 14 = 24 -> 3 pages) holds pages; A is the OLDER admission,
    # so when its 4th page comes due the strictly-younger B is evicted
    first = sch.submit(prompts[0], max_new_tokens=14)
    second = sch.submit(prompts[1], max_new_tokens=14)
    plan = faults.FaultPlan(faults.FailStep(at_step=3, times=1))
    order = []
    orig_admit = sch._admit

    def probe_admit():
        before = set(id(r) for r in sch.active.values())
        orig_admit()
        for r in sch.active.values():
            if id(r) not in before:
                order.append(r)

    sch._admit = probe_admit
    with faults.injecting(plan):
        # grow both until one must evict the other
        for _ in range(200):
            if not sch.step() and sch.queue.peek() is None:
                break
    assert second.n_evictions >= 1, (
        "page pressure must have evicted the younger request")
    assert first.n_evictions == 0  # a strict total order: no thrash
    assert sch.metrics()["step_retries"] >= 1  # the stall really fired
    assert sch.metrics()["quarantined"] == 0   # transient: no quarantine
    # the evicted request re-admitted (original seq kept it at the
    # front of its priority class)
    assert order.count(second) >= 2
    toks = [first.out_tokens, second.out_tokens]
    assert toks == _sequential(eng1, prompts[:2], 14)
    sch.pool.check()
    del total


# ---------- Scheduler.metrics() key schema (ISSUE 11 satellite) ----------

# the metrics() contract: these keys travel together on EVERY read —
# a dashboard keyed on one of them must never silently lose another
# (docs/observability.md "Serve metrics")
_METRICS_BASE_KEYS = {
    "n", "tokens_per_s", "quarantined", "step_retries",
    "submitted", "rejected", "admitted", "evicted", "preempted",
    "retries", "guard_trips", "steps", "tokens_out",
    "queue_depth", "active_slots", "pool_free_pages", "pool_used_pages",
}
_METRICS_LATENCY_KEYS = {"ttft_p50_us", "ttft_p99_us",
                         "tpot_p50_us", "tpot_p99_us"}
_METRICS_COUNTER_KEYS = (
    "submitted", "rejected", "admitted", "evicted", "preempted",
    "retries", "guard_trips", "steps", "tokens_out", "quarantined",
    "step_retries",
)


def test_metrics_keys_travel_together(eng1, prompts):
    sch = Scheduler(eng1, **GEO)
    m0 = sch.metrics()
    assert _METRICS_BASE_KEYS <= set(m0), (
        _METRICS_BASE_KEYS - set(m0))
    for r in prompts:
        sch.submit(r, max_new_tokens=4)
    sch.run()
    m1 = sch.metrics()
    # the full schema including the latency summary once requests
    # finished; every counter is an int, every gauge-like key >= 0
    assert (_METRICS_BASE_KEYS | _METRICS_LATENCY_KEYS) <= set(m1), (
        (_METRICS_BASE_KEYS | _METRICS_LATENCY_KEYS) - set(m1))
    for k in _METRICS_COUNTER_KEYS:
        assert isinstance(m1[k], int) and m1[k] >= 0, (k, m1[k])
    assert m1["n"] == len(prompts) and m1["admitted"] == len(prompts)
    assert m1["tokens_out"] == 4 * len(prompts)
    assert m1["ttft_p99_us"] >= m1["ttft_p50_us"] > 0


def test_metrics_counters_monotone_across_steps(eng1, prompts):
    sch = Scheduler(eng1, **GEO)
    for r in prompts:
        sch.submit(r, max_new_tokens=5)
    prev = sch.metrics()
    for _ in range(200):
        progressed = sch.step()
        cur = sch.metrics()
        for k in _METRICS_COUNTER_KEYS:
            assert cur[k] >= prev[k], (
                f"counter {k!r} moved backwards: {prev[k]} -> {cur[k]}")
        prev = cur
        if not progressed and sch.queue.peek() is None:
            break
    assert prev["steps"] > 0 and prev["tokens_out"] == 5 * len(prompts)


def test_metrics_match_injected_failstep_plan(eng1, prompts):
    """Quarantine/retry counts must equal what the injected FailStep
    plan implies: times == retry budget + 1 consumes exactly one
    quarantine after exactly max_step_retries retries, and the trip
    counter mirrors every failed attempt."""
    from triton_dist_tpu import faults

    sch = Scheduler(eng1, **GEO, max_step_retries=2)
    plan = faults.FaultPlan(faults.FailStep(at_step=1, times=3))
    with faults.injecting(plan):
        for r in prompts[:2]:
            sch.submit(r, max_new_tokens=4)
        sch.run()
    m = sch.metrics()
    assert m["step_retries"] == 3  # 1 first try + 2 retries, all failed
    assert m["retries"] == 3
    assert m["quarantined"] == 1
    assert m["guard_trips"] == 3  # one DeadlineExceeded per attempt
    # survivors finished; the registry histogram streamed their TTFT
    assert sch.obs.hist_count("serve_ttft_us") == m["n"] >= 1
    # and a transient fault (fewer times than the budget) quarantines
    # nothing while still counting its retries
    sch2 = Scheduler(eng1, **GEO, max_step_retries=2)
    with faults.injecting(faults.FaultPlan(
            faults.FailStep(at_step=1, times=1))):
        sch2.submit(prompts[0], max_new_tokens=4)
        sch2.run()
    m2 = sch2.metrics()
    assert m2["quarantined"] == 0 and m2["step_retries"] == 1
    assert m2["n"] == 1
