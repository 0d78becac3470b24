"""Shared by the tests of the serve step's head (ISSUE 36): the
one-emission step takes each slot's hidden row at column `n_valid - 1`
BEFORE the final norm and the vocabulary projection. What it must
equal is the all-rows form: every row through the norm and the head,
the column taken afterwards, sampled under the same keys.
"""

import numpy as np

import jax
import jax.numpy as jnp

from triton_dist_tpu.serve import RequestState, Scheduler
from triton_dist_tpu.serve.worker import sampling_keys

SLOTS, CHUNK, PAGE = 4, 4, 8
PROMPT_LENS = (12, 10, 9, 7)
# the acceptance tolerance; the CPU backend reads 0.0 at these sizes
# (the tests that use it say so with assert_array_equal where it does)
ATOL = 1e-5


def n_valid_for(width: int):
    """Mixed rows a slot: none, one, some, the whole width."""
    return np.minimum(np.asarray([0, 1, 3, CHUNK], np.int32), width)


def temps_for(sampled: bool):
    return np.asarray([0.7, 0.0, 0.9, 1.3] if sampled else [0.0] * SLOTS,
                      np.float32)


def decoding_scheduler(eng, vocab: int, **kw):
    """A scheduler whose four slots all hold a prefilled request: a
    pool state with real lengths, pages and (hybrid) recurrent state."""
    rng = np.random.default_rng(3)
    sch = Scheduler(eng, slots=SLOTS, chunk=CHUNK, page=PAGE, **kw)
    reqs = [sch.submit(list(map(int, rng.integers(0, vocab, n))),
                       max_new_tokens=8) for n in PROMPT_LENS]
    while any(r.state is not RequestState.DECODE for r in reqs):
        sch.step()
    return sch


def step_args(sch, width: int, sampled: bool):
    """(tokens (K, width), table, lengths, n_valid, temps, keys (K, 2))
    for one raw call of a compiled step over `sch`'s pool."""
    rng = np.random.default_rng(width)
    vocab = sch.worker.engine.cfg.vocab_size
    tokens = rng.integers(0, vocab, (SLOTS, width)).astype(np.int32)
    keys = sampling_keys(np.arange(SLOTS) + 11, np.arange(SLOTS) + 5)
    return (jnp.asarray(tokens), jnp.asarray(sch.pool.table),
            jnp.asarray(sch.pool.lengths), jnp.asarray(n_valid_for(width)),
            jnp.asarray(temps_for(sampled)), jnp.asarray(keys))


def sample_afterwards(logits, n_valid, temps, keys):
    """The all-rows form's (tok, last): (K, C, V) logits, column
    `n_valid - 1` taken afterwards, then the step's own sampler."""
    from triton_dist_tpu.models.engine import _sample_last

    last = logits[jnp.arange(logits.shape[0]), jnp.maximum(n_valid - 1, 0)]
    return _sample_last(last, temps, keys), last


def assert_same_step(got, want, bitwise: bool = True):
    """(tok, last, cache) of the step against the all-rows form's."""
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    a, b = np.asarray(got[1]), np.asarray(want[1])
    assert a.shape == b.shape and a.dtype == np.float32
    # the rows are not all zero (logits of order 0.1; 0.006 where a tied
    # table is drawn under an embedding multiplier)
    assert float(np.abs(b).max()) > 1e-3
    if bitwise:
        np.testing.assert_array_equal(a, b)
    else:
        np.testing.assert_allclose(a, b, atol=ATOL, rtol=0)
    for x, y in zip(jax.tree.leaves(got[2]), jax.tree.leaves(want[2])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def hybrid_all_rows_step(eng, sch):
    """The hybrid family's all-rows form of `sch`'s wide step: the
    chunk's (K, C, H) hidden rows all through the final norm and the
    head, then `sample_afterwards` (the family compiles no
    per-position step to stand for it)."""
    from triton_dist_tpu.models import hybrid
    from triton_dist_tpu.models.kv_cache import KVCache
    from triton_dist_tpu.plan.planner import (
        route_hybrid_attention,
        route_window_attention,
    )

    cfg, pool = eng.cfg, sch.pool
    attn_impl = route_hybrid_attention(cfg, SLOTS, CHUNK,
                                       pool.max_pages * PAGE)
    window_impl = (route_window_attention(cfg, SLOTS, CHUNK)
                   if cfg.num_window_layers else None)

    def step(params, tokens, cache, table, lengths, n_valid, temps, keys):
        cache = hybrid.Cache.of(cfg, cache)
        x, rows, rec, conv, win, _ = hybrid.chunk_hidden(
            cfg, params, tokens, cache, table, lengths, n_valid, attn_impl,
            window_impl)
        logits = hybrid.head_logits(cfg, params, x)
        assert logits.shape == (SLOTS, CHUNK, cfg.vocab_size)
        tok, last = sample_afterwards(logits, n_valid, temps, keys)
        pages = KVCache.scatter_step(cache.pages, rows, table, lengths,
                                     n_valid)
        return tok, last, hybrid.Cache(pages, rec, conv, win).flat()

    return jax.jit(step)


def check_hybrid_step(eng, sampled: bool):
    """A hybrid member's compiled step against its all-rows form, on
    one pool state: tokens, `last` and the whole cache bit for bit."""
    sch = decoding_scheduler(eng, 256)
    args = step_args(sch, CHUNK, sampled)
    got = sch.worker._fn(eng.params, args[0], sch.pool.state, *args[1:])
    want = hybrid_all_rows_step(eng, sch)(
        eng.params, args[0], sch.pool.state, *args[1:])
    assert got[1].shape == (SLOTS, eng.cfg.vocab_size)
    assert_same_step(got[:3], want)


def check_hybrid_lowering(cfg, mesh):
    """No (K, C, V) float32 array in the member's lowered step, at a
    vocabulary that no other width of the tiny model equals."""
    import dataclasses

    from triton_dist_tpu.models import Engine

    vocab = 384
    eng = Engine(dataclasses.replace(cfg, vocab_size=vocab), mesh,
                 max_len=cfg.max_positions, fast_init=True,
                 donate_cache=False)
    sch = Scheduler(eng, slots=SLOTS, chunk=CHUNK, page=PAGE)
    args = step_args(sch, CHUNK, False)
    text = sch.worker._fn.lower(
        eng.params, args[0], sch.pool.state, *args[1:]).as_text()
    assert f"tensor<{SLOTS}x{vocab}xf32>" in text
    assert f"tensor<{SLOTS}x{CHUNK}x{vocab}xf32>" not in text
