"""Radix prefix cache + KVPool refcount/COW plane (ISSUE 14).

The load-bearing property: a prefix-HIT request's token stream is
BITWISE equal to its cold run — greedy and sampled — because the
serve step's row numerics are placement/
chunk-alignment independent (the tier-1-pinned eviction property), so
a donor's cached KV pages are bitwise the pages the hit request's own
prefill would have written. Around it: the KVPool refcount/share/cow
entry points and their generalized leak/alias assertions, the trie's
LRU reclaim with the shared-page refusal, pool-pressure integration,
and the ledger's prefill collapse on hits.

Wall budget: ONE engine geometry for the whole module (module-scoped
fixtures, GEO shared with tests/test_serve.py's shapes).
"""

import numpy as np
import pytest

from triton_dist_tpu.models import Engine, ModelConfig
from triton_dist_tpu.runtime import make_mesh
from triton_dist_tpu.serve import KVPool, PoolExhausted, PrefixCache, Scheduler

GEO = dict(slots=3, chunk=4, page=8)
BLOCK = 8  # trie block == page: every prompt >= 9 tokens can hit


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
    rng = np.random.default_rng(7)
    v = eng1.cfg.vocab_size
    # >= BLOCK + 1 tokens each, so every prompt can hit a full block
    return [list(map(int, rng.integers(0, v, n))) for n in (12, 11, 9)]


def _cold(eng, prompts, gen, **kw):
    """Sequential stepwise baseline (the bit-identity oracle)."""
    return [
        list(map(int, np.asarray(
            eng.serve(np.asarray([p], np.int32), gen, slots=GEO["slots"],
                      chunk=GEO["chunk"], page=GEO["page"], **kw))[0]))
        for p in prompts
    ]


# ---------- KVPool refcount / share / cow units ----------


def test_pool_ref_unref_keeps_pages_alive(eng1):
    pool = KVPool(eng1, slots=2, page=8, total_pages=4)
    pool.admit(0, 16)  # 2 pages
    held = list(pool._pages[0])
    pool.ref_pages(held)  # external holder (the cache)
    pool.release(0)
    pool.check()
    assert pool.free_pages() == 2  # refs keep the donor's pages
    assert all(pool.refcount(p) == 1 for p in held)
    assert pool.unref_pages(held) == 2
    assert pool.free_pages() == 4
    pool.check()


def test_pool_share_admits_over_held_pages(eng1):
    pool = KVPool(eng1, slots=2, page=8, total_pages=4)
    pool.admit(0, 16)
    held = list(pool._pages[0])
    pool.ref_pages(held)
    pool.release(0)
    pool.share(1, held, 20)  # 3 pages total: 2 shared + 1 fresh
    assert pool.lengths[1] == 16  # shared coverage
    assert list(pool.table[1, :3]) == held + [pool._pages[1][2]]
    assert all(pool.refcount(p) == 2 for p in held)
    pool.check()
    pool.release(1)
    assert all(pool.refcount(p) == 1 for p in held)  # cache still holds
    pool.check()


def test_pool_share_is_all_or_nothing(eng1):
    pool = KVPool(eng1, slots=2, page=8, total_pages=2)
    pool.admit(0, 16)
    held = list(pool._pages[0])
    pool.ref_pages(held)
    pool.release(0)
    pool.share(1, held, 16)  # exact fit, no fresh page needed
    pool.release(1)
    with pytest.raises(PoolExhausted):
        pool.share(1, held, 24)  # 1 fresh needed, 0 free
    assert pool._pages[1] is None  # nothing half-claimed
    assert all(pool.refcount(p) == 1 for p in held)
    pool.check()


def test_pool_cow_copies_shared_page(eng1):
    import jax.numpy as jnp

    pool = KVPool(eng1, slots=2, page=8, total_pages=4)
    pool.admit(0, 8)
    (pg,) = pool._pages[0]
    # token-major pages: (L, P, page, Hkv, D)
    pool.k = pool.k.at[:, pg].set(jnp.ones_like(pool.k[:, pg]))
    assert pool.cow(0, 0) == pg  # exclusive: no-op
    pool.ref_pages([pg])
    new = pool.cow(0, 0)
    assert new != pg and pool.table[0, 0] == new
    assert pool.refcount(pg) == 1 and pool.refcount(new) == 1
    np.testing.assert_array_equal(np.asarray(pool.k[:, new]),
                                  np.asarray(pool.k[:, pg]))
    assert float(np.asarray(pool.k[:, new], np.float32).min()) == 1.0
    pool.check()
    pool.release(0)
    pool.unref_pages([pg])
    pool.check()


def test_pool_check_catches_refcount_drift(eng1):
    pool = KVPool(eng1, slots=2, page=8, total_pages=4)
    pool.admit(0, 8)
    pool._refs[pool._pages[0][0]] += 1  # phantom holder
    with pytest.raises(AssertionError, match="refcount drift"):
        pool.check()


def test_pool_double_free_still_asserts(eng1):
    pool = KVPool(eng1, slots=2, page=8, total_pages=4)
    pool.admit(0, 8)
    pool.release(0)
    with pytest.raises(AssertionError, match="double free"):
        pool.release(0)


# ---------- trie units ----------


def _pool_cache(eng, total_pages=12):
    pool = KVPool(eng, slots=3, page=8, total_pages=total_pages)
    return pool, PrefixCache(pool, block=BLOCK)


def _fill_slot(pool, slot, n_tokens):
    pool.admit(slot, n_tokens)
    return pool.table[slot]


def test_trie_match_insert_roundtrip(eng1):
    pool, cache = _pool_cache(eng1)
    toks = list(range(20))
    row = _fill_slot(pool, 0, 20)  # 3 pages
    assert cache.match(toks) == (0, [])
    assert cache.insert(toks, row) == 2  # two FULL blocks of 8
    n, pages = cache.match(toks)
    assert n == 16 and pages == list(row[:2])
    # a prompt that only shares the first block matches one block
    n2, pages2 = cache.match(toks[:8] + [99, 98, 97])
    assert n2 == 8 and pages2 == [int(row[0])]
    # match is capped at len-1: a 17-token prompt uses 2 full blocks
    # only when 17 > 16
    assert cache.match(toks[:16])[0] == 8
    cache.check()
    pool.check()


def test_trie_insert_dedups_and_lru_reclaim(eng1):
    pool, cache = _pool_cache(eng1)
    row0 = _fill_slot(pool, 0, 9)
    row1 = _fill_slot(pool, 1, 9)
    a = [1] * 8 + [2]
    b = [3] * 8 + [4]
    cache.insert(a, row0)
    cache.insert(b, row1)
    assert cache.insert(a, row0) == 0  # dedup
    assert cache.n_blocks() == 2
    pool.release(0)
    pool.release(1)
    cache.match(b)  # bump b's LRU stamp
    freed = cache.reclaim(1)
    assert freed == 1 and cache.n_blocks() == 1
    assert cache.match(b)[0] == 8  # LRU victim was a, not b
    assert cache.match(a)[0] == 0
    cache.check()
    pool.check()


def test_trie_drop_shared_block_refused(eng1):
    """The chaos-cell polarity as a unit: force-dropping a node whose
    pages a live slot still reads must be REFUSED (assert), and
    pressure reclaim must skip it."""
    pool, cache = _pool_cache(eng1)
    row0 = _fill_slot(pool, 0, 9)
    a = [1] * 8 + [2]
    cache.insert(a, row0)
    pool.release(0)
    # a live reader shares the cached block
    n, pages = cache.match(a + [5])
    pool.share(2, pages, 10)
    (node,) = list(cache._iter_leaves())
    with pytest.raises(AssertionError, match="refusing to evict"):
        cache._drop(node)
    assert cache.reclaim(8) == 0  # nothing unshared to reclaim
    assert cache.n_blocks() == 1
    pool.release(2)
    assert cache.reclaim(8) == 1  # reader gone: now droppable
    pool.check()


def test_trie_max_blocks_bounds_and_reclaims(eng1):
    pool, cache = _pool_cache(eng1, total_pages=12)
    cache.max_blocks = 2
    for slot, first in enumerate((1, 2, 3)):
        row = _fill_slot(pool, slot, 9)
        cache.insert([first] * 8 + [0], row)
        pool.release(slot)
    assert cache.n_blocks() == 2  # LRU block was reclaimed to fit
    cache.check()
    pool.check()


# ---------- scheduler-level bit-identity ----------


def test_prefix_hot_cold_bitwise_host(eng1, prompts):
    cold = _cold(eng1, prompts, 6)
    sch = Scheduler(eng1, prefix_cache=True, prefix_block=BLOCK, **GEO)
    first = [sch.submit(p, max_new_tokens=6) for p in prompts]
    sch.run()
    hot = [sch.submit(p, max_new_tokens=6) for p in prompts]
    sch.run()
    assert [r.out_tokens for r in first] == cold
    assert [r.out_tokens for r in hot] == cold
    assert all(r.prefix_len >= BLOCK for r in hot)
    m = sch.metrics()
    assert m["prefix_hits"] >= len(prompts)
    assert m["prefix_pages_shared"] >= len(prompts)
    sch.pool.check()
    sch.prefix.check()


def test_prefix_hot_cold_bitwise_host_sampled(eng1, prompts):
    sch = Scheduler(eng1, prefix_cache=True, prefix_block=BLOCK, **GEO)

    def batch():
        reqs = [sch.submit(p, max_new_tokens=6, temperature=0.9,
                           seed=50 + i) for i, p in enumerate(prompts)]
        sch.run()
        return [r.out_tokens for r in reqs]

    cold = batch()
    hot = batch()
    assert hot == cold
    assert sch.metrics()["prefix_hits"] >= len(prompts)
    sch.pool.check()


def test_prefix_hit_survives_donor_eviction(eng1, prompts):
    """The cache's refs outlive the donor: evict the donor mid-flight,
    then admit the same prompt — the hit still streams bitwise."""
    cold = _cold(eng1, prompts[:1], 6)[0]
    sch = Scheduler(eng1, total_pages=5, prefix_cache=True,
                    prefix_block=BLOCK, **GEO)
    # donor (older) outgrows the 5-page pool at its 4th page (12 + 14
    # = 26 tokens) while the younger request holds 3 — the growth
    # eviction lands on the younger (the strict total order)
    donor = sch.submit(prompts[0], max_new_tokens=14)
    second = sch.submit(prompts[1], max_new_tokens=10)
    sch.run()
    assert donor.n_evictions + second.n_evictions > 0, (
        "pool was not constrained enough to exercise eviction")
    hot = sch.submit(prompts[0], max_new_tokens=6)
    sch.run()
    assert hot.out_tokens == cold
    sch.pool.check()
    sch.prefix.check()


def test_prefix_pressure_reclaims_cache_before_eviction(eng1, prompts):
    """Pool pressure drains UNSHARED cached blocks before evicting any
    live request (the reclaim valve in _room/_admit)."""
    sch = Scheduler(eng1, total_pages=6, prefix_cache=True,
                    prefix_block=BLOCK, **GEO)
    for p in prompts:  # populate the cache, requests finish
        sch.submit(p, max_new_tokens=2)
    sch.run()
    blocks_before = sch.prefix.n_blocks()
    assert blocks_before >= 2
    # a long request needs more pages than are free: the cache gives
    # its blocks back instead of an eviction (nothing to evict anyway)
    big = sch.submit(prompts[0] + prompts[1], max_new_tokens=12)
    sch.run()
    assert big.state.value == "finished"
    # the reclaim valve fired (an old LRU block is gone — big also
    # inserted its own new block, so count alone is not the signal)
    # and NO live request was evicted
    assert 0 in [sch.prefix.match(p)[0] for p in prompts[1:]]
    assert sch.metrics()["evicted"] == 0
    sch.pool.check()
    sch.prefix.check()


def test_prefix_hit_ledger_prefill_collapse(eng1, prompts):
    """The ledger satellite: a hit request's prefill_us collapses
    (only the residual chunks span it), prefix_hit_tokens marks the
    skip, and the close contract is untouched."""
    from triton_dist_tpu.trace.ledger import check_close

    sch = Scheduler(eng1, prefix_cache=True, prefix_block=BLOCK, **GEO)
    cold = sch.submit(prompts[0], max_new_tokens=4)
    sch.run()
    hot = sch.submit(prompts[0], max_new_tokens=4)
    sch.run()
    led = sch.ledger()
    assert check_close(led) == []
    rows = {r["request_id"]: r for r in led["requests"]}
    assert rows[cold.request_id]["prefix_hit_tokens"] == 0
    assert rows[hot.request_id]["prefix_hit_tokens"] >= BLOCK
    # the hit skipped at least one chunk step of prefill
    assert (rows[hot.request_id]["prefill_chunks"]
            < rows[cold.request_id]["prefill_chunks"])


# ---------- chooser + bench schema ----------


def test_choose_prefix_block_page_multiple():
    from triton_dist_tpu.perf_model import CHIPS, choose_prefix_block

    chip = CHIPS["TPU v5 lite"]
    dims = dict(num_layers=36, hidden=4096, inter_loc=1536, hq_loc=4,
                hkv_loc=1, head_dim=128, vocab_loc=18992, chip=chip)
    b = choose_prefix_block(page=64, t_max=4096, **dims)
    assert b % 64 == 0 and 64 <= b <= 4096
    # slower per-token prefill (bigger model) pulls the block DOWN
    # toward the page; a tiny model pushes it up
    tiny = dict(num_layers=2, hidden=128, inter_loc=64, hq_loc=2,
                hkv_loc=1, head_dim=32, vocab_loc=512, chip=chip)
    assert choose_prefix_block(page=8, t_max=256, **tiny) >= 8


def test_bench_prefix_schema_travels_together():
    import bench

    good = {
        "metric": "x", "value": 1.0, "unit": "r", "vs_baseline": 1.0,
        "prefix_hit_ttft_us": 100.0, "prefix_cold_ttft_us": 400.0,
        "prefix_hit_ttft": 0.25,
    }
    assert bench.check_result(good) == []
    bad = dict(good)
    del bad["prefix_cold_ttft_us"]
    assert any("travel together" in p for p in bench.check_result(bad))
