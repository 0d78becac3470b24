"""The paged pool's layout and the serve step's round trip through it
(ISSUE 33): token-major pages (L, P, page, Hkv, D), read as each
layer's dense view by `KVCache.layer_view` / `dense_view` and written
by `KVCache.scatter_step` from the step's rows, page slabs in place.

Both are pure copies, so everything here is BITWISE against a numpy
model of the pool: a page is `page` whole token rows, position t of
slot s lives at `pool[:, table[s, t // page], t % page]`. The two
boundaries that speak another order — the migration image and the
megakernel bridge — are held to their documented order.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from triton_dist_tpu.models import Engine, ModelConfig
from triton_dist_tpu.models.kv_cache import KVCache
from triton_dist_tpu.runtime import make_mesh
from triton_dist_tpu.serve import KVPool

PAGE, MAX_PAGES, SLOTS = 64, 6, 5
POOL_SPEC = P(None, None, None, "tp")  # the kv-head axis


def _pool(tp: int, page=PAGE, max_pages=MAX_PAGES, slots=SLOTS, seed=0):
    """A KVPool over a tp-device mesh with random pages (page 0 too:
    the step must leave it as it is). The engine is the four attributes
    the pool reads; no parameter is built."""
    cfg = ModelConfig.tiny(num_q_heads=4, num_kv_heads=4, head_dim=8,
                           max_positions=page * max_pages,
                           dtype="bfloat16")
    mesh = make_mesh(mesh_shape=(tp,), axis_names=("tp",))
    eng = types.SimpleNamespace(cfg=cfg, mesh=mesh, axis="tp",
                                max_len=page * max_pages)
    pool = KVPool(eng, slots=slots, page=page)
    rng = np.random.default_rng(seed)
    for name in ("k", "v"):
        old = getattr(pool, name)
        assert old.shape == (cfg.num_layers, 1 + slots * max_pages, page,
                             cfg.num_kv_heads, cfg.head_dim)
        setattr(pool, name, jax.device_put(
            jnp.asarray(rng.standard_normal(old.shape), old.dtype),
            old.sharding))
    return pool, mesh, rng


def _model_view(pages: np.ndarray, table: np.ndarray) -> np.ndarray:
    """The numpy model's dense (L, B, T, Hkv, D) view."""
    L, _, page, hkv, d = pages.shape
    b, maxp = table.shape
    out = np.zeros((L, b, maxp * page, hkv, d), pages.dtype)
    for s in range(b):
        for t in range(maxp * page):
            out[:, s, t] = pages[:, table[s, t // page], t % page]
    return out


def _model_scatter(pages, rows, table, lengths, n_valid):
    """Valid columns land on their table pages; nothing else moves."""
    out = pages.copy()
    page = pages.shape[2]
    for s in range(table.shape[0]):
        for j in range(int(n_valid[s])):
            t = int(lengths[s]) + j
            out[:, table[s, t // page], t % page] = rows[:, s, j]
    return out


def _step(mesh, pool, rows_k, rows_v, n_valid):
    """What the serve step does to the pool, per rank: every layer's
    view, then the rows written back."""
    def per_rank(pool_k, pool_v, rows_k, rows_v, table, lengths, n_valid):
        cache = KVCache(pool_k, pool_v, lengths, table)
        views = [cache.layer_view(i) for i in range(pool_k.shape[0])]
        k_view = jnp.stack([kv[0] for kv in views])
        v_view = jnp.stack([kv[1] for kv in views])
        new_k, new_v = KVCache.scatter_step(
            (pool_k, pool_v), (rows_k, rows_v), table, lengths, n_valid)
        return k_view, v_view, new_k, new_v

    heads = P(None, None, None, "tp")  # rows and views: (L, B, *, Hkv, D)
    fn = jax.jit(jax.shard_map(
        per_rank, mesh=mesh,
        in_specs=(POOL_SPEC, POOL_SPEC, heads, heads, P(), P(), P()),
        out_specs=(heads, heads, POOL_SPEC, POOL_SPEC), check_vma=False))
    return fn(pool.k, pool.v, rows_k, rows_v, jnp.asarray(pool.table),
              jnp.asarray(pool.lengths), jnp.asarray(n_valid, jnp.int32))


def _lay_out(pool, case: str, chunk: int):
    """Admit the case's slots; returns n_valid (SLOTS,). Every case
    holds a full row, a one-token row and a padding-only row."""
    full, page = chunk, pool.page
    if case == "aligned":
        # lengths on page boundaries: a full row of 128 covers exactly
        # two pages, its window's third slab has no row of it
        lengths = [page, 0, 2 * page, page, 3 * page]
        n_valid = [full, full, 1, 0, min(full, 77)]
    elif case == "unaligned":
        # a full row of 128 from 37 crosses THREE pages (37..164), from
        # 65 two and a piece; the last slot ends flush with the table
        lengths = [37, 65, 100, 70, pool.t_max - full]
        n_valid = [full, full, 1, 0, full]
    else:
        assert case == "cow"
        lengths = [page, 0, 7, 0, 0]
        n_valid = [1, 0, full, 0, 0]
    for s, n in enumerate(lengths):
        if case == "cow" and s == 1:
            continue
        pool.admit(s, n + chunk)
        pool.lengths[s] = n
    if case == "cow":
        # slot 1 shares slot 0's first page, takes a private copy of it
        # and then writes INTO the copy (the misaligned caller `cow`
        # exists for): the donor's page must not move
        donor = pool._pages[0][0]
        pool.share(1, [donor], page + chunk)
        mine = pool.cow(1, 0)
        assert mine != donor and pool.refcount(donor) == 1
        pool.lengths[1] = page - 3
        n_valid[1] = min(full, 5)
    pool.check()
    return np.asarray(n_valid, np.int32)


@pytest.mark.parametrize("tp", [1, 4], ids=["tp1", "heads-sharded-4"])
@pytest.mark.parametrize("case", ["aligned", "unaligned", "cow"])
@pytest.mark.parametrize("chunk", [1, 128])
def test_view_and_scatter_round_trip_bitwise(chunk, case, tp):
    pool, mesh, rng = _pool(tp)
    before = {n: np.array(getattr(pool, n)) for n in ("k", "v")}
    n_valid = _lay_out(pool, case, chunk)
    if case == "cow":  # the copy is part of the model
        donor, mine = pool._pages[0][0], pool._pages[1][0]
        for pages in before.values():
            pages[:, mine] = pages[:, donor]
    L, _, _, hkv, d = pool.k.shape
    rows = {n: np.asarray(jnp.asarray(
        rng.standard_normal((L, SLOTS, chunk, hkv, d)), pool.k.dtype))
        for n in ("k", "v")}
    k_view, v_view, new_k, new_v = _step(
        mesh, pool, jnp.asarray(rows["k"]), jnp.asarray(rows["v"]), n_valid)
    got_view = {"k": k_view, "v": v_view}
    got_pool = {"k": new_k, "v": new_v}
    for n in ("k", "v"):
        # the read: every table entry's whole page, in the view's order
        np.testing.assert_array_equal(
            np.asarray(got_view[n]), _model_view(before[n], pool.table))
        # the write: the whole pool against the model — valid columns on
        # their pages, padding columns nowhere, page 0 as it was
        want = _model_scatter(before[n], rows[n], pool.table, pool.lengths,
                              n_valid)
        np.testing.assert_array_equal(np.asarray(got_pool[n]), want)
        np.testing.assert_array_equal(want[:, 0], before[n][:, 0])
        assert got_pool[n].sharding.is_equivalent_to(
            NamedSharding(mesh, POOL_SPEC), got_pool[n].ndim)
    # and the round trip: the next step's view holds the rows
    pool.k, pool.v = new_k, new_v
    pool.lengths = pool.lengths + n_valid
    view = pool.to_dense()
    for s in range(SLOTS):
        a, b = int(pool.lengths[s]) - int(n_valid[s]), int(pool.lengths[s])
        np.testing.assert_array_equal(np.asarray(view.k[:, s, a:b]),
                                      rows["k"][:, s, :b - a])
        np.testing.assert_array_equal(np.asarray(view.v[:, s, a:b]),
                                      rows["v"][:, s, :b - a])
    if case == "cow":
        np.testing.assert_array_equal(np.asarray(new_k[:, donor]),
                                      before["k"][:, donor])
    assert pool.dense_view_tokens() == view.k.shape[1] * view.k.shape[2]


@pytest.mark.parametrize("tp", [1, 4], ids=["tp1", "heads-sharded-4"])
def test_migration_image_keeps_its_order_across_pools(tp):
    """`export_pages` -> `install` into a second pool round-trips
    bitwise, and the image between them is in the order
    xslice/migrate.py documents, (L, Hkv, n_pages, page, D): the pool's
    own order is turned at this boundary and nowhere else."""
    src, _, _ = _pool(tp, page=8, max_pages=4, slots=2, seed=1)
    dst, _, _ = _pool(tp, page=8, max_pages=4, slots=2, seed=2)
    n_tokens = 19  # three pages, the last one partly live
    src.admit(1, n_tokens)
    src.lengths[1] = n_tokens
    dst.admit(0, 8)  # so the image lands on other page ids
    k_img, v_img = src.export_pages(1, n_tokens)
    L, _, page, hkv, d = src.k.shape
    assert k_img.shape == v_img.shape == (L, hkv, 3, page, d)
    for img, pages in ((k_img, src.k), (v_img, src.v)):
        pages = np.asarray(pages)
        for i, pg in enumerate(src._pages[1]):
            for h in range(hkv):
                np.testing.assert_array_equal(img[:, h, i],
                                              pages[:, pg, :, h])
    dst.install(1, k_img, v_img, n_tokens)
    dst.check()
    assert dst._pages[1] != src._pages[1] and dst.lengths[1] == n_tokens
    a, b = src.to_dense(), dst.to_dense()
    np.testing.assert_array_equal(np.asarray(b.k[:, 1, :n_tokens]),
                                  np.asarray(a.k[:, 1, :n_tokens]))
    np.testing.assert_array_equal(np.asarray(b.v[:, 1, :n_tokens]),
                                  np.asarray(a.v[:, 1, :n_tokens]))
    k_back, v_back = dst.export_pages(1, n_tokens)
    np.testing.assert_array_equal(k_back, k_img)
    np.testing.assert_array_equal(v_back, v_img)


def test_mega_bridge_hands_over_the_megakernel_s_order():
    """`as_mega_cache` keeps page ids and table and hands the
    megakernel its own (L, Hkv, P, page, D): a transposed copy."""
    pool, _, _ = _pool(1, page=8, max_pages=4, slots=2, seed=3)
    pool.admit(0, 12)
    mc = pool.as_mega_cache()
    L, p, page, hkv, d = pool.k.shape
    assert mc.k.shape == mc.v.shape == (L, hkv, p, page, d)
    np.testing.assert_array_equal(
        np.asarray(mc.k), np.asarray(pool.k).transpose(0, 3, 1, 2, 4))
    np.testing.assert_array_equal(np.asarray(mc.table), pool.table)


def test_forward_lays_the_step_s_rows_into_its_kv_cache():
    """`forward` through `Engine.decode_step` (no cell runs it): the
    layer scan hands out the step's rows and `forward` lays them into
    the KVCache — position `length` of every layer and nothing else,
    the length advanced — and what it laid there is what a one-pass
    prefill of the same tokens computes."""
    cfg = ModelConfig.tiny(num_q_heads=4, num_kv_heads=2, max_positions=32)
    mesh = make_mesh(mesh_shape=(1,), axis_names=("tp",))
    eng = Engine(cfg, mesh, prefill_mode="xla", decode_mode="xla",
                 donate_cache=False, max_len=32)
    rng = np.random.default_rng(5)
    ids = rng.integers(0, cfg.vocab_size, (2, 7)).astype(np.int32)
    _, c0 = eng.prefill(ids[:, :5])
    assert np.asarray(c0.length).tolist() == [5, 5]
    assert not np.asarray(c0.k[:, :, 5:]).any()  # untouched tail
    logits, c1 = eng.decode_step(ids[:, 5], c0)
    _, c2 = eng.decode_step(ids[:, 6], c1)
    assert np.asarray(c2.length).tolist() == [7, 7]
    for name in ("k", "v"):
        old, new, last = (np.asarray(getattr(c, name), np.float32)
                          for c in (c0, c1, c2))
        np.testing.assert_array_equal(new[:, :, :5], old[:, :, :5])
        np.testing.assert_array_equal(new[:, :, 6:], old[:, :, 6:])
        assert np.abs(new[:, :, 5]).min(axis=(-1, -2)).all()  # every layer
        np.testing.assert_array_equal(last[:, :, :6], new[:, :, :6])
    # the same seven tokens in one pass: the rows `forward` kept are
    # the rows attention saw
    ref_logits, ref = eng.prefill(ids)
    np.testing.assert_allclose(np.asarray(c2.k, np.float32)[:, :, :7],
                               np.asarray(ref.k, np.float32)[:, :, :7],
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(c2.v, np.float32)[:, :, :7],
                               np.asarray(ref.v, np.float32)[:, :, :7],
                               atol=2e-5, rtol=2e-5)
    step_logits, _ = eng.decode_step(ids[:, 6], c1)
    np.testing.assert_allclose(np.asarray(step_logits),
                               np.asarray(ref_logits), atol=2e-4, rtol=2e-4)
