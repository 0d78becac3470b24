"""KV cache — preallocated, functionally updated.

TPU-native analog of the reference's KV_Cache
(ref: python/triton_dist/models/kv_cache.py:29-66): there, per-layer torch
tensors mutated in place; here, one stacked array per model updated
functionally and donated through the jit'd decode step, which XLA turns
into the same in-place update (buffer donation is the TPU idiom for
mutation under jit).

Shapes (per tp rank): k/v (L, B, T_max, Hkv_loc, D). Inside shard_map the
head axis is the tp-sharded one.

The serve plane's cache is PAGED (serve/kv_pool.KVPool): k/v are page
pools, token-major (L, P, page, Hkv, D), and a `table` maps each
sequence's page grid onto them. A page is `page` whole token rows of a
layer's dense (B, T, Hkv, D) view, so the step's round trip moves each
byte once: every layer reads its own view through the table
(`layer_view`), and the step's new rows go back as page slabs in place
(`scatter_step`). No whole-model view is built in between.

What a page keeps of a token is the family's (`ModelConfig.page_arrays`):
keys and values a kv head, the two pools k and v; or, under latent
attention, ONE row a token from which both come, (L, P, page, 1, W),
and then `v` is None: there is no second pool.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from triton_dist_tpu.layers.parts import part


class KVCache(NamedTuple):
    k: jax.Array  # (L, B, T_max, Hkv, D); paged: (L, P, page, Hkv, D)
    v: Optional[jax.Array]  # None: the one latent pool is `k`
    length: jax.Array  # (B,) valid entries per sequence
    # a PAGED cache (the serve plane's): k/v are page pools and `table`
    # (B, MAXP) maps each sequence's page grid onto pool pages
    table: Optional[jax.Array] = None

    @staticmethod
    def of(pools, length, table=None) -> "KVCache":
        """The cache over `pools`: (k, v), or a latent cache's (k,)."""
        return KVCache(pools[0], pools[1] if len(pools) == 2 else None,
                       length, table)

    @property
    def pools(self) -> tuple:
        """The arrays that hold pages: (k, v), or (k,) of a latent cache."""
        return (self.k,) if self.v is None else (self.k, self.v)

    @part("pool.gather")
    def layer_view(self, i):
        """Layer i's dense (k, v), each (B, T, Hkv, D) (a latent
        cache's one (B, T, 1, W) array, as a 1-tuple) — what the layer
        lays its rows into and attends over (models/dense.py's layer
        scan calls this in its body). Of a paged cache it is the
        layer's pages gathered through the table, `pool[i, table]` with
        the two page axes read as one: each page is moved once, as one
        contiguous piece, into a buffer that is the layer's own — no
        transposition, and no copy of a whole-model view to slice it
        from. A pure copy, so values round-trip bitwise: paging is an
        allocation policy, never a numeric one. Unallocated table
        entries point at page 0 (the pool's reserved null page); what
        they gather sits beyond each sequence's length and is masked
        by attention's kv_len/causal bounds."""
        if self.table is None:
            return tuple(pool[i] for pool in self.pools)
        b, maxp = self.table.shape
        page = self.k.shape[2]
        return tuple(pool[i, self.table].reshape(
            (b, maxp * page) + pool.shape[3:]) for pool in self.pools)

    @staticmethod
    def dense_view(pool_k, pool_v, table, lengths) -> "KVCache":
        """Every layer's `layer_view` of a paged pool at once, as a
        dense (L, B, T, Hkv, D) KVCache: `pool[:, table]` with the two
        page axes read as one. The SNAPSHOT form (KVPool.to_dense, the
        megakernel bridge's reference, tests); the serve step never
        builds it — its layers each read their own view."""
        L, _, page = pool_k.shape[:3]
        B = table.shape[0]
        t = KVCache.dense_view_tokens(table.shape, page) // B

        def view(pool):
            return None if pool is None else pool[:, table].reshape(
                (L, B, t) + pool.shape[3:])

        return KVCache(view(pool_k), view(pool_v), lengths)

    @staticmethod
    def dense_view_tokens(table_shape, page: int) -> int:
        """Token positions a step's `layer_view`s of a `table_shape`
        table gather, a layer and kv head: every entry's whole page,
        live or null, each moved once. The gather's own size — the
        serve plane's `serve_kv_tokens_gathered` counts this, so the
        two change together."""
        slots, maxp = table_shape
        return slots * maxp * page

    @staticmethod
    @part("pool.scatter")
    def scatter_step(pools, rows, table, lengths, n_valid):
        """A serve step's K/V rows back into the paged pool — the write
        path beside `layer_view`. `pools` are the page arrays, (k, v)
        or a latent cache's one, and `rows` (each (L, B, C, Hkv, D))
        the rows the layers computed for the step's C columns; slot
        s's columns [0, n_valid[s]) belong at positions lengths[s] ..
        of its pages, the rest are padding and are written nowhere.

        A slot's rows touch at most `(C + page - 2) // page + 1` pages,
        each a slab (L, 1, page, Hkv, D) of the pool, so the write is a
        loop over the slots of read the slabs, lay the rows over them,
        keep the pool's own row wherever the step has no valid one, and
        put the slabs back with `dynamic_update_slice`: the donated pool
        is updated in place, at every C alike. A slab with no valid row
        is the null page's (page 0), so an index past a slot's pages
        never reaches a live page."""
        page = pools[0].shape[2]
        slots, max_pages = table.shape
        chunk = rows[0].shape[2]
        touch = (chunk + page - 2) // page + 1

        def one_slot(s, pools):
            first, at = lengths[s] // page, lengths[s] % page
            col = jnp.arange(touch * page) - at  # window row -> column
            mine = (col >= 0) & (col < n_valid[s])
            logical = jnp.minimum(first + jnp.arange(touch), max_pages - 1)
            pages = jnp.where(mine.reshape(touch, page).any(axis=1),
                              table[s, logical], 0)

            def into(pool, rows):
                L, _, _, Hkv, D = pool.shape
                slab = (L, 1, page, Hkv, D)
                old = jnp.concatenate(
                    [jax.lax.dynamic_slice(pool, (0, pages[i], 0, 0, 0),
                                           slab) for i in range(touch)],
                    axis=2)
                row = jax.lax.dynamic_slice(
                    rows, (0, s, 0, 0, 0), (L, 1, chunk, Hkv, D))
                new = jax.lax.dynamic_update_slice(
                    old, row.astype(pool.dtype), (0, 0, at, 0, 0))
                new = jnp.where(mine[None, None, :, None, None], new, old)
                for i in range(touch):
                    pool = jax.lax.dynamic_update_slice(
                        pool, new[:, :, i * page:(i + 1) * page],
                        (0, pages[i], 0, 0, 0))
                return pool

            return tuple(into(p, r) for p, r in zip(pools, rows))

        return jax.lax.fori_loop(0, slots, one_slot, tuple(pools))

    @staticmethod
    def create(num_layers, batch, max_len, num_kv_heads, head_dim,
               dtype=jnp.bfloat16) -> "KVCache":
        shape = (num_layers, batch, max_len, num_kv_heads, head_dim)
        return KVCache(
            k=jnp.zeros(shape, dtype),
            v=jnp.zeros(shape, dtype),
            length=jnp.zeros((batch,), jnp.int32),
        )

    def advanced(self, n: int) -> "KVCache":
        return self._replace(length=self.length + n)
