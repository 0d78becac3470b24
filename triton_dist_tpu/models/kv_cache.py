"""KV cache — preallocated, functionally updated.

TPU-native analog of the reference's KV_Cache
(ref: python/triton_dist/models/kv_cache.py:29-66): there, per-layer torch
tensors mutated in place; here, one stacked array per model updated
functionally and donated through the jit'd decode step, which XLA turns
into the same in-place update (buffer donation is the TPU idiom for
mutation under jit).

Shapes (per tp rank): k/v (L, B, T_max, Hkv_loc, D). Inside shard_map the
head axis is the tp-sharded one.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class KVCache(NamedTuple):
    k: jax.Array  # (L, B, T_max, Hkv, D)
    v: jax.Array  # (L, B, T_max, Hkv, D)
    length: jax.Array  # (B,) valid entries per sequence

    @staticmethod
    def dense_view(pool_k, pool_v, table, lengths) -> "KVCache":
        """Dense (L, B, T, Hkv, D) view of a PAGED pool — the serve
        plane's read path (serve/kv_pool.KVPool): pool_k/pool_v are
        shared page pools in megakernel pool layout (L, Hkv, P, page, D)
        and `table` (B, MAXP) maps each sequence's page grid onto pool
        pages. The gather is a pure copy, so values round-trip bitwise —
        paging is an allocation policy, never a numeric one. Unallocated
        table entries point at page 0 (the pool's reserved null page);
        the garbage they gather sits beyond each sequence's `lengths`
        and is masked by attention's kv_len/causal bounds."""
        L, Hkv, _, page, D = pool_k.shape
        B = table.shape[0]
        t = KVCache.dense_view_tokens(table.shape, page) // B
        k = jnp.moveaxis(pool_k[:, :, table].reshape(L, Hkv, B, t, D),
                         1, 3)
        v = jnp.moveaxis(pool_v[:, :, table].reshape(L, Hkv, B, t, D),
                         1, 3)
        return KVCache(k, v, lengths)

    @staticmethod
    def dense_view_tokens(table_shape, page: int) -> int:
        """Token positions one `dense_view` of a `table_shape` table
        gathers, a layer and kv head: every entry's whole page, live or
        null. The gather's own size — the serve plane's
        `serve_kv_tokens_gathered` counts this, so the two change
        together."""
        slots, maxp = table_shape
        return slots * maxp * page

    @staticmethod
    def scatter_step(pool_k, pool_v, new: "KVCache", table, lengths,
                     n_valid, chunk: int):
        """A serve step's K/V rows back into the paged pool — the write
        path beside `dense_view`: the rows at positions lengths ..
        lengths + chunk of `new` (the dense view after the forward).
        Valid columns land on their table pages; padding columns are
        routed to page 0, the pool's reserved null page (their
        positions may sit past the slot's allocated pages, whose table
        entries still map to live pages of OTHER slots)."""
        page = pool_k.shape[3]
        slots, max_pages = table.shape
        bidx = jnp.arange(slots)[:, None]
        pos = lengths[:, None] + jnp.arange(chunk)[None, :]  # (K, C)
        posc = jnp.minimum(pos, max_pages * page - 1)
        valid = jnp.arange(chunk)[None, :] < n_valid[:, None]
        pg = jnp.where(valid, table[bidx, posc // page], 0)
        off = posc % page
        kn = jnp.moveaxis(new.k[:, bidx, posc], 3, 1)
        vn = jnp.moveaxis(new.v[:, bidx, posc], 3, 1)
        if chunk == 1:
            # the decode-only step: one row a slot, written in place a
            # slot at a time. At one column XLA wraps the scatter below
            # in four transposed copies of the whole pool (compile, PR
            # 31: 27 M of the entry computation's 63 M estimated
            # cycles); unrolled, the same updates keep 1.5 GB more of
            # temporaries alive than the loop does
            def rows_in_place(pool, rows):
                rows = rows.astype(pool.dtype)  # (L, Hkv, K, 1, D)

                def one_slot(s, pool):
                    row = jax.lax.dynamic_slice_in_dim(rows, s, 1, axis=2)
                    return jax.lax.dynamic_update_slice(
                        pool, row, (0, 0, pg[s, 0], off[s, 0], 0))

                return jax.lax.fori_loop(0, slots, one_slot, pool)

            return rows_in_place(pool_k, kn), rows_in_place(pool_v, vn)
        return (pool_k.at[:, :, pg, off].set(kn.astype(pool_k.dtype)),
                pool_v.at[:, :, pg, off].set(vn.astype(pool_v.dtype)))

    @staticmethod
    def create(num_layers, batch, max_len, num_kv_heads, head_dim,
               dtype=jnp.bfloat16) -> "KVCache":
        shape = (num_layers, batch, max_len, num_kv_heads, head_dim)
        return KVCache(
            k=jnp.zeros(shape, dtype),
            v=jnp.zeros(shape, dtype),
            length=jnp.zeros((batch,), jnp.int32),
        )

    def layer(self, i):
        """(k, v) views for layer i (used as tp_attn_fwd's kv_cache)."""
        return self.k[i], self.v[i]

    def with_layer(self, i, kv) -> "KVCache":
        k_l, v_l = kv
        return self._replace(
            k=self.k.at[i].set(k_l), v=self.v.at[i].set(v_l)
        )

    def advanced(self, n: int) -> "KVCache":
        return self._replace(length=self.length + n)
