"""Qwen3 megakernel model: the whole TP decode layer stack as one task
graph executed by a single persistent Pallas kernel per step.

TPU-native re-design of the reference's Qwen3 megakernel
(ref: python/triton_dist/mega_triton_kernel/models/qwen3.py and
models/layers/{tp_attn,tp_mlp}.py): the per-layer make_* calls build one
Graph; the scheduler orders it; compile_graph lowers it to one
pallas_call. The decode step is then: embed (XLA gather) -> megakernel ->
lm_head matmul + logits all-gather (XLA) -> KV scatter (XLA
dynamic-update fused into the same jit) — two XLA ops around one kernel,
the TPU shape of "one launch per decode step".

Weights reuse models.dense's DenseLLMParams layout verbatim, so a
DenseLLM/Engine checkpoint drops in (the ref megakernel also reuses the
HF weights of its eager model, test/models/test_qwen3.py).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from triton_dist_tpu.layers import rope_table
from triton_dist_tpu.mega.builder import ModelBuilder
from triton_dist_tpu.mega.kernel import CompiledMega, compile_graph
from triton_dist_tpu.mega.scheduler import schedule_graph, validate_schedule
from triton_dist_tpu.models.config import ModelConfig
from triton_dist_tpu.models.dense import (
    DenseLLMParams,
    init_params,
    param_specs,
)
from triton_dist_tpu.runtime.init import TP_AXIS


class MegaKVCache(NamedTuple):
    """Decode cache in megakernel layout (L, Hkv_loc, B, S_max, D): the
    per-head read `k[layer, h]` slices only leading dims, which is the
    Mosaic-friendly access (kernel.py module docstring)."""

    k: jax.Array
    v: jax.Array
    length: jax.Array  # (B,)

    @staticmethod
    def from_dense(cache, s_max: Optional[int] = None) -> "MegaKVCache":
        """Convert a models.kv_cache.KVCache (L, B, T, Hkv, D) — e.g. the
        output of an Engine prefill — into megakernel layout."""
        k = jnp.moveaxis(cache.k, 3, 1)  # (L, Hkv, B, T, D)
        v = jnp.moveaxis(cache.v, 3, 1)
        if s_max is not None and s_max != k.shape[3]:
            pad = s_max - k.shape[3]
            assert pad >= 0, "prefill longer than megakernel s_max"
            k = jnp.pad(k, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
        return MegaKVCache(k, v, cache.length)


class PagedMegaKVCache(NamedTuple):
    """Paged decode cache (ref: mega_triton_kernel/models/
    paged_kv_cache.py): k/v are SHARED page pools
    (L, Hkv_loc, n_pages, PAGE, D); `table` (B, MAXP) int32 maps
    (sequence, page index) -> pool page, allocated on demand (bump
    allocator `next_free`) as sequences grow — ragged batches consume
    pool pages proportional to their ACTUAL lengths, not B * S_max."""

    k: jax.Array
    v: jax.Array
    table: jax.Array      # (B, MAXP) int32; 0 until allocated
    length: jax.Array     # (B,)
    next_free: jax.Array  # () int32 bump-allocator head

    @staticmethod
    def create(cfg: ModelConfig, batch: int, hkv_loc: int, page: int,
               max_pages: int, total_pages: int) -> "PagedMegaKVCache":
        dt = jnp.dtype(cfg.dtype)
        shape = (cfg.num_layers, hkv_loc, total_pages, page, cfg.head_dim)
        return PagedMegaKVCache(
            jnp.zeros(shape, dt), jnp.zeros(shape, dt),
            jnp.zeros((batch, max_pages), jnp.int32),
            jnp.zeros((batch,), jnp.int32),
            jnp.zeros((), jnp.int32),
        )

    @staticmethod
    def from_dense(cache, page: int, total_pages: int,
                   max_pages: int) -> "PagedMegaKVCache":
        """Page an Engine prefill cache (L, B, T, Hkv, D): each
        sequence's VALID prefix (cache.length, not the cache's full
        allocated T — Engine allocates at max_len) claims
        ceil(len/page) consecutive pool pages, so
        next_free == sum_b ceil(len_b / page) and ragged batches share
        the pool. Runs outside jit: lengths are concrete, and the page
        walk is a host-built gather over the cache's page grid."""
        L, B, T, Hkv, D = cache.k.shape
        assert T % page == 0, f"cache len {T} % page {page}"
        lengths = np.asarray(cache.length)
        pages_per = -(-lengths // page)  # ceil
        used = int(pages_per.sum())
        assert used <= total_pages, "pool too small for the prefill"
        assert int(pages_per.max(initial=0)) <= max_pages, (
            "prefill longer than the table's max_pages"
        )
        # (seq, page-in-seq) of each claimed pool page, in claim order
        src_b = np.repeat(np.arange(B), pages_per)
        src_p = np.concatenate(
            [np.arange(p) for p in pages_per]
        ).astype(np.int64) if used else np.zeros((0,), np.int64)
        grid = jnp.moveaxis(cache.k, 3, 1).reshape(
            L, Hkv, B, T // page, page, D)
        gridv = jnp.moveaxis(cache.v, 3, 1).reshape(
            L, Hkv, B, T // page, page, D)
        k = grid[:, :, src_b, src_p]          # (L, Hkv, used, page, D)
        v = gridv[:, :, src_b, src_p]
        pad = total_pages - used
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        table = np.zeros((B, max_pages), np.int32)
        off = 0
        for b in range(B):
            table[b, :pages_per[b]] = np.arange(off, off + pages_per[b])
            off += int(pages_per[b])
        return PagedMegaKVCache(k, v, jnp.asarray(table), cache.length,
                                jnp.asarray(used, jnp.int32))


def _dense_only(cfg: ModelConfig) -> None:
    if cfg.is_hybrid:
        from triton_dist_tpu.models.hybrid import slot_state

        raise NotImplementedError(
            "the megakernel's task graph is the dense decoder's: a "
            f"configuration whose slots carry {slot_state(cfg)} has no "
            "delta-rule, convolution, window or held-expert task, and no "
            "state buffer beside the KV pages")


def build_qwen3_graph(
    cfg: ModelConfig, batch: int, world: int, s_max: int,
    axis: str = TP_AXIS, page: int = 0,
) -> Tuple[ModelBuilder, dict]:
    """The decode-step task graph (ref: Qwen3 model build over
    model_builder.make_* calls, mega_triton_kernel/models/qwen3.py).

    Norms-array row layout (stacked into one (4L+1, NW) input):
      [0,L) input_ln · [L,2L) post_attn_ln · [2L] final_ln ·
      [2L+1,3L+1) q_norm · [3L+1,4L+1) k_norm
    """
    _dense_only(cfg)
    n = world
    L = cfg.num_layers
    H = cfg.hidden_size
    D = cfg.head_dim
    hq_l = cfg.num_q_heads // n
    hkv_l = cfg.num_kv_heads // n
    i_l = cfg.intermediate_size // n
    wqkv = (hq_l + 2 * hkv_l) * D

    mb = ModelBuilder(batch, axis, world=n)
    x = mb.buffer(H, "x", pinned=True)
    mb.make_barrier()
    kn_bufs, vn_bufs = [], []
    for l in range(L):
        qkv = mb.make_rms_matmul("w_qkv", l, x, H, wqkv, norm_row=l,
                                 eps=cfg.rms_eps, tag=f"ln1+qkv[{l}]")
        attn, kn, vn = mb.make_attention(
            l, qkv, hq_l, hkv_l, D, s_max, cfg.rms_eps, cfg.use_qk_norm,
            q_norm_base=2 * L + 1, k_norm_base=3 * L + 1, page=page,
        )
        kn_bufs.append(kn)
        vn_bufs.append(vn)
        o = mb.make_matmul("w_o", l, attn, hq_l * D, H, tag=f"o[{l}]")
        x = mb.make_allreduce_add(o, x, H, tag=f"ar_attn[{l}]")
        gu = mb.make_rms_matmul("w_gate_up", l, x, H, 2 * i_l,
                                norm_row=L + l, eps=cfg.rms_eps,
                                tag=f"ln2+gate_up[{l}]")
        dn = mb.make_act_matmul("w_down", l, gu, i_l, H,
                                tag=f"silu+down[{l}]")
        x = mb.make_allreduce_add(dn, x, H, tag=f"ar_mlp[{l}]")
    final = mb.make_rms_norm(2 * L, x, H, cfg.rms_eps, tag="final_ln")
    mb.graph.pinned[final.id] = True
    meta = dict(
        input_buf=0, final=final, kn_bufs=kn_bufs, vn_bufs=vn_bufs,
        hq_l=hq_l, hkv_l=hkv_l, i_l=i_l, wqkv=wqkv,
    )
    return mb, meta


class MegaQwen3:
    """Engine-compatible decode over the megakernel (ref: ModelBuilder
    compile/run + model_server loop, mega_triton_kernel/test/models/).

    decode_step matches models.engine.Engine.decode_step's contract:
    tokens (B,) -> (logits (B, V) f32, cache). Prefill runs through the
    regular Engine (the megakernel covers decode, like the reference);
    `from_engine`/MegaKVCache.from_dense bridge the cache layouts.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        mesh,
        batch: int,
        axis: str = TP_AXIS,
        s_max: Optional[int] = None,
        params: Optional[DenseLLMParams] = None,
        seed: int = 0,
        fast_init: bool = False,
        donate_cache: bool = True,
        num_cores: int = 1,
        straggler: tuple = (-1, 0),
        paged: bool = False,
        page_size: Optional[int] = None,
        total_pages: Optional[int] = None,
    ):
        _dense_only(cfg)
        assert not cfg.is_moe, "megakernel covers the dense decode graph"
        from triton_dist_tpu.lang.core import use_interpret

        if not use_interpret() and cfg.head_dim % 128 != 0:
            # the attention branch reshapes (B, H*D) -> (B, H, D): native
            # Mosaic only supports this when the minor dim is lane-width
            raise ValueError(
                f"megakernel on native TPU requires head_dim % 128 == 0 "
                f"(got {cfg.head_dim}); sub-lane head dims run in "
                "interpret mode only"
            )
        n_ = int(mesh.shape[axis])
        assert cfg.num_q_heads % n_ == 0 and cfg.num_kv_heads % n_ == 0, (
            f"head counts ({cfg.num_q_heads}q/{cfg.num_kv_heads}kv) must "
            f"be divisible by the tp size {n_}"
        )
        self.cfg = cfg
        self.mesh = mesh
        self.axis = axis
        self.batch = batch
        self.s_max = s_max or cfg.max_positions
        n = int(mesh.shape[axis])
        self.world = n
        self.hkv_loc = cfg.num_kv_heads // n
        self.params = (
            params if params is not None
            else init_params(cfg, mesh, seed, axis, fast=fast_init)
        )
        dt = jnp.dtype(cfg.dtype)
        self.dtype = dt

        from triton_dist_tpu.mega.kernel import _kv_chunk

        self.paged = paged
        self.page = page_size or _kv_chunk(self.s_max)
        assert self.s_max % self.page == 0
        self.max_pages = self.s_max // self.page
        # shared pool size: B*max_pages reproduces dense; smaller pools
        # SHARE capacity across ragged sequences (allocation is on
        # demand — the point of paging)
        self.total_pages = (total_pages if total_pages is not None
                            else batch * self.max_pages)

        mb, meta = build_qwen3_graph(
            cfg, batch, n, self.s_max, axis,
            page=(page_size or 0) if not paged else self.page,
        )
        self.graph = mb.graph
        sched = schedule_graph(self.graph, num_cores=num_cores)
        validate_schedule(self.graph, sched)
        self.sched = sched
        # construct the model inside trace.building() for a traced
        # megakernel: decode_step then returns (logits, cache, trace_buf)
        from triton_dist_tpu.trace.events import active_build

        self._trace_build = active_build()
        self.cm: CompiledMega = compile_graph(
            self.graph, sched, dt, name=f"mega_qwen3_{axis}{n}",
            straggler=straggler, tiled_weights=("w_gate_up",),
        )
        self._meta = meta

        # fuse gate|up ONCE at init for one-DMA weight streaming in the
        # kernel (params store them split so XLA can fuse the silu
        # epilogue in the eager paths; see models/dense.py) — and lay
        # the fused copy out TILE-MAJOR (L, n, nt, H, TN): this weight
        # is >half the 32B shard's streamed bytes and the copy is being
        # materialized anyway, so re-blocking it is free HBM-wise and
        # turns its per-tile DMA from N-strided TN*2-byte bursts into
        # one fully contiguous K*TN*2-byte block (the round-5 ledger's
        # biggest single burst-efficiency lever; kernel.
        # tile_weight_major). The split copies are then stripped from
        # the pytree this model's jit consumes — the kernel never reads
        # them, and for a standalone MegaQwen3 (no Engine sharing the
        # params) stripping frees their HBM.
        from triton_dist_tpu.mega.kernel import tile_weight_major

        # One layer at a time (lax.map): fusing the whole stack in one
        # op costs 1.5x the copy in transient HBM on top of it (0.3 GB
        # per Qwen3-8B layer), which no real depth survives beside the
        # split originals; per layer the transient is two layers' worth
        # whatever the depth.
        gu_tn = self.cm.tile_cols("w_gate_up")
        self._w_gate_up = jax.jit(
            lambda g, u: jax.lax.map(
                lambda gu: tile_weight_major(
                    jnp.concatenate(gu, axis=-1), gu_tn), (g, u)),
            out_shardings=NamedSharding(mesh, P(None, axis)),
        )(self.params.layers.w_gate, self.params.layers.w_up)
        self.params = self.params._replace(
            layers=self.params.layers._replace(w_gate=None, w_up=None)
        )

        L = cfg.num_layers
        NW = self.cm.norm_width
        cos, sin = rope_table(cfg.head_dim, cfg.max_positions,
                              cfg.rope_theta)
        rope_cs = jnp.concatenate([cos, sin], axis=-1)  # (P, D) f32
        # 8-row stripes (see kernel.py norm/rope loads)
        self._rope_cs = jnp.repeat(rope_cs, 8, axis=0)
        self._norms = self._stack_norms(self.params)  # params-only: once

        slot = sched.buf_slot
        pb = self.cm.pb
        self._x_rows = int(slot[0]) * pb  # buffer 0 is the residual input
        self._final_rows = int(slot[meta["final"].id]) * pb
        self._kn_rows = np.array([int(slot[b.id]) * pb
                                  for b in meta["kn_bufs"]])
        self._vn_rows = np.array([int(slot[b.id]) * pb
                                  for b in meta["vn_bufs"]])

        from triton_dist_tpu.mega.kernel import _kv_chunk as _kc

        self._schunk = _kc(self.s_max, (page_size or 0) if not paged
                           else self.page)
        nch_d = self.s_max // self._schunk
        import numpy as _np

        self._ident_table = jnp.asarray(
            _np.arange(batch * nch_d, dtype=_np.int32).reshape(batch,
                                                               nch_d))

        p_specs = param_specs(axis, moe=False)
        p_specs = p_specs._replace(
            layers=p_specs.layers._replace(w_gate=None, w_up=None)
        )
        if paged:
            c_specs = PagedMegaKVCache(
                k=P(None, axis), v=P(None, axis), table=P(), length=P(),
                next_free=P(),
            )
        else:
            c_specs = MegaKVCache(k=P(None, axis), v=P(None, axis),
                                  length=P())

        def step(params: DenseLLMParams, w_gate_up, tokens,
                 cache: MegaKVCache):
            return self._device_step(params, w_gate_up, tokens, cache)

        out_specs = (P(), c_specs)
        if self._trace_build is not None:
            out_specs += (P(axis),)  # per-rank trace buffers, stacked
        self._decode = jax.jit(
            jax.shard_map(
                step, mesh=mesh,
                in_specs=(p_specs, P(None, axis), P(), c_specs),
                out_specs=out_specs,
                check_vma=False,
            ),
            donate_argnums=(3,) if donate_cache else (),
        )
        # resident multi-step decode executables, keyed on step count
        # (decode_resident; same specs as the one-step dispatch),
        # LRU-bounded like Engine._serve_cache — a window-size sweep
        # must not retain one executable per steps value forever
        self._decode_specs = (p_specs, P(None, axis), P(), c_specs)
        self._resident_fns: dict = {}
        self._resident_fns_max = 8
        self._donate = donate_cache

    # -- per-device step (inside shard_map) ---------------------------------

    def _stack_norms(self, params: DenseLLMParams):
        """Stacked norms (4L+1, NW) in f32 8-row stripes (packed bf16
        rows cannot be rank-reduced-sliced by a dynamic index on Mosaic;
        see kernel.py). Depends only on params — computed once at init and
        closed over by the jit (like the rope table)."""
        NW = self.cm.norm_width
        lp = params.layers

        def pad_to(v, w):
            return jnp.pad(v.astype(jnp.float32),
                           ((0, 0), (0, w - v.shape[-1])))

        norms = jnp.concatenate([
            pad_to(lp.input_ln, NW),
            pad_to(lp.post_attn_ln, NW),
            pad_to(params.final_ln[None, :], NW),
            pad_to(lp.q_norm, NW),
            pad_to(lp.k_norm, NW),
        ], axis=0)
        return jnp.repeat(norms, 8, axis=0)

    def _device_step(self, params: DenseLLMParams, w_gate_up, tokens,
                     cache):
        cfg = self.cfg
        L = cfg.num_layers
        H = cfg.hidden_size
        B = self.batch
        pb = self.cm.pb
        lp = params.layers
        dt = self.dtype
        norms = self._norms

        weights = {
            "w_qkv": lp.w_qkv[:, 0],
            "w_o": lp.w_o[:, 0],
            "w_gate_up": w_gate_up[:, 0],
            "w_down": lp.w_down[:, 0],
        }

        x = params.embed[tokens].astype(dt)  # (B, H)
        ws = self.cm.workspace(dt)
        ws = jax.lax.dynamic_update_slice(ws, x, (self._x_rows, 0))
        pos = cache.length

        if isinstance(cache, PagedMegaKVCache):
            k_pool, v_pool, table = cache.k, cache.v, cache.table
        else:
            # dense cache = identity page table over its own page grid
            # (free reshape; one kernel path serves both cache forms)
            Lh, Hh = cache.k.shape[0], cache.k.shape[1]
            nch = self.s_max // self._schunk
            k_pool = cache.k.reshape(Lh, Hh, B * nch, self._schunk,
                                     cfg.head_dim)
            v_pool = cache.v.reshape(Lh, Hh, B * nch, self._schunk,
                                     cfg.head_dim)
            table = self._ident_table

        res = self.cm.run(pos, table, ws, weights, norms,
                          self._rope_cs, k_pool, v_pool)
        if self._trace_build is not None:
            ws_o, trace_buf = res
        else:
            ws_o, trace_buf = res, None

        hidden = jax.lax.dynamic_slice(
            ws_o, (self._final_rows, 0), (pb, self.cm.wmax)
        )[:B, :H]
        head = params.lm_head[0]  # (H, V_loc)
        logits = jnp.dot(hidden, head, preferred_element_type=jnp.float32)
        logits = jax.lax.all_gather(logits, self.axis, axis=1, tiled=True)

        # KV scatter: gather the per-layer k/v rows out of the workspace
        # and write them at each sequence's position (the ref's paged KV
        # append, models/paged_kv_cache.py, as one fused XLA scatter).
        kw = self.hkv_loc * cfg.head_dim
        row_idx = (jnp.asarray(self._kn_rows)[:, None]
                   + jnp.arange(B)[None, :])  # (L, B)
        kn = ws_o[row_idx][..., :kw].reshape(L, B, self.hkv_loc,
                                             cfg.head_dim)
        row_idx_v = (jnp.asarray(self._vn_rows)[:, None]
                     + jnp.arange(B)[None, :])
        vn = ws_o[row_idx_v][..., :kw].reshape(L, B, self.hkv_loc,
                                               cfg.head_dim)
        kn = jnp.moveaxis(kn, 2, 1)  # (L, Hkv, B, D)
        vn = jnp.moveaxis(vn, 2, 1)
        bidx = jnp.arange(B)

        def ret(logits, new_cache):
            if trace_buf is not None:
                return logits, new_cache, trace_buf
            return logits, new_cache

        if isinstance(cache, PagedMegaKVCache):
            # page allocation (bump allocator): a sequence crossing into
            # a fresh page claims the next pool page(s) this step
            pidx = cache.length // self.page
            need = (cache.length % self.page) == 0
            new_ids = (cache.next_free
                       + jnp.cumsum(need.astype(jnp.int32)) - need)
            table = cache.table.at[bidx, pidx].set(
                jnp.where(need, new_ids.astype(jnp.int32),
                          cache.table[bidx, pidx]))
            next_free = cache.next_free + jnp.sum(need.astype(jnp.int32))
            slots = table[bidx, pidx]
            offs = cache.length % self.page
            k = cache.k.at[:, :, slots, offs].set(kn.astype(dt))
            v = cache.v.at[:, :, slots, offs].set(vn.astype(dt))
            return ret(logits, PagedMegaKVCache(k, v, table,
                                                cache.length + 1,
                                                next_free))
        k = cache.k.at[:, :, bidx, cache.length].set(kn.astype(dt))
        v = cache.v.at[:, :, bidx, cache.length].set(vn.astype(dt))
        return ret(logits, MegaKVCache(k, v, cache.length + 1))

    # -- public API ----------------------------------------------------------

    def new_paged_cache(self) -> PagedMegaKVCache:
        assert self.paged, "construct MegaQwen3 with paged=True"
        cache = PagedMegaKVCache.create(
            self.cfg, self.batch, self.hkv_loc, self.page,
            self.max_pages, self.total_pages,
        )
        specs = PagedMegaKVCache(k=P(None, self.axis),
                                 v=P(None, self.axis), table=P(),
                                 length=P(), next_free=P())
        return jax.tree.map(
            lambda a, sp: jax.device_put(a, NamedSharding(self.mesh, sp)),
            cache, specs,
        )

    def paged_cache_from_dense(self, cache) -> PagedMegaKVCache:
        assert self.paged, "construct MegaQwen3 with paged=True"
        pc = PagedMegaKVCache.from_dense(cache, self.page,
                                         self.total_pages,
                                         self.max_pages)
        specs = PagedMegaKVCache(k=P(None, self.axis),
                                 v=P(None, self.axis), table=P(),
                                 length=P(), next_free=P())
        return jax.tree.map(
            lambda a, sp: jax.device_put(a, NamedSharding(self.mesh, sp)),
            pc, specs,
        )

    def new_cache(self) -> MegaKVCache:
        shape = (self.cfg.num_layers, self.hkv_loc * self.world,
                 self.batch, self.s_max, self.cfg.head_dim)
        kv = NamedSharding(self.mesh, P(None, self.axis))
        # zeros created IN the sharding: never whole on one device
        return MegaKVCache(
            jnp.zeros(shape, self.dtype, device=kv),
            jnp.zeros(shape, self.dtype, device=kv),
            jnp.zeros((self.batch,), jnp.int32,
                      device=NamedSharding(self.mesh, P())))

    def decode_step(self, tokens, cache: MegaKVCache):
        """tokens (B,) -> (logits (B, V) f32, cache)."""
        return self._decode(
            self.params, self._w_gate_up, jnp.asarray(tokens, jnp.int32),
            cache
        )

    def decode_resident(self, tokens, cache, steps: int):
        """Device-RESIDENT decode: `steps` megakernel decode iterations
        — kernel step, greedy sampling, KV append, token feedback —
        inside ONE compiled dispatch (ISSUE 12: the persistent-loop
        form of the reference's model-server decode; the host re-enters
        once per WINDOW instead of once per token, which is exactly the
        per-step dispatch tax the r05 engine-vs-mega gap prices).
        Works over both cache forms; with a PagedMegaKVCache the loop
        iterates directly over the shared page pool — a serve-plane
        `KVPool.as_mega_cache()` export (the pool's pages copied into
        this module's page order) decodes in place.

        tokens (B,) -> (generated ids (B, steps), cache). Greedy only
        (argmax — the self-feeding loop's fixed point); bitwise equal
        to `steps` repeated decode_step/argmax calls, test-pinned
        (tests/test_mega_model.py)."""
        assert steps >= 1
        assert self._trace_build is None, (
            "decode_resident does not thread per-step trace buffers; "
            "build the model outside trace.building()"
        )
        fn = self._resident_fns.pop(steps, None)
        if fn is None:
            fn = self._build_decode_resident(steps)
            while len(self._resident_fns) >= self._resident_fns_max:
                self._resident_fns.pop(next(iter(self._resident_fns)))
        self._resident_fns[steps] = fn  # re-insert = LRU touch
        return fn(self.params, self._w_gate_up,
                  jnp.asarray(tokens, jnp.int32), cache)

    def _build_decode_resident(self, steps: int):
        p_specs, gu_spec, t_spec, c_specs = self._decode_specs

        def per_rank(params, w_gate_up, tok, cache):
            b = tok.shape[0]

            def body(i, carry):
                tok, cache, out = carry
                logits, cache = self._device_step(params, w_gate_up,
                                                  tok, cache)
                nxt = jnp.argmax(logits, -1).astype(jnp.int32)
                return nxt, cache, out.at[:, i].set(nxt)

            out0 = jnp.zeros((b, steps), jnp.int32)
            _tok, cache, out = jax.lax.fori_loop(
                0, steps, body, (tok, cache, out0))
            return out, cache

        return jax.jit(
            jax.shard_map(
                per_rank, mesh=self.mesh,
                in_specs=(p_specs, gu_spec, t_spec, c_specs),
                out_specs=(t_spec, c_specs),
                check_vma=False,
            ),
            donate_argnums=(3,) if self._donate else (),
        )
