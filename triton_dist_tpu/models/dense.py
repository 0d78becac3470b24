"""DenseLLM — Qwen3-style TP transformer over the fused kernel library.

TPU-native re-design of the reference's DenseLLM/DenseLLMLayer
(ref: python/triton_dist/models/dense.py:53-241): the torch module tree
with a per-layer fwd mode switch (:84-98) becomes a functional model —
params are pytrees of per-rank shards (leading mesh-axis dim, consumed by
shard_map in_specs), the layer stack is a `lax.scan` over stacked layer
params (one trace for all layers), and the forward modes mirror the
reference's torch / triton_dist / triton_dist_AR:

  xla  — unfused collectives (parity reference)
  dist — ag_gemm/gemm_rs sequence-sharded pipeline (prefill)
  ar   — replicated activations + gemm_ar (decode / low latency)

Mode routing is OWNED by the fusion planner (triton_dist_tpu.plan):
`forward` resolves its `mode` argument to a Plan and executes through
plan/execute — this module contains no fused-vs-sequential branches.
mode="auto" lets the planner price the lowerings per shape.

Sharding layout per tensor (n = tp size):
  embed (V, H) replicated · norms (L, H) replicated
  w_qkv (L, n, H, (Hq+2Hkv)/n*D) · w_o (L, n, Hq/n*D, H)
  w_gate / w_up (L, n, H, I/n) · w_down (L, n, I/n, H)
  lm_head (n, H, V/n)
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from triton_dist_tpu.layers import (
    TPAttnParams,
    TPAttnSpec,
    TPMLPParams,
    rms_norm,
    rope_table,
)
from triton_dist_tpu.layers.parts import part
from triton_dist_tpu.models.config import ModelConfig
from triton_dist_tpu.models.kv_cache import KVCache
from triton_dist_tpu.plan import execute as plan_exec
from triton_dist_tpu.plan.planner import Plan, plan_dense_forward
from triton_dist_tpu.runtime.init import TP_AXIS


class DenseLayerParams(NamedTuple):
    input_ln: jax.Array
    post_attn_ln: jax.Array
    w_qkv: jax.Array
    w_o: jax.Array
    q_norm: jax.Array
    k_norm: jax.Array
    # dense: w_gate/w_up (L, n, H, I/n) SEPARATE (like the HF checkpoint's
    #   gate_proj/up_proj; the split layout is what lets XLA fuse the silu
    #   epilogue — see layers/tp_mlp.py), w_down (L, n, I/n, H).
    #   The megakernel fuses them once at init for one-DMA streaming.
    # MoE:   w_gate_up (L, n, E, H, 2I_moe/n), w_down (L, n, E, I_moe/n, H)
    #   stays fused (the grouped-GEMM expert layout).
    w_down: jax.Array
    w_gate: Optional[jax.Array] = None
    w_up: Optional[jax.Array] = None
    w_gate_up: Optional[jax.Array] = None  # MoE only
    w_router: Optional[jax.Array] = None  # MoE only: (L, H, E) replicated


class DenseLLMParams(NamedTuple):
    embed: jax.Array
    layers: DenseLayerParams  # stacked: leading (L, n, ...) dims
    final_ln: jax.Array
    lm_head: jax.Array


def param_specs(axis: str = TP_AXIS, moe: bool = False):
    """shard_map in_specs for DenseLLMParams (leading n dim -> axis)."""
    layers = DenseLayerParams(
        input_ln=P(), post_attn_ln=P(),
        w_qkv=P(None, axis), w_o=P(None, axis),
        q_norm=P(), k_norm=P(),
        w_down=P(None, axis),
        w_gate=None if moe else P(None, axis),
        w_up=None if moe else P(None, axis),
        w_gate_up=P(None, axis) if moe else None,
        w_router=P() if moe else None,
    )
    return DenseLLMParams(
        embed=P(), layers=layers, final_ln=P(), lm_head=P(axis)
    )


def cache_specs(axis: str = TP_AXIS, batch_axis: Optional[str] = None):
    """KV cache specs: heads shard over tp; batch optionally over dp."""
    return KVCache(
        k=P(None, batch_axis, None, axis),
        v=P(None, batch_axis, None, axis),
        length=P(batch_axis),
    )


def param_shapes(cfg: ModelConfig, n: int) -> DenseLLMParams:
    """GLOBAL shape of every DenseLLMParams leaf at tp size n (the
    module-doc layout; None for the leaves this config does not have).
    One definition for init_params' two RNG paths and for callers that
    need the tree as `jax.ShapeDtypeStruct`s (compile-only checks)."""
    assert cfg.num_q_heads % n == 0 and cfg.num_kv_heads % n == 0, (
        f"num_q_heads={cfg.num_q_heads} and num_kv_heads={cfg.num_kv_heads} "
        f"must both divide the tp size {n} (pick a smaller tp for this "
        "config, e.g. Qwen3-30B-A3B with 4 kv heads supports tp<=4)"
    )
    assert cfg.vocab_size % n == 0 and (
        (cfg.moe_intermediate_size if cfg.is_moe else cfg.intermediate_size)
        % n == 0
    ), "vocab/intermediate sizes must divide the tp size"
    h, d = cfg.hidden_size, cfg.head_dim
    hq_l, hkv_l = cfg.num_q_heads // n, cfg.num_kv_heads // n
    L = cfg.num_layers
    if cfg.is_moe:
        e = cfg.num_experts
        mi_l = cfg.moe_intermediate_size // n
        ffn = dict(
            w_gate_up=(L, n, e, h, 2 * mi_l),
            w_down=(L, n, e, mi_l, h),
            w_router=(L, h, e),
        )
    else:
        i_l = cfg.intermediate_size // n
        ffn = dict(
            w_gate=(L, n, h, i_l),
            w_up=(L, n, h, i_l),
            w_down=(L, n, i_l, h),
        )
    layers = DenseLayerParams(
        input_ln=(L, h), post_attn_ln=(L, h),
        w_qkv=(L, n, h, (hq_l + 2 * hkv_l) * d),
        w_o=(L, n, hq_l * d, h),
        q_norm=(L, d), k_norm=(L, d),
        **ffn,
    )
    return DenseLLMParams(
        embed=(cfg.vocab_size, h), layers=layers, final_ln=(h,),
        lm_head=(n, h, cfg.vocab_size // n),
    )


# norm gains (*_ln, *_norm) start at one; every other leaf is
# N(0, _INIT_SCALE)
_INIT_SCALE = 0.02
# elements one jax.random draw may produce at once (its f32 values and
# u32 bits are transient device memory: ~0.7 GB at 2**26)
_DRAW_ELEMS = 1 << 26


def _named_leaves(shapes: DenseLLMParams, specs: DenseLLMParams) -> dict:
    """{field name: (shape, spec, is_norm)} over the leaves this config
    has (field names are unique across the two NamedTuples)."""
    out = {}
    for shp, spc in ((shapes, specs), (shapes.layers, specs.layers)):
        for f in shp._fields:
            shape = getattr(shp, f)
            if type(shape) is tuple:  # not the nested tuple, not None
                out[f] = (shape, getattr(spc, f),
                          f.endswith(("_ln", "_norm")))
    return out


def _assemble(leaves: dict) -> DenseLLMParams:
    lay = {f: leaves.get(f) for f in DenseLayerParams._fields}
    top = {f: leaves[f] for f in ("embed", "final_ln", "lm_head")}
    return DenseLLMParams(layers=DenseLayerParams(**lay), **top)


def _draw(key, shape, dt):
    """N(0, _INIT_SCALE) of `shape` in dtype dt, drawn in slabs of at
    most _DRAW_ELEMS elements along the leading dims so the transient
    f32/bit buffers stay bounded whatever the tensor's size."""
    total = int(np.prod(shape))
    if total <= _DRAW_ELEMS or len(shape) == 1:
        return (jax.random.normal(key, shape, jnp.float32)
                * _INIT_SCALE).astype(dt)
    lead, rest = shape[0], shape[1:]
    # largest divisor of the leading dim whose slab fits the budget;
    # none (one leading slice is already too large): slab the rest
    c = max((c for c in range(1, lead + 1)
             if lead % c == 0 and c * (total // lead) <= _DRAW_ELEMS),
            default=0)
    if c:
        def slab(k):
            return _draw(k, (c,) + rest, dt)
    else:
        c = 1

        def slab(k):
            return _draw(k, rest, dt)
    return jax.lax.map(slab, jax.random.split(key, lead // c)).reshape(
        shape)


def _init_on_mesh(mesh, seed: int, axis: str, dt, named: dict,
                  specs: DenseLLMParams) -> DenseLLMParams:
    """fast=True init: one shard_map'd program in which every device
    draws exactly the shards it keeps (tp-sharded leaves under a
    rank-folded key, replicated leaves under the shared one). Nothing
    is ever built whole on one device: Qwen3-8B's w_gate alone is
    7.2 GB in f32."""

    def per_rank(key):
        rank = jax.lax.axis_index(axis)
        out = {}
        for i, (f, (shape, spec, is_norm)) in enumerate(named.items()):
            local = tuple(
                1 if j < len(spec) and spec[j] == axis else s
                for j, s in enumerate(shape))
            if is_norm:
                out[f] = jnp.ones(local, dt)
                continue
            k = jax.random.fold_in(key, i)
            if local != shape:
                k = jax.random.fold_in(k, rank)
            out[f] = _draw(k, local, dt)
        return _assemble(out)

    return jax.jit(jax.shard_map(
        per_rank, mesh=mesh, in_specs=P(), out_specs=specs,
        check_vma=False))(jax.random.PRNGKey(seed))


# host-stream draw order: part of what a seed means (FFN, attention,
# then embed and head; norms consume none of it)
_HOST_ORDER = ("w_gate_up", "w_gate", "w_up", "w_down", "w_router",
               "w_qkv", "w_o", "embed", "lm_head")


def init_params(
    cfg: ModelConfig, mesh, seed: int = 0, axis: str = TP_AXIS,
    fast: bool = False,
) -> DenseLLMParams:
    """Random-init global arrays laid out for shard_map (the reference
    streams HF weights at init, dense.py:150-167; random init keeps the
    framework dependency-free — `load_hf` maps real checkpoints).

    Both paths place every tensor straight into its NamedSharding — no
    tensor is first built whole on the default device.

    fast=False draws the GLOBAL tensors from one host numpy stream
    (identical values whatever the tp size — what parity tests want)
    and transfers each shard to its device. fast=True draws on the
    devices instead (_init_on_mesh: each shard where it lives, values
    depend on the tp size) — O(seconds) instead of O(minutes) at
    multi-billion-param scale, and the only path that fits a real model
    on the chips; use it whenever the exact host RNG stream doesn't
    matter (benchmarks, chip_smoke.py)."""
    n = int(mesh.shape[axis])
    specs = param_specs(axis, cfg.is_moe)
    named = _named_leaves(param_shapes(cfg, n), specs)
    dt = jnp.dtype(cfg.dtype)
    if fast:
        return _init_on_mesh(mesh, seed, axis, dt, named, specs)
    rng = np.random.default_rng(seed)

    def mk(f):
        # host array in the final dtype (f64 -> f32 -> dt, the rounding
        # jnp.asarray applies), then one transfer per shard
        shape, spec, is_norm = named[f]
        x = (np.ones(shape, np.float32) if is_norm else np.asarray(
            rng.standard_normal(shape) * _INIT_SCALE, np.float32))
        return jax.device_put(x.astype(dt), NamedSharding(mesh, spec))

    order = [f for f in _HOST_ORDER if f in named]
    order += [f for f in named if f not in order]
    return _assemble({f: mk(f) for f in order})


def _layer_fwd(cfg: ModelConfig, spec: TPAttnSpec, cos, sin, positions,
               kv_len, batch, axis, plan: Plan, x,
               lp: DenseLayerParams, kv):
    """One transformer block (ref DenseLLMLayer.fwd, dense.py:101-114).
    All mode/impl routing lives in the Plan (triton_dist_tpu.plan):
    this function only states the block structure. Returns (x, the
    step's (k, v) rows (B, S, Hkv, D))."""
    attn_params = TPAttnParams(
        w_qkv=lp.w_qkv, w_o=lp.w_o,
        q_norm=lp.q_norm if cfg.use_qk_norm else None,
        k_norm=lp.k_norm if cfg.use_qk_norm else None,
    )
    # the parts (layers/parts.py): the block's norm goes with the
    # projection it feeds, the residual with what it adds; the
    # attention layer names its own projections and core inside
    with part("attn.proj"):
        h = rms_norm(x, lp.input_ln, cfg.rms_eps)
    attn_out, rows = plan_exec.attn_fwd(
        plan, h, attn_params, spec, cos, sin, positions, batch,
        axis, kv, kv_len,
    )
    with part("attn.proj"):
        x = x + attn_out
    if cfg.is_moe:
        from triton_dist_tpu.layers import TPMoEParams

        ffn_params = TPMoEParams(lp.w_router, lp.w_gate_up, lp.w_down)
    else:
        ffn_params = TPMLPParams(lp.w_gate, lp.w_up, lp.w_down)
    with part("moe.experts" if cfg.is_moe else "ffn.dense"):
        h = rms_norm(x, lp.post_attn_ln, cfg.rms_eps)
        mlp_out = plan_exec.ffn_fwd(plan, h, ffn_params, axis,
                                    top_k=cfg.num_experts_per_tok)
        x = x + mlp_out
    return x, rows


# `forward_rows(head_cols=ALL_COLS)`: the head reads every column
ALL_COLS = "all"


def forward_rows(
    cfg: ModelConfig,
    params: DenseLLMParams,
    tokens: jax.Array,  # (B, S) int32, replicated
    cache: Optional[KVCache],  # per-rank head shards
    mode: str = "dist",
    axis: str = TP_AXIS,
    head_cols=None,
    attn_impl: Optional[str] = None,
    plan: Optional[Plan] = None,
):
    """Per-device forward (inside shard_map) over a cache it only
    READS — dense or paged, each layer through `cache.layer_view`.
    Returns (logits, (k, v)). `head_cols` says which rows the head
    reads: None, the last column of every row of the batch; a (B,)
    int32 array, one column a row (the serve step's `n_valid - 1`);
    `ALL_COLS`, every one. The one-column forms take their (B, H)
    hidden rows BEFORE the final norm and the vocabulary projection,
    so the norm, the head and the tp all-gather of the logits work on
    B rows and return (B, V) f32; `ALL_COLS` returns (B, S, V) (the
    per-position serve step, a teacher-forced reference). (k, v) are
    the step's new K/V rows
    (L, B, S, Hkv, D) for positions cache.length .. + S — what leaves
    the layer scan is the rows, never the (B, T, Hkv, D) views they
    were laid into: `forward` lays them into a KVCache, the serve step
    into its pages (KVCache.scatter_step). attn_impl: prefill
    attention implementation override ("xla" | "pallas"; None = auto —
    the flash-prefill switch, plan.route_prefill_impl).

    Routing is the fusion planner's (triton_dist_tpu.plan): a legacy
    `mode` string is honored bit-for-bit as a plan constraint,
    mode="auto" lets the planner price the lowerings, and a prebuilt
    `plan` (the same memoized object Engine / serve / mega hold)
    short-circuits planning entirely."""
    if cache is None:
        raise ValueError("forward requires a KVCache (create one per serve)")
    n = jax.lax.axis_size(axis)
    b, s = tokens.shape
    h_dim = cfg.hidden_size
    m = b * s
    if plan is None:
        # trace-time planning on static shapes: memoized, so this is a
        # dict lookup on every retrace of the same step geometry
        plan = plan_dense_forward(cfg, b, s, n, mode=mode,
                                  attn_impl=attn_impl)
    from triton_dist_tpu.trace import events as _tev

    _tev.note_plan(plan.plan_id)  # trace provenance (Timeline.plan_id)
    spec = TPAttnSpec(cfg.num_q_heads // n, cfg.num_kv_heads // n,
                      cfg.head_dim)
    cos, sin = rope_table(cfg.head_dim, cfg.max_positions, cfg.rope_theta)

    start = cache.length
    positions = start[:, None] + jnp.arange(s)[None, :]  # (B, S)
    kv_len = start + s

    with part("embed"):
        x = params.embed[tokens].reshape(m, h_dim)
        x = plan_exec.shard_tokens(x, axis, plan)

    def step(x, xs):
        i, lp = xs
        return _layer_fwd(cfg, spec, cos, sin, positions, kv_len, b,
                          axis, plan, x, lp, cache.layer_view(i))

    # strip the n-axis dim (shard_map gives size-1 shards on that dim)
    lp_local = jax.tree.map(
        lambda a, sp: a[:, 0] if sp == P(None, axis) else a,
        params.layers, param_specs(axis, cfg.is_moe).layers,
    )
    x, rows = jax.lax.scan(
        step, x, (jnp.arange(cfg.num_layers), lp_local))

    every_col = isinstance(head_cols, str)
    if every_col and head_cols != ALL_COLS:
        raise ValueError(f"head_cols {head_cols!r}: None, a (B,) int32 "
                         "array or ALL_COLS")
    with part("head"):
        x = plan_exec.gather_tokens(x, axis, plan)  # (M, H) when sharded
        if not every_col:
            col = s - 1 if head_cols is None else head_cols
            x = x[jnp.arange(b) * s + col]  # (B, H): the head's rows
        x = rms_norm(x, params.final_ln, cfg.rms_eps)
        if every_col:
            x = x.reshape(b, s, h_dim)
        head = params.lm_head[0]  # strip n dim
        # bf16 operands + f32 accumulation: avoids materialising an f32
        # copy of the (H, V/n) head shard (the MXU accumulates in f32
        # natively).
        logits = jnp.einsum(
            "...h,hv->...v", x, head, preferred_element_type=jnp.float32
        )
        # (B, V), or (B, S, V) for ALL_COLS
        logits = jax.lax.all_gather(logits, axis, axis=logits.ndim - 1,
                                    tiled=True)
    return logits, rows


def forward(cfg: ModelConfig, params: DenseLLMParams, tokens: jax.Array,
            cache: Optional[KVCache], **kw):
    """`forward_rows` (and its keywords) with the step's rows laid into
    the cache: returns (logits, new_cache), new_cache the (donated)
    cache with the rows at positions cache.length .. + S and its length
    advanced. Mirrors the reference inference entry (ref:
    models/dense.py:221-241 `inference`)."""
    logits, (k, v) = forward_rows(cfg, params, tokens, cache, **kw)
    s = tokens.shape[1]
    bidx = jnp.arange(tokens.shape[0])[:, None]
    positions = cache.length[:, None] + jnp.arange(s)[None, :]
    return logits, KVCache(k=cache.k.at[:, bidx, positions].set(k),
                           v=cache.v.at[:, bidx, positions].set(v),
                           length=cache.length + s)
