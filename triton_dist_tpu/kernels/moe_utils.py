"""MoE routing utilities — topk routing + expert-aligned token sort.

TPU-native re-design of the reference's MoE utils
(ref: python/triton_dist/kernels/nvidia/moe_utils.py:1-405 topk
reduce/histogram; csrc/lib/moe_utils.cu:61-165
`moe_ag_scatter_align_block_size`, the CUDA kernel building the sorted
token->block mapping). On TPU the alignment problem disappears:
`lax.ragged_dot` takes contiguous group sizes directly, so the "align to
GEMM block size" native op reduces to a stable argsort by expert id +
bincount — static shapes, no atomics, fully fused by XLA.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


def topk_routing(
    router_logits: jax.Array,  # (M, E) f32
    k: int,
    normalize: bool = True,
    score: str = "softmax",
    bias: Optional[jax.Array] = None,  # (E,)
    scale: float = 1.0,
) -> Tuple[jax.Array, jax.Array]:
    """Score-then-topk router. The default is softmax-then-topk
    (Qwen3MoE's norm_topk_prob convention, ref: models/qwen_moe.py:
    50-206); `score="sigmoid"` scores each expert on its own. A `bias`
    is added for the CHOICE alone: the weights are the scores of the
    chosen, divided by their sum under `normalize` and multiplied by
    `scale`. Returns (weights (M, k) f32, ids (M, k) int32)."""
    logits = router_logits.astype(jnp.float32)
    assert score in ("softmax", "sigmoid"), score
    probs = (jax.nn.softmax(logits, axis=-1) if score == "softmax"
             else jax.nn.sigmoid(logits))
    if bias is None:
        weights, ids = jax.lax.top_k(probs, k)
    else:
        _, ids = jax.lax.top_k(probs + bias.astype(jnp.float32), k)
        weights = jnp.take_along_axis(probs, ids, axis=-1)
    if normalize:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale
    return weights, ids.astype(jnp.int32)


def silu_mul(h: jax.Array) -> jax.Array:
    """silu(gate) * up over a fused (…, 2I) gate_up projection, in f32 —
    the FFN epilogue shared by the TP-MoE layer and the EP expert FFNs
    (sequential and chunk-pipelined paths must share ONE implementation:
    the overlap parity tests compare their outputs bitwise)."""
    gate, up = jnp.split(h.astype(jnp.float32), 2, axis=-1)
    return jax.nn.silu(gate) * up


def expert_histogram(topk_ids: jax.Array, n_experts: int) -> jax.Array:
    """Tokens per expert (the reference's triton bincount,
    ref: kernels/nvidia/ep_a2a.py:310-336)."""
    return jnp.bincount(topk_ids.reshape(-1), length=n_experts).astype(
        jnp.int32
    )


class ExpertSort(NamedTuple):
    """Sorted (token, choice) pairs grouped by expert — the align-block-
    size output analog (ref: csrc/lib/moe_utils.cu:61-165)."""

    sort_idx: jax.Array  # (M*k,) flat position -> original flat (tok*k+j)
    token_idx: jax.Array  # (M*k,) source token row per sorted position
    group_sizes: jax.Array  # (E,) tokens per expert, sorted-order segments
    unsort_idx: jax.Array  # (M*k,) original flat -> sorted position


def sort_by_expert(topk_ids: jax.Array, n_experts: int) -> ExpertSort:
    """Stable sort of the (M, k) routing table by expert id."""
    m, k = topk_ids.shape
    flat = topk_ids.reshape(-1)
    sort_idx = jnp.argsort(flat, stable=True).astype(jnp.int32)
    group_sizes = expert_histogram(topk_ids, n_experts)
    unsort_idx = jnp.argsort(sort_idx, stable=True).astype(jnp.int32)
    token_idx = (sort_idx // k).astype(jnp.int32)
    return ExpertSort(sort_idx, token_idx, group_sizes, unsort_idx)


class ExpertPack(NamedTuple):
    """Local tokens packed into fixed-capacity per-expert blocks — the
    static-shape MXU formulation of the reference's sorted ragged layout
    (ref: kernels/nvidia/allgather_group_gemm.py:85-199 sorted gather
    index). Capacity-padded blocks trade pad FLOPs for fully static
    tiles; overflow beyond `capacity` rows per expert is dropped (GShard
    trade, same as kernels/ep_a2a.py — `drops` counts them)."""

    x: jax.Array           # (E * cap, H) tokens grouped by expert
    slot_of: jax.Array     # (M, k) flat slot e*cap+p per choice, -1=drop
    counts: jax.Array      # (E,) tokens per expert (clamped to cap)
    drops: jax.Array       # () int32 overflow rows dropped


def pack_by_expert(
    x: jax.Array,          # (M, H)
    topk_ids: jax.Array,   # (M, k)
    n_experts: int,
    capacity: int,
) -> ExpertPack:
    """Gather-formulated fixed-capacity pack (one dense gather, no
    row-scatter — see kernels/ep_a2a.py `_pack_by_dest` for why scatter
    is serial on TPU). Slot (e, p) takes the p-th (token, choice) pair
    routed to expert e in stable token order; `slot_of` is the inverse
    map (also gather-built, via the double argsort), which lets the
    combine read expert outputs back with one dense gather."""
    m, k = topk_ids.shape
    c = capacity
    flat_ids = topk_ids.reshape(-1)
    order = jnp.argsort(flat_ids, stable=True)
    inv_order = jnp.argsort(order, stable=True)
    seg_count = jnp.bincount(flat_ids, length=n_experts)
    seg_start = jnp.cumsum(seg_count) - seg_count

    slot_e = (jnp.arange(n_experts * c) // c).astype(jnp.int32)
    slot_p = (jnp.arange(n_experts * c) % c).astype(jnp.int32)
    valid = slot_p < jnp.minimum(seg_count, c)[slot_e]
    entry = order[jnp.minimum(seg_start[slot_e] + slot_p, m * k - 1)]
    tok = jnp.where(valid, (entry // k).astype(jnp.int32), 0)
    xp = jnp.where(valid[:, None], x[tok], jnp.zeros((), x.dtype))

    # inverse map: choice f sits at within-expert position
    # inv_order[f] - seg_start[expert(f)]; beyond capacity -> dropped
    p_of = inv_order - seg_start[flat_ids]
    slot_of = jnp.where(
        p_of < c, flat_ids * c + p_of, -1
    ).astype(jnp.int32).reshape(m, k)
    drops = jnp.sum(jnp.maximum(seg_count - c, 0)).astype(jnp.int32)
    return ExpertPack(
        x=xp,
        slot_of=slot_of,
        counts=jnp.minimum(seg_count, c).astype(jnp.int32),
        drops=drops,
    )


def chunk_group_sizes(
    expert_counts: jax.Array,  # (n, E) valid rows per (segment, expert)
    capacity: int,
    lo: int,
    rows: int,
) -> jax.Array:
    """Expert-group sizes of one capacity chunk of an expert-sorted
    dispatch buffer — the per-chunk sort/segment metadata of the
    chunk-pipelined EP MoE (kernels/ep_a2a.py).

    Each received segment is expert-sorted with its invalid slots packed
    at the tail (ep_a2a._pack_by_dest expert_sorted=True), so segment
    j's group boundaries are the running sums of expert_counts[j]
    followed by `capacity` for the trailing null group. The chunk
    [lo, lo+rows) intersects each group as
    clip(b[e+1]) - clip(b[e]); returns (n, E+1) int32 summing to `rows`
    per segment (last column = null/invalid rows — callers mask them)."""
    n, e = expert_counts.shape
    bounds = jnp.concatenate(
        [
            jnp.zeros((n, 1), jnp.int32),
            jnp.cumsum(expert_counts.astype(jnp.int32), axis=1),
            jnp.full((n, 1), capacity, jnp.int32),
        ],
        axis=1,
    )  # (n, E+2): [0, cs_1..cs_E, capacity]
    clipped = jnp.clip(bounds, lo, lo + rows)
    return (clipped[:, 1:] - clipped[:, :-1]).astype(jnp.int32)


def combine_topk(
    y_sorted: jax.Array,  # (M*k, H) expert outputs in sorted order
    sort: ExpertSort,
    topk_weights: jax.Array,  # (M, k) f32
) -> jax.Array:
    """Unsort + weighted sum over the k choices -> (M, H) f32
    (the reference's topk-reduce, moe_reduce_rs.py:293-488)."""
    m, k = topk_weights.shape
    y_flat = y_sorted[sort.unsort_idx]  # (M*k, H) original order
    y_flat = y_flat.reshape(m, k, -1).astype(jnp.float32)
    return jnp.einsum("mkh,mk->mh", y_flat, topk_weights)
