"""TP attention layer — column-parallel QKV, row-parallel O, GQA + RoPE.

TPU-native re-design of the reference's TP_Attn
(ref: python/triton_dist/layers/nvidia/tp_attn.py:79-330): torch_fwd :180,
dist_triton_fwd :215 (ag_gemm QKV -> rope + flash attn -> gemm_rs O),
AR modes :254-330. Heads shard over the tp axis (Hq/n query heads and
Hkv/n kv heads per rank); the sequence-sharded residual stream is gathered
by the fused AG+GEMM exactly as in the reference.

Qwen3 specifics carried here: per-head q/k RMSNorm ("qk norm") before rope
(Qwen3 applies it over head_dim), rope_theta 1e6.

Per-rank weight layout:
  w_qkv (hidden, (Hq + 2*Hkv)/n * D)  — q then k then v column blocks
  w_o   (Hq/n * D, hidden)
  q_norm, k_norm (D,) — per-head rmsnorm weights (optional, Qwen3)
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from triton_dist_tpu.kernels import (
    AgGemmConfig,
    GemmRsConfig,
    ag_gemm,
    gemm_ar,
    gemm_rs,
)
from triton_dist_tpu.layers.attention import gqa_attention
from triton_dist_tpu.layers.norm import rms_norm
from triton_dist_tpu.layers.parts import part
from triton_dist_tpu.layers.rope import apply_rope
from triton_dist_tpu.runtime.init import TP_AXIS


class TPAttnParams(NamedTuple):
    w_qkv: jax.Array
    w_o: jax.Array
    q_norm: Optional[jax.Array] = None
    k_norm: Optional[jax.Array] = None


class TPAttnSpec(NamedTuple):
    """Static per-rank head geometry."""

    num_q_heads: int  # per rank
    num_kv_heads: int  # per rank
    head_dim: int


def _split_qkv(h, spec: TPAttnSpec, batch: int):
    """(M, (Hq+2Hkv)*D) -> q (B, S, Hq, D), k/v (B, S, Hkv, D)."""
    m = h.shape[0]
    s = m // batch
    hq, hkv, d = spec.num_q_heads, spec.num_kv_heads, spec.head_dim
    q, k, v = jnp.split(h, [hq * d, (hq + hkv) * d], axis=-1)
    return (
        q.reshape(batch, s, hq, d),
        k.reshape(batch, s, hkv, d),
        v.reshape(batch, s, hkv, d),
    )


def _qk_norm_rope(q, k, params: TPAttnParams, cos, sin, positions):
    if params.q_norm is not None:
        q = rms_norm(q, params.q_norm)
    if params.k_norm is not None:
        k = rms_norm(k, params.k_norm)
    q = apply_rope(q, cos, sin, positions)
    k = apply_rope(k, cos, sin, positions)
    return q, k


@part("attn.core")
def _attn_core(qkv, params, spec, batch, cos, sin, positions, kv_cache,
               kv_len, attn_impl=None, attn_block=None):
    """Shared middle: split + qknorm + rope + (cached) attention.

    attn_impl: forwarded to gqa_attention's prefill_impl — the serve
    prefill-chunk / blockwise-prefill switch ("xla" | "pallas" | None =
    auto; kernels/flash_prefill.py). attn_block: forwarded to
    gqa_attention's prefill_block — the planner's tune-cache KV page
    height (None keeps the default block, i.e. the legacy program).
    Returns (attn_out (M, Hq*D), (k, v)): the step's NEW ROWS
    (B, S, Hkv, D), rope applied — with a cache, in its dtype, the rows
    this call laid into its own copy of the layer's view before
    attending; the caller owns where they are kept (models/dense.py
    `forward`, KVCache.scatter_step)."""
    q, k, v = _split_qkv(qkv, spec, batch)
    q, k = _qk_norm_rope(q, k, params, cos, sin, positions)
    if kv_cache is None:
        out = gqa_attention(q, k, v, causal=True,
                            prefill_impl=attn_impl,
                            prefill_block=attn_block)
    else:
        assert kv_len is not None, (
            "kv_cache without kv_len would attend over the uninitialized "
            "cache tail"
        )
        k_cache, v_cache = kv_cache
        # the rows have two readers, this layer's view and the caller;
        # behind the barrier they are ONE materialised value, and what
        # XLA fuses into the norm and rope that produce them does not
        # depend on who else reads them (without it the tp=4 wide step
        # re-fuses the k norm's sum: logits 0.2 from the same program
        # without the second reader; PERF.md, PR 33)
        k, v = jax.lax.optimization_barrier(
            (k.astype(k_cache.dtype), v.astype(v_cache.dtype)))
        # Write this step's K/V into the cache at `positions`, then attend
        # causally by absolute position — one code path for 1-token decode
        # and multi-token prefill-into-cache.
        out = gqa_attention(
            q, _scatter_kv(k_cache, k, positions),
            _scatter_kv(v_cache, v, positions), causal=True,
            q_positions=positions, kv_len=kv_len, prefill_impl=attn_impl,
            prefill_block=attn_block,
        )
    m = out.shape[0] * out.shape[1]
    return out.reshape(m, spec.num_q_heads * spec.head_dim), (k, v)


def _scatter_kv(cache, kv, positions):
    """cache (B, T, H, D) <- kv (B, S, H, D) at positions (B, S)."""
    bidx = jnp.arange(cache.shape[0])[:, None]
    return cache.at[bidx, positions].set(kv.astype(cache.dtype))


def tp_attn_xla_fwd(x_shard, params: TPAttnParams, spec: TPAttnSpec,
                    cos, sin, positions, batch: int, axis: str = TP_AXIS,
                    kv_cache=None, kv_len=None, attn_impl=None,
                    attn_block=None):
    """Unfused parity path (ref torch_fwd, tp_attn.py:180)."""
    with part("attn.proj"):
        x_full = jax.lax.all_gather(x_shard, axis, tiled=True)
        qkv = jnp.dot(x_full, params.w_qkv,
                      preferred_element_type=jnp.float32).astype(
                          x_shard.dtype)
    out, rows = _attn_core(qkv, params, spec, batch, cos, sin,
                                positions, kv_cache, kv_len, attn_impl,
                                attn_block)
    with part("attn.proj"):
        partial = jnp.dot(out, params.w_o,
                          preferred_element_type=jnp.float32)
        y = jax.lax.psum_scatter(
            partial.astype(x_shard.dtype), axis, tiled=True
        )
    return y, rows


def tp_attn_dist_fwd(x_shard, params: TPAttnParams, spec: TPAttnSpec,
                     cos, sin, positions, batch: int, axis: str = TP_AXIS,
                     kv_cache=None, kv_len=None, attn_impl=None,
                     attn_block=None,
                     ag_config: Optional[AgGemmConfig] = None,
                     rs_config: Optional[GemmRsConfig] = None):
    """Fused path (ref dist_triton_fwd, tp_attn.py:215): overlapped
    AG+GEMM QKV projection, attention, overlapped GEMM+RS O projection.
    x_shard: (M/n, hidden) -> ((M/n, hidden), the step's (k, v) rows)."""
    from triton_dist_tpu.trace.events import primary

    # primary(): build-safe under trace.building() (buffers dropped; see
    # tp_mlp.dist_fwd)
    with part("attn.proj"):
        qkv = primary(ag_gemm(x_shard, params.w_qkv, axis=axis,
                              config=ag_config))
    out, rows = _attn_core(qkv, params, spec, batch, cos, sin,
                                positions, kv_cache, kv_len, attn_impl,
                                attn_block)
    with part("attn.proj"):
        y = primary(gemm_rs(out, params.w_o, axis=axis, config=rs_config))
    return y, rows


def tp_attn_ar_fwd(x_full, params: TPAttnParams, spec: TPAttnSpec,
                   cos, sin, positions, batch: int, axis: str = TP_AXIS,
                   kv_cache=None, kv_len=None, attn_impl=None,
                   attn_block=None,
                   rs_config: Optional[GemmRsConfig] = None):
    """Replicated-activation path (ref AR fwd modes, tp_attn.py:254-330):
    local QKV gemm, attention, fused gemm+allreduce O projection."""
    with part("attn.proj"):
        qkv = jnp.dot(x_full, params.w_qkv,
                      preferred_element_type=jnp.float32).astype(
                          x_full.dtype)
    out, rows = _attn_core(qkv, params, spec, batch, cos, sin,
                                positions, kv_cache, kv_len, attn_impl,
                                attn_block)
    with part("attn.proj"):
        y = gemm_ar(out, params.w_o, axis=axis, config=rs_config)
    return y, rows


MODES = {
    "xla": tp_attn_xla_fwd,
    "dist": tp_attn_dist_fwd,
    "ar": tp_attn_ar_fwd,
}


def tp_attn_fwd(x, params, spec, cos, sin, positions, batch,
                axis: str = TP_AXIS, mode: str = "dist", **kw):
    """Mode-switched forward (ref: models/dense.py:84-98 set_fwd)."""
    return MODES[mode](x, params, spec, cos, sin, positions, batch,
                       axis=axis, **kw)
