"""Gated full attention — Qwen3-Next's softmax mixer, one chip's heads.

q and an output gate come out of ONE projection (per head [q | gate]);
q and k are RMS-normalised per head under a (1 + w) gain; rotary
embedding (rotate-half) turns the first `rotary_dim` of the head's
dims and leaves the rest; causal grouped-query attention over the
cache at D ** -0.5; the heads' output is multiplied by sigmoid(gate)
before the output projection.

The attention itself is `layers.attention.gqa_attention` over the
dense view of the slot's pages, as in the dense family; `attn_impl`
is the planner's answer (plan.planner.route_gated_attention) and is
never left to fall through to another implementation.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from triton_dist_tpu.layers.attention import gqa_attention
from triton_dist_tpu.layers.norm import rms_norm
from triton_dist_tpu.layers.parts import part
from triton_dist_tpu.layers.rope import apply_rope
from triton_dist_tpu.layers.tp_attn import _scatter_kv


class GatedAttnSpec(NamedTuple):
    num_q_heads: int
    num_kv_heads: int
    head_dim: int
    rotary_dim: int


class GatedAttnParams(NamedTuple):
    w_q: jax.Array  # (H, Hq * 2 D): per head q | gate
    w_kv: jax.Array  # (H, 2 Hkv D): k | v
    q_norm: jax.Array
    k_norm: jax.Array
    w_o: jax.Array


def _partial_rope(x, cos, sin, positions, rot: int):
    if rot == x.shape[-1]:
        return apply_rope(x, cos, sin, positions)
    return jnp.concatenate(
        [apply_rope(x[..., :rot], cos, sin, positions), x[..., rot:]],
        axis=-1)


def gated_attn_fwd(x, p: GatedAttnParams, spec: GatedAttnSpec, cos, sin,
                   positions, kv_cache, kv_len, attn_impl: str,
                   eps: float = 1e-6):
    """x (B, C, H); kv_cache (k, v) each (B, T, Hkv, D); positions
    (B, C) absolute; kv_len (B,). Returns (y (B, C, H), (k, v): the
    chunk's rows (B, C, Hkv, D) in the cache's dtype, as they were laid
    into this call's own copy of the view at `positions`)."""
    b, c, _ = x.shape
    hq, hkv, d = spec.num_q_heads, spec.num_kv_heads, spec.head_dim
    with part("attn.proj"):
        qg = jnp.dot(x, p.w_q, preferred_element_type=jnp.float32).astype(
            x.dtype).reshape(b, c, hq, 2 * d)
        q, gate = qg[..., :d], qg[..., d:]
        kv = jnp.dot(x, p.w_kv, preferred_element_type=jnp.float32).astype(
            x.dtype)
        k = kv[..., :hkv * d].reshape(b, c, hkv, d)
        v = kv[..., hkv * d:].reshape(b, c, hkv, d)
    with part("attn.core"):
        q = rms_norm(q, p.q_norm, eps, zero_centred=True)
        k = rms_norm(k, p.k_norm, eps, zero_centred=True)
        q = _partial_rope(q, cos, sin, positions, spec.rotary_dim)
        k = _partial_rope(k, cos, sin, positions, spec.rotary_dim)
        k_cache, v_cache = kv_cache
        k, v = k.astype(k_cache.dtype), v.astype(v_cache.dtype)
        out = gqa_attention(q, _scatter_kv(k_cache, k, positions),
                            _scatter_kv(v_cache, v, positions), causal=True,
                            q_positions=positions, kv_len=kv_len,
                            prefill_impl=attn_impl)
    with part("attn.proj"):
        out = out.astype(jnp.float32) * jax.nn.sigmoid(
            gate.astype(jnp.float32))
        y = jnp.dot(out.reshape(b, c, hq * d).astype(x.dtype), p.w_o,
                    preferred_element_type=jnp.float32).astype(x.dtype)
    return y, (k, v)
