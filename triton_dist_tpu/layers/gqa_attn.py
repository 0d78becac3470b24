"""Plain grouped-query attention blocks of the hybrid family — one
chip's heads, in two kinds that share their weights' shapes.

q, k and v come out of two projections (q; k | v); q and k are
RMS-normalised per head under a gain w (unless the spec says the
source has no such norm); causal grouped-query attention at D ** -0.5
(or the spec's own scale); the output projection. No output gate (that
is `layers/gated_attn.py`). A global block whose heads are narrower
than a page keeps them (`GQAttnSpec.store`: heads of 64 in whole
128-value lanes) pads q, k and v with zero columns before the
attention and keeps the output's first D: the scores and the values
are the unpadded ones exactly, and the kernel sees a head it takes.
What the two kinds differ in is the cache they read and the rotary
embedding:

  global  (`global_attn_fwd`)  every cached position, through the
          slot's pages, as the gated block reads them; NO rotary.
  window  (`window_attn_fwd`)  rotary over the whole head, and the
          last `window` positions alone: key j for row i where
          i - window < j <= i. Its cache is a fixed per-slot TAIL, the
          keys and values of the `window` positions before the chunk
          (B, window, Hkv, D), so a step attends `window + C`
          positions whatever the context, and the new tail is the
          last `window` rows of [tail | the chunk's valid rows].

The attention itself is `layers.attention.gqa_attention`; `attn_impl`
is the planner's answer (plan.planner.route_hybrid_attention,
route_window_attention) and is never left to fall through to another
implementation.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from triton_dist_tpu.layers.attention import gqa_attention
from triton_dist_tpu.layers.norm import rms_norm
from triton_dist_tpu.layers.parts import part
from triton_dist_tpu.layers.rope import apply_rope
from triton_dist_tpu.layers.tp_attn import _scatter_kv


class GQAttnSpec(NamedTuple):
    num_q_heads: int
    num_kv_heads: int
    head_dim: int
    qk_norm: bool = True  # q and k RMS-normalised a head
    scale: Optional[float] = None  # the softmax scale; None = D ** -0.5
    store: int = 0  # the width a page keeps a head in; 0 = head_dim


class GQAttnParams(NamedTuple):
    w_q: jax.Array  # (H, Hq D)
    w_kv: jax.Array  # (H, 2 Hkv D): k | v
    q_norm: Optional[jax.Array]  # (D,); None without the head norms
    k_norm: Optional[jax.Array]
    w_o: jax.Array  # (Hq D, H)


def _qkv(x, p: GQAttnParams, spec: GQAttnSpec, eps: float):
    b, c, _ = x.shape
    hq, hkv, d = spec[:3]
    with part("attn.proj"):
        q = jnp.dot(x, p.w_q, preferred_element_type=jnp.float32).astype(
            x.dtype).reshape(b, c, hq, d)
        kv = jnp.dot(x, p.w_kv, preferred_element_type=jnp.float32).astype(
            x.dtype)
        k = kv[..., :hkv * d].reshape(b, c, hkv, d)
        v = kv[..., hkv * d:].reshape(b, c, hkv, d)
    if not spec.qk_norm:
        return q, k, v
    with part("attn.core"):
        return (rms_norm(q, p.q_norm, eps, zero_centred=False),
                rms_norm(k, p.k_norm, eps, zero_centred=False), v)


@part("attn.proj")
def _out(out, x, p: GQAttnParams):
    b, c, _ = x.shape
    return jnp.dot(out.reshape(b, c, -1).astype(x.dtype), p.w_o,
                   preferred_element_type=jnp.float32).astype(x.dtype)


def global_attn_fwd(x, p: GQAttnParams, spec: GQAttnSpec, positions,
                    kv_cache, kv_len, attn_impl: str, eps: float):
    """x (B, C, H); kv_cache (k, v) each (B, T, Hkv, D); positions
    (B, C) absolute; kv_len (B,). Returns (y (B, C, H), (k, v): the
    chunk's rows (B, C, Hkv, D) in the cache's dtype)."""
    q, k, v = _qkv(x, p, spec, eps)
    d = spec.head_dim
    pad = (spec.store or d) - d
    scale = spec.scale
    with part("attn.core"):
        if pad:  # zero columns: q . k and the first D of p v as they were
            q, k, v = (jnp.pad(t, ((0, 0),) * 3 + ((0, pad),))
                       for t in (q, k, v))
            scale = d ** -0.5 if scale is None else scale
        k_cache, v_cache = kv_cache
        k, v = k.astype(k_cache.dtype), v.astype(v_cache.dtype)
        out = gqa_attention(q, _scatter_kv(k_cache, k, positions),
                            _scatter_kv(v_cache, v, positions), causal=True,
                            q_positions=positions, kv_len=kv_len,
                            scale=scale, prefill_impl=attn_impl)
        if pad:
            out = out[..., :d]
    return _out(out, x, p), (k, v)


def window_attn_fwd(x, p: GQAttnParams, spec: GQAttnSpec, cos, sin,
                    positions, tail, lengths, n_valid, window: int,
                    attn_impl: str, eps: float):
    """x (B, C, H); tail (k, v) each (B, window, Hkv, D): row t holds
    position lengths - window + t (nothing where that is negative);
    positions (B, C) absolute; lengths, n_valid (B,). Returns
    (y (B, C, H), the new tail (k, v))."""
    b, c, _ = x.shape
    q, k, v = _qkv(x, p, spec, eps)
    with part("attn.core"):
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)
        k_tail, v_tail = tail
        keys = jnp.concatenate([k_tail, k.astype(k_tail.dtype)], axis=1)
        vals = jnp.concatenate([v_tail, v.astype(v_tail.dtype)], axis=1)
        # in the step's own coordinates: the tail is 0 .. window - 1,
        # the chunk's column j is window + j
        local = jnp.broadcast_to(window + jnp.arange(c)[None, :], (b, c))
        out = gqa_attention(q, keys, vals, causal=True, q_positions=local,
                            prefill_impl=attn_impl, window=window,
                            kv_from=jnp.maximum(window - lengths, 0))

    def shift(rows, n):  # the last `window` of [tail | n valid rows]
        return jax.lax.dynamic_slice_in_dim(rows, n, window, axis=0)

    y = _out(out, x, p)
    with part("pool.scatter"):
        return y, (jax.vmap(shift)(keys, n_valid),
                   jax.vmap(shift)(vals, n_valid))
