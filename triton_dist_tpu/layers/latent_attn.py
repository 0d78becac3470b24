"""Latent attention without rotary — Kimi-Linear's softmax mixer (MLA
with `mla_use_nope`), one chip's heads, in the ABSORBED form.

What a token leaves in the cache is ONE row shared by every head,
`[c_kv | k_r]`: c_kv = RMSNorm(x W_a [:r]) of the latent rank r, and
k_r = x W_a [r:], the part of the key that does not go through the
latent (it would carry the rotary embedding; this model applies none).
A head's keys and values are c_kv W_b, (k_n | v) a head. Expanded,
score_h(t, s) = (q_n . k_n + q_r . k_r) / sqrt(dn + dr). Absorbed, the
same numbers without ever expanding the cache: with W_b split a head
into W_uk, W_uv (r x dn, r x dv),

    q^_h = q_n W_uk^T           score = (q^_h . c_kv,s + q_r . k_r,s) * scale
    o^_h = sum_s p c_kv,s       o_h = o^_h W_uv

that is, Hq query heads of width r + dr over ONE shared head whose
first r columns are also the values. `attn_impl` is the planner's
answer (plan.planner.route_hybrid_attention) and is never left to fall
through: "pallas" is `kernels.flash_prefill.flash_prefill_local` with
the value a column prefix of the key page (each latent page is read
once), "xla" `layers.attention.gqa_attention_blockwise`'s scan over
the same view.

Column layout of the weights (the builder's; the benchmark's reference
draws the same): w_q (H, Hq (dn + dr)) a head q_n | q_r · w_a
(H, r + dr) c | k_r · kv_norm (r,) · w_b (r, Hq (dn + dv)) a head
k_n | v · w_o (Hq dv, H).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from triton_dist_tpu.layers.attention import gqa_attention_blockwise
from triton_dist_tpu.layers.norm import rms_norm
from triton_dist_tpu.layers.parts import part
from triton_dist_tpu.layers.tp_attn import _scatter_kv


class LatentAttnSpec(NamedTuple):
    num_q_heads: int
    rank: int  # kv_lora_rank r
    nope_dim: int  # dn
    rope_dim: int  # dr
    v_dim: int  # dv

    @property
    def row(self) -> int:
        """Values a token leaves in the cache."""
        return self.rank + self.rope_dim


class LatentAttnParams(NamedTuple):
    w_q: jax.Array
    w_a: jax.Array
    kv_norm: jax.Array
    w_b: jax.Array
    w_o: jax.Array


def latent_attn_fwd(x, p: LatentAttnParams, spec: LatentAttnSpec,
                    positions, view, kv_len, n_valid, attn_impl: str,
                    eps: float = 1e-6):
    """x (B, C, H); view (B, T, 1, W), the slot's latent rows;
    positions (B, C) absolute; kv_len (B,); n_valid (B,) real columns
    of each row. Returns (y (B, C, H), the chunk's rows (B, C, 1, W) in
    the cache's dtype, as they were laid into this call's own copy of
    the view at `positions`).

    A column at or past n_valid attends NOTHING: it is handed to the
    attention at position -1, before every key, so the kernel's
    dead-page skip passes over a tile of such columns without reading
    a page (its output, zeros, is discarded with the column). A row
    that decodes one token then pays for one tile of 16 columns over
    its context and not for eight, and an idle row for none; a valid
    column folds the pages it folded before, bit for bit."""
    b, c, _ = x.shape
    hq, r, dn, dr, dv = spec
    f32 = jnp.float32
    with part("attn.proj"):
        q = jnp.dot(x, p.w_q, preferred_element_type=f32).astype(
            x.dtype).reshape(b, c, hq, dn + dr)
        a = jnp.dot(x, p.w_a, preferred_element_type=f32).astype(x.dtype)
    with part("attn.core"):
        row = jnp.concatenate(
            [rms_norm(a[..., :r], p.kv_norm, eps), a[..., r:]],
            axis=-1).astype(view.dtype)[:, :, None, :]
    with part("attn.proj"):  # the absorbed key projection
        w_b = p.w_b.reshape(r, hq, dn + dv)
        q_hat = jnp.einsum("bchn,rhn->bchr", q[..., :dn], w_b[..., :dn],
                           preferred_element_type=f32).astype(x.dtype)
    with part("attn.core"):
        q_abs = jnp.concatenate([q_hat, q[..., dn:]], axis=-1)
        pad = view.shape[-1] - spec.row  # a page row padded to the lanes
        if pad:
            row, q_abs = (jnp.pad(t, ((0, 0),) * 3 + ((0, pad),))
                          for t in (row, q_abs))
        view = _scatter_kv(view, row, positions)
        asks = jnp.where(jnp.arange(c)[None, :] < n_valid[:, None],
                         positions, -1)
        o_hat = gqa_attention_blockwise(
            q_abs, view, view[..., :r], causal=True, q_positions=asks,
            kv_len=kv_len, scale=(dn + dr) ** -0.5, impl=attn_impl)
    with part("attn.proj"):  # the absorbed value projection, then out
        o = jnp.einsum("bchr,rhv->bchv", o_hat, w_b[..., dn:],
                       preferred_element_type=f32).astype(x.dtype)
        y = jnp.dot(o.reshape(b, c, hq * dv), p.w_o,
                    preferred_element_type=f32).astype(x.dtype)
    return y, (row,)
