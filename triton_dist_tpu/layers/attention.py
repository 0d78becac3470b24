"""Attention cores: GQA prefill (causal) and single-step decode.

TPU-native analog of the reference's attention calls inside TP_Attn
(ref: python/triton_dist/layers/nvidia/tp_attn.py:180-253, which calls
flashinfer prefill/decode kernels). Two regimes:

  dense — one einsum chain; XLA fuses it and the MXU does the work. The
  (B, Hkv, G, S, T) f32 logits tensor is materialized, fine up to a few
  thousand tokens.
  blockwise — the flash-attention form: fold KV chunk-by-chunk through
  the online softmax, so peak memory is O(S*chunk) instead of O(S*T).
  gqa_attention auto-selects it past _BLOCKWISE_T tokens (the flashinfer
  prefill analog, ref tp_attn.py:180-253). Two implementations ride the
  same contract behind the `impl` switch: "xla" (lax.scan over
  _block_update — each chunk's f32 logits tensor materializes between
  the einsums) and "pallas" (kernels/flash_prefill.flash_prefill_local —
  double-buffered KV pages, logits never leave VMEM). "auto" asks
  perf_model.choose_prefill_impl, with the xla path as the fallback
  whenever the kernel's native shape support does not hold.

Pallas also carries the *distributed* variants (sp_attention.py,
flash_decode.py, flash_prefill.sp_flash_prefill) where per-segment
semaphore waits are the point.

Shapes (GQA): q (B, S, Hq, D), k/v (B, T, Hkv, D), Hq = G * Hkv.
All softmax math in f32.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30

# past this KV length the dense S x T logits tensor is a liability and
# the blockwise path takes over (at the bench ctx=512 the dense fused
# chain stays)
_BLOCKWISE_T = 4096


def _route_prefill_impl(b, s, t, hq, hkv, d, dtype) -> str:
    """The prefill-impl routing predicate ("pallas" | "xla"), shared by
    gqa_attention's auto path and gqa_attention_blockwise's "auto".
    The decision itself lives with the fusion planner
    (plan.planner.route_prefill_impl — native gate + VMEM fit +
    perf_model.choose_prefill_impl); this is the call-site delegate."""
    from triton_dist_tpu.plan.planner import route_prefill_impl

    return route_prefill_impl(b, s, t, hq, hkv, d, dtype)


def gqa_attention_blockwise(
    q,
    k,
    v,
    causal: bool = True,
    q_offset: int | jnp.ndarray = 0,
    q_positions: Optional[jnp.ndarray] = None,
    kv_len: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    chunk: int = 512,
    impl: str = "auto",
    window: Optional[int] = None,
    kv_from: Optional[jnp.ndarray] = None,
):
    """Blockwise (flash) GQA prefill: same contract as gqa_attention but
    KV is folded chunk-by-chunk through the online softmax, never
    materializing the (S, T) logits (ref: the flashinfer prefill call,
    tp_attn.py:180-253; xla core shared with ring_attention's
    _block_update). impl: "xla" | "pallas" | "auto" (the module-doc
    switch; perf_model.choose_prefill_impl).

    v may be narrower than k (latent attention, layers/latent_attn.py:
    one shared head whose values are the first columns of its keys);
    the result then has v's width. The kernel is told so and reads
    each key page once, taking the values out of it.

    window / kv_from: gqa_attention's; the kernel's alone (the scan
    below knows no lower bound)."""
    from triton_dist_tpu.kernels.sp_attention import _block_update

    if impl == "auto":
        bq, sq, hq_, dq = q.shape
        impl = _route_prefill_impl(bq, sq, k.shape[1], hq_, k.shape[2],
                                   dq, k.dtype)
    if impl == "pallas":
        from triton_dist_tpu.kernels.flash_prefill import (
            flash_prefill_local,
        )

        # `chunk` IS the kernel's KV page height — the tuning knob of
        # the shared contract must steer both implementations
        prefix = v.shape[-1] if v.shape[-1] != k.shape[-1] else None
        return flash_prefill_local(
            q, k, None if prefix else v, q_positions=q_positions,
            q_offset=q_offset, kv_len=kv_len, causal=causal, scale=scale,
            block=chunk, v_prefix=prefix, window=window, kv_from=kv_from,
        )
    assert impl == "xla", f"unknown blockwise impl {impl!r}"
    assert window is None, "the blockwise scan has no window bound"

    b, s, hq, d = q.shape
    _, t, hkv, _ = k.shape
    dv = v.shape[-1]
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    if t % chunk:
        # pad KV to a chunk multiple and mask the tail via kv_len —
        # shrinking the chunk instead degrades to 1-token blocks for odd
        # T (round-5 review: 4097 scan steps on the 'fast' path)
        pad = chunk - t % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_len = (jnp.full((b,), t) if kv_len is None
                  else jnp.minimum(jnp.reshape(kv_len, (-1,)), t))
        t += pad
    nc = t // chunk

    qf = q.astype(jnp.float32).reshape(b, s, hkv, g, d)
    if q_positions is None:
        q_pos = jnp.arange(s)[None, :] + q_offset
        q_pos = jnp.broadcast_to(q_pos, (b, s))
    else:
        q_pos = q_positions

    kc = jnp.moveaxis(k.reshape(b, nc, chunk, hkv, d), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, nc, chunk, hkv, dv), 1, 0)

    def body(state, xs):
        acc, m, l = state
        ci, kb, vb = xs
        k_pos = ci * chunk + jnp.arange(chunk)
        acc, m, l = _block_update(
            qf, kb.astype(jnp.float32), vb.astype(jnp.float32),
            q_pos, k_pos, acc, m, l, scale, causal, kv_len=kv_len,
        )
        return (acc, m, l), None

    state0 = (
        jnp.zeros((b, hkv, g, s, dv), jnp.float32),
        jnp.full((b, hkv, g, s, 1), NEG_INF, jnp.float32),
        jnp.zeros((b, hkv, g, s, 1), jnp.float32),
    )
    (acc, m, l), _ = jax.lax.scan(body, state0,
                                  (jnp.arange(nc), kc, vc))
    out = acc / jnp.maximum(l, 1e-30)
    out = jnp.einsum("bkgsd->bskgd", out).reshape(b, s, hq, dv)
    return out.astype(q.dtype)


def gqa_attention(
    q,
    k,
    v,
    causal: bool = True,
    q_offset: int | jnp.ndarray = 0,
    q_positions: Optional[jnp.ndarray] = None,
    kv_len: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    prefill_impl: Optional[str] = None,
    prefill_block: Optional[int] = None,
    window: Optional[int] = None,
    kv_from: Optional[jnp.ndarray] = None,
):
    """Grouped-query attention forward.

    q_offset: absolute position of q row 0 within the KV timeline (decode:
    cache length). q_positions: (B, S) absolute positions of the q rows —
    the general form (prefill-into-cache, per-batch offsets); overrides
    q_offset. kv_len: optional valid KV prefix length (masks the
    preallocated cache tail). prefill_impl: force the multi-token
    prefill implementation ("xla" | "pallas" — the serve prefill-chunk
    switch; None = auto routing: the Pallas flash kernel whenever the
    native gate + perf model pick it, the blockwise scan past
    _BLOCKWISE_T, the dense einsum chain otherwise). prefill_block:
    override the blockwise KV page height (the planner's tune-cache
    attn_block; None keeps the 512 default, so an empty cache compiles
    exactly the legacy program). window: a row at position i attends
    the keys at i - window + 1 .. i alone, and none before kv_from
    ((B,), default 0); a static branch, the Pallas kernel's or the
    dense chain's (a window's T is short). Returns (B, S, Hq, D) in
    q.dtype.
    """
    b, s, hq, d = q.shape
    _, t, hkv, _ = k.shape
    if s > 1:
        impl = (prefill_impl if prefill_impl is not None
                else _route_prefill_impl(b, s, t, hq, hkv, d, k.dtype))
        blk = {} if prefill_block is None else {"chunk": int(prefill_block)}
        if impl == "pallas":
            # serve prefill-chunk / native prefill: the Pallas kernel
            # beats the dense chain as soon as the f32 logits tensor
            # is the dominant HBM term (perf_model prices both)
            return gqa_attention_blockwise(
                q, k, v, causal=causal, q_offset=q_offset,
                q_positions=q_positions, kv_len=kv_len, scale=scale,
                impl="pallas", window=window, kv_from=kv_from, **blk,
            )
        if t >= _BLOCKWISE_T:
            # long-context prefill: O(S*chunk) blockwise path (decode
            # s==1 stays dense — its "logits" are one row)
            return gqa_attention_blockwise(
                q, k, v, causal=causal, q_offset=q_offset,
                q_positions=q_positions, kv_len=kv_len, scale=scale,
                impl="xla", **blk,
            )
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5

    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qg = qf.reshape(b, s, hkv, g, d)

    # logits: (B, Hkv, G, S, T)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, kf)

    mask = None
    kpos = jnp.arange(t)
    if causal:
        if q_positions is not None:
            qpos = q_positions[:, :, None]  # (B, S, 1)
            mask = (kpos[None, None, :] <= qpos)[:, None, None]  # (B,1,1,S,T)
        else:
            qpos = jnp.arange(s)[:, None] + q_offset  # (S, 1)
            mask = kpos[None, :] <= qpos  # (S, T)
    if kv_len is not None:
        valid = kpos[None, :] < jnp.reshape(kv_len, (-1, 1))  # (B, T)
        valid = valid[:, None, None, None, :]
        mask = valid if mask is None else jnp.logical_and(mask, valid)
    if window is not None:
        assert causal and q_positions is not None
        near = kpos[None, None, :] > q_positions[:, :, None] - window
        if kv_from is not None:
            near = near & (kpos[None, None, :]
                           >= jnp.reshape(kv_from, (-1, 1, 1)))
        mask = jnp.logical_and(mask, near[:, None, None])
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)

    # Numerically-safe softmax (rows fully masked yield zeros, not NaN).
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - jnp.maximum(m, NEG_INF / 2))
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)

    out = jnp.einsum("bkgst,btkd->bskgd", p, vf)
    return out.reshape(b, s, hq, d).astype(q.dtype)
