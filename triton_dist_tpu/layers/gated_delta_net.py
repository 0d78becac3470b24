"""Gated delta net — Qwen3-Next's linear-attention mixer, in the
chunked (matmul) form over a carried per-slot state.

Per value head, with state S (dk, dv) in float32:

    S = exp(g_t) S;  r = v_t - S^T k_t;  S = S + k_t (beta_t r)^T
    o_t = S^T q_t

q, k and v come out of one projection and a causal depthwise
convolution of width K (no bias) followed by SiLU; q and k are
L2-normalised over dk (q scaled by dk ** -0.5) and repeated to the
value heads; the output is RMS-normalised over dv under a gain and
SiLU(z), then projected.

A serve step hands this layer a fixed (slots, chunk) block in which
slot s has `n_valid[s]` real columns. A column at or past n_valid has
beta = 0 and g = 0 and feeds nothing to the convolution state, so it
leaves both states bit for bit as they were; a slot whose length is 0
starts from zero state. What is carried between steps is S and the
last K - 1 convolution inputs (the projection's q | k | v channels).

Column layout of the weights (the builder's, written down once; the
benchmark's reference draws the same):
  w_qkvz (H, 2 Hk dk + 2 Hv dv)   q | k | v | z, each head-major
  w_ba   (H, 2 Hv)                b | a
  conv_w (K, 2 Hk dk + Hv dv)     tap j multiplies the input K-1-j back
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from triton_dist_tpu.layers.norm import rms_norm

# the delta rule's products are float32 and stay float32 on the MXU:
# the default precision would round the state's operands to bfloat16
_HI = jax.lax.Precision.HIGHEST
# columns one triangular solve covers; a step's chunk is cut into
# sub-chunks of this many columns with the state carried between them
SUB_CHUNK = 64


class GDNSpec(NamedTuple):
    num_k_heads: int
    num_v_heads: int
    k_dim: int
    v_dim: int
    conv: int  # convolution width K

    @property
    def channels(self) -> int:
        """The convolved channels: q | k | v."""
        return 2 * self.num_k_heads * self.k_dim \
            + self.num_v_heads * self.v_dim


class GDNParams(NamedTuple):
    w_qkvz: jax.Array
    w_ba: jax.Array
    conv_w: jax.Array
    a_log: jax.Array
    dt_bias: jax.Array
    norm: jax.Array
    w_out: jax.Array


def sub_chunk(chunk: int) -> int:
    return SUB_CHUNK if chunk % SUB_CHUNK == 0 else chunk


def _unit_lower_inverse(low):
    """(I + L)^-1 for strictly lower L (..., c, c), as the product
    (I - L)(I + L^2)(I + L^4)...: L is nilpotent, so the series ends."""
    c = low.shape[-1]
    eye = jnp.eye(c, dtype=low.dtype)
    out = eye - low
    power = low
    span = 2
    while span < c:
        power = jnp.matmul(power, power, precision=_HI)
        out = jnp.matmul(out, eye + power, precision=_HI)
        span *= 2
    return out


def chunk_gated_delta_rule(q, k, v, g, beta, state, c: int):
    """The delta rule over L = N * c columns in matmul form.
    q, k (B, H, L, dk), v (B, H, L, dv), g, beta (B, H, L), state
    (B, H, dk, dv), all float32; q already scaled. Returns
    (o (B, H, L, dv), the state after the last column)."""
    b, h, length, dk = k.shape
    dv = v.shape[-1]
    n = length // c

    def cut(x):
        return x.reshape(b, h, n, c, *x.shape[3:])

    q, k, v, g, beta = map(cut, (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-1)  # (B, H, N, c)
    k_beta = k * beta[..., None]
    v_beta = v * beta[..., None]
    rows = jnp.arange(c)
    lower = rows[:, None] >= rows[None, :]
    strict = rows[:, None] > rows[None, :]
    # exp only where it is used: above the diagonal the exponent is >= 0
    decay = jnp.exp(jnp.where(lower, gc[..., :, None] - gc[..., None, :],
                              -jnp.inf))
    kk = jnp.einsum("bhnid,bhnjd->bhnij", k_beta, k, precision=_HI)
    solve = _unit_lower_inverse(jnp.where(strict, kk * decay, 0.0))
    value = jnp.matmul(solve, v_beta, precision=_HI)
    k_cum = jnp.matmul(solve, k_beta * jnp.exp(gc)[..., None],
                       precision=_HI)
    qk = jnp.einsum("bhnid,bhnjd->bhnij", q, k, precision=_HI) * decay
    q_in = q * jnp.exp(gc)[..., None]
    last = gc[..., -1]  # (B, H, N)
    k_out = k * jnp.exp(last[..., None] - gc)[..., None]

    def step(s, xs):
        value_i, k_cum_i, qk_i, q_in_i, k_out_i, last_i = xs
        v_new = value_i - jnp.matmul(k_cum_i, s, precision=_HI)
        o = jnp.matmul(q_in_i, s, precision=_HI) \
            + jnp.matmul(qk_i, v_new, precision=_HI)
        s = s * jnp.exp(last_i)[..., None, None] + jnp.einsum(
            "bhck,bhcv->bhkv", k_out_i, v_new, precision=_HI)
        return s, o

    xs = tuple(jnp.moveaxis(x, 2, 0)
               for x in (value, k_cum, qk, q_in, k_out, last))
    state, o = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(o, 0, 2).reshape(b, h, length, dv), state


def _l2norm(x, eps: float = 1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def gated_delta_net_fwd(x, p: GDNParams, spec: GDNSpec, rec, conv,
                        n_valid, fresh, eps: float = 1e-6):
    """x (B, C, H); rec (B, Hv, dk, dv) float32; conv (B, K-1,
    channels); n_valid (B,) real columns of each row; fresh (B,) bool,
    rows that start from zero state. Returns (y (B, C, H), rec, conv)."""
    b, c, _ = x.shape
    hk, hv, dk, dv = (spec.num_k_heads, spec.num_v_heads, spec.k_dim,
                      spec.v_dim)
    ch, taps = spec.channels, spec.conv
    valid = jnp.arange(c)[None, :] < n_valid[:, None]  # (B, C)
    rec = jnp.where(fresh[:, None, None, None], 0.0, rec)
    conv = jnp.where(fresh[:, None, None], jnp.zeros((), conv.dtype), conv)

    qkvz = jnp.dot(x, p.w_qkvz,
                   preferred_element_type=jnp.float32).astype(x.dtype)
    ba = jnp.dot(x, p.w_ba, preferred_element_type=jnp.float32)
    mixed, z = qkvz[..., :ch], qkvz[..., ch:]

    # causal depthwise convolution over [carried K-1 inputs | chunk]
    seq = jnp.concatenate([conv.astype(x.dtype), mixed], axis=1)
    w = p.conv_w.astype(jnp.float32)
    acc = sum(seq[:, j:j + c].astype(jnp.float32) * w[j]
              for j in range(taps))
    mixed = jax.nn.silu(acc).astype(x.dtype)
    # the K-1 inputs before column n_valid: the old state where
    # n_valid is 0, untouched by the padding columns
    conv = jax.vmap(lambda s, n: jax.lax.dynamic_slice_in_dim(
        s, n, taps - 1, axis=0))(seq, n_valid).astype(conv.dtype)

    f32 = jnp.float32
    q = mixed[..., :hk * dk].reshape(b, c, hk, dk).astype(f32)
    k = mixed[..., hk * dk:2 * hk * dk].reshape(b, c, hk, dk).astype(f32)
    v = mixed[..., 2 * hk * dk:].reshape(b, c, hv, dv).astype(f32)
    beta = jnp.where(valid[..., None], jax.nn.sigmoid(ba[..., :hv]), 0.0)
    g = -jnp.exp(p.a_log.astype(f32)) * jax.nn.softplus(
        ba[..., hv:] + p.dt_bias.astype(f32))
    g = jnp.where(valid[..., None], g, 0.0)
    rep = hv // hk
    q = jnp.repeat(_l2norm(q) * dk ** -0.5, rep, axis=2)
    k = jnp.repeat(_l2norm(k), rep, axis=2)

    def heads_first(t):
        return jnp.moveaxis(t, 2, 1)

    o, rec = chunk_gated_delta_rule(
        heads_first(q), heads_first(k), heads_first(v),
        heads_first(g), heads_first(beta), rec, sub_chunk(c))
    o = jnp.moveaxis(o, 1, 2)  # (B, C, Hv, dv)
    zf = z.reshape(b, c, hv, dv).astype(f32)
    o = rms_norm(o, p.norm, eps) * jax.nn.silu(zf)
    y = jnp.dot(o.reshape(b, c, hv * dv).astype(x.dtype), p.w_out,
                preferred_element_type=jnp.float32).astype(x.dtype)
    return y, rec, conv
