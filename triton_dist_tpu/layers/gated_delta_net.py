"""Gated delta nets — the hybrid family's linear-attention mixers, in
the chunked (matmul) form over a carried per-slot state.

Per value head, with state S (dk, dv) in float32:

    S = exp(g_t) S;  r = v_t - S^T k_t;  S = S + k_t (beta_t r)^T
    o_t = S^T q_t

The gate g_t is ONE number a head (Qwen3-Next's gated delta net,
`gated_delta_net_fwd`) or a vector over the head's dk KEY CHANNELS, so
that exp(g_t) S is diag(exp(g_t)) S (Kimi-Linear's KDA, `kda_fwd`);
`chunk_gated_delta_rule` takes either.

In the scalar-gated mixer q, k and v come out of one projection and a causal depthwise
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

The channel-gated mixer (KDA) has as many key heads as value heads;
its gate is -exp(A_log[h]) softplus((x W_fa) W_fb + dt_bias) a key
channel, beta = sigmoid(x W_b) a head, and its output is
RMS-normalised over dv under a gain and multiplied by
sigmoid((x W_ga) W_gb) before the projection. Its three convolutions
are one depthwise convolution over the q | k | v channels, as the
state holds them.

Column layout of the weights (the builder's, written down once; the
benchmark's references draw the same):
  w_qkvz (H, 2 Hk dk + 2 Hv dv)   q | k | v | z, each head-major
  w_ba   (H, 2 Hv)                b | a
  conv_w (K, 2 Hk dk + Hv dv)     tap j multiplies the input K-1-j back
  KDA: w_qkv (H, 3 Hv dk) q | k | v · w_fgb (H, 2 r + Hv) f_a | g_a | b
  · w_fb, w_gb (r, Hv dk / Hv dv) · dt_bias (Hv dk) · a_log (Hv)
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from triton_dist_tpu.layers.norm import rms_norm
from triton_dist_tpu.layers.parts import part

# the delta rule's products are float32 and stay float32 on the MXU:
# the default precision would round the state's operands to bfloat16
_HI = jax.lax.Precision.HIGHEST
# columns one triangular solve covers; a step's chunk is cut into
# sub-chunks of this many columns with the state carried between them
SUB_CHUNK = 64
# under a gate per key channel, the columns of one BLOCK of a
# sub-chunk: inside a block the decay between two columns is taken
# channel by channel, an (n, n, dk) term; across blocks it factors
# through the later block's first column (`_channel_products`)
GATE_BLOCK = 16


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


def _channel_products(q, k, k_beta, gc, n: int):
    """The two (c, c) products of a sub-chunk under a gate per key
    channel: kk[i, j] = sum_d k_beta_i[d] k_j[d] exp(gc_i[d] - gc_j[d])
    below the diagonal, qk[i, j] the same with q_i on and below it.
    q, k, k_beta, gc (..., c, dk), gc the gate's running sum.

    exp(gc_i) exp(-gc_j) does not stay in float32's range over a
    sub-chunk (a gate of -0.7 a column is exp(88) after 128), so every
    exponent is kept at or below zero: between two columns of one
    block of `n` the difference is taken channel by channel; a column
    i of a later block meets an earlier column j through the first
    column f of i's block, exp(gc_i - gc_f) exp(gc_f - gc_j), both at
    or below zero because gc only falls."""
    c, dk = k.shape[-2:]
    nb = c // n
    lead = k.shape[:-2]

    def blocks(x):
        return x.reshape(lead + (nb, n, dk))

    qb, kb, kbb, gb = map(blocks, (q, k, k_beta, gc))
    rows = jnp.arange(n)
    within = (rows[:, None] >= rows[None, :])[..., None]
    first = gb[..., :1, :]  # (..., nb, 1, dk)
    fall = jnp.exp(gb - first)  # a block's columns from its first
    # every column j from each block's first; at or after it the
    # exponent is cut to 0 and the product masked below
    rise = jnp.exp(jnp.minimum(first - gc[..., None, :, :], 0.0))
    k_from = k[..., None, :, :] * rise  # (..., nb, c, dk)
    col_block = jnp.arange(c) // n
    earlier = (col_block[None, None, :]
               < jnp.arange(nb)[:, None, None])  # (nb, 1, c)
    same = col_block[None, None, :] == jnp.arange(nb)[:, None, None]

    def product(left):
        # the (n, n, dk) term, made where it is summed: each product
        # exponentiates for itself so that neither keeps it in memory
        inside = jnp.exp(jnp.where(
            within, gb[..., :, None, :] - gb[..., None, :, :], -jnp.inf))
        near = jnp.sum(left[..., :, None, :] * inside
                       * kb[..., None, :, :], axis=-1)  # (..., nb, n, n)
        far = jnp.einsum("...id,...jd->...ij", left * fall, k_from,
                         precision=_HI)  # (..., nb, n, c)
        full = jnp.where(earlier, far, jnp.where(
            same, jnp.tile(near, (1,) * (near.ndim - 1) + (nb,)), 0.0))
        return full.reshape(lead + (c, c))

    strict = jnp.arange(c)[:, None] > jnp.arange(c)[None, :]
    return jnp.where(strict, product(kbb), 0.0), product(qb)


def chunk_gated_delta_rule(q, k, v, g, beta, state, c: int):
    """The delta rule over L = N * c columns in matmul form.
    q, k (B, H, L, dk), v (B, H, L, dv), beta (B, H, L), state
    (B, H, dk, dv), all float32; q already scaled. g is (B, H, L), one
    gate a head, or (B, H, L, dk), one a key channel. Returns
    (o (B, H, L, dv), the state after the last column)."""
    b, h, length, dk = k.shape
    dv = v.shape[-1]
    n = length // c
    per_channel = g.ndim == k.ndim

    def cut(x):
        return x.reshape(b, h, n, c, *x.shape[3:])

    q, k, v, g, beta = map(cut, (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=3)  # (B, H, N, c[, dk])
    k_beta = k * beta[..., None]
    v_beta = v * beta[..., None]
    if per_channel:
        kk, qk = _channel_products(q, k, k_beta, gc,
                                   GATE_BLOCK if c % GATE_BLOCK == 0 else c)
        from_start = jnp.exp(gc)  # (B, H, N, c, dk)
        last = gc[..., -1, :]  # (B, H, N, dk)
        to_end = jnp.exp(last[..., None, :] - gc)
    else:
        rows = jnp.arange(c)
        lower = rows[:, None] >= rows[None, :]
        strict = rows[:, None] > rows[None, :]
        # exp only where it is used: above the diagonal the exponent
        # is >= 0
        decay = jnp.exp(jnp.where(
            lower, gc[..., :, None] - gc[..., None, :], -jnp.inf))
        kk = jnp.where(strict, jnp.einsum(
            "bhnid,bhnjd->bhnij", k_beta, k, precision=_HI) * decay, 0.0)
        qk = jnp.einsum("bhnid,bhnjd->bhnij", q, k, precision=_HI) * decay
        from_start = jnp.exp(gc)[..., None]
        last = gc[..., -1]  # (B, H, N)
        to_end = jnp.exp(last[..., None] - gc)[..., None]
    solve = _unit_lower_inverse(kk)
    value = jnp.matmul(solve, v_beta, precision=_HI)
    k_cum = jnp.matmul(solve, k_beta * from_start, precision=_HI)
    q_in = q * from_start
    k_out = k * to_end

    def step(s, xs):
        value_i, k_cum_i, qk_i, q_in_i, k_out_i, last_i = xs
        v_new = value_i - jnp.matmul(k_cum_i, s, precision=_HI)
        o = jnp.matmul(q_in_i, s, precision=_HI) \
            + jnp.matmul(qk_i, v_new, precision=_HI)
        # one factor a head, or one a key channel (a row of the state)
        keep = jnp.exp(last_i)[(...,) + (None,) * (s.ndim - last_i.ndim)]
        s = s * keep + jnp.einsum(
            "bhck,bhcv->bhkv", k_out_i, v_new, precision=_HI)
        return s, o

    xs = tuple(jnp.moveaxis(x, 2, 0)
               for x in (value, k_cum, qk, q_in, k_out, last))
    state, o = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(o, 0, 2).reshape(b, h, length, dv), state


def _l2norm(x, eps: float = 1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


@part("mixer.conv")
def _causal_conv(mixed, conv, conv_w, n_valid, bias=None):
    """SiLU of the causal depthwise convolution of `mixed` (B, C,
    channels) over [carried K-1 inputs | chunk], and the K-1 inputs to
    carry on: those before column n_valid, so the old state where
    n_valid is 0, untouched by the padding columns. `bias` (channels,)
    is added before the SiLU (the state-space mixer's,
    layers/mamba2.py; the delta nets have none)."""
    c, taps = mixed.shape[1], conv_w.shape[0]
    seq = jnp.concatenate([conv.astype(mixed.dtype), mixed], axis=1)
    w = conv_w.astype(jnp.float32)
    acc = sum(seq[:, j:j + c].astype(jnp.float32) * w[j]
              for j in range(taps))
    if bias is not None:
        acc = acc + bias.astype(jnp.float32)
    conv = jax.vmap(lambda s, n: jax.lax.dynamic_slice_in_dim(
        s, n, taps - 1, axis=0))(seq, n_valid).astype(conv.dtype)
    return jax.nn.silu(acc).astype(mixed.dtype), conv


def gated_delta_net_fwd(x, p: GDNParams, spec: GDNSpec, rec, conv,
                        n_valid, fresh, eps: float = 1e-6):
    """x (B, C, H); rec (B, Hv, dk, dv) float32; conv (B, K-1,
    channels); n_valid (B,) real columns of each row; fresh (B,) bool,
    rows that start from zero state. Returns (y (B, C, H), rec, conv)."""
    b, c, _ = x.shape
    hk, hv, dk, dv = (spec.num_k_heads, spec.num_v_heads, spec.k_dim,
                      spec.v_dim)
    ch = spec.channels
    with part("mixer.rule"):  # a fresh slot's state
        valid = jnp.arange(c)[None, :] < n_valid[:, None]  # (B, C)
        rec = jnp.where(fresh[:, None, None, None], 0.0, rec)
    with part("mixer.conv"):
        conv = jnp.where(fresh[:, None, None], jnp.zeros((), conv.dtype),
                         conv)

    with part("mixer.proj"):
        qkvz = jnp.dot(x, p.w_qkvz,
                       preferred_element_type=jnp.float32).astype(x.dtype)
        ba = jnp.dot(x, p.w_ba, preferred_element_type=jnp.float32)
        mixed, z = qkvz[..., :ch], qkvz[..., ch:]

    mixed, conv = _causal_conv(mixed, conv, p.conv_w, n_valid)

    f32 = jnp.float32

    def heads_first(t):
        return jnp.moveaxis(t, 2, 1)

    with part("mixer.rule"):
        q = mixed[..., :hk * dk].reshape(b, c, hk, dk).astype(f32)
        k = mixed[..., hk * dk:2 * hk * dk].reshape(
            b, c, hk, dk).astype(f32)
        v = mixed[..., 2 * hk * dk:].reshape(b, c, hv, dv).astype(f32)
        beta = jnp.where(valid[..., None], jax.nn.sigmoid(ba[..., :hv]),
                         0.0)
        g = -jnp.exp(p.a_log.astype(f32)) * jax.nn.softplus(
            ba[..., hv:] + p.dt_bias.astype(f32))
        g = jnp.where(valid[..., None], g, 0.0)
        rep = hv // hk
        q = jnp.repeat(_l2norm(q) * dk ** -0.5, rep, axis=2)
        k = jnp.repeat(_l2norm(k), rep, axis=2)
        o, rec = chunk_gated_delta_rule(
            heads_first(q), heads_first(k), heads_first(v),
            heads_first(g), heads_first(beta), rec, sub_chunk(c))
        o = jnp.moveaxis(o, 1, 2)  # (B, C, Hv, dv)
    with part("mixer.proj"):
        zf = z.reshape(b, c, hv, dv).astype(f32)
        o = rms_norm(o, p.norm, eps) * jax.nn.silu(zf)
        y = jnp.dot(o.reshape(b, c, hv * dv).astype(x.dtype), p.w_out,
                    preferred_element_type=jnp.float32).astype(x.dtype)
    return y, rec, conv


class KDAParams(NamedTuple):
    w_qkv: jax.Array
    w_fgb: jax.Array
    w_fb: jax.Array
    w_gb: jax.Array
    conv_w: jax.Array
    a_log: jax.Array
    dt_bias: jax.Array
    norm: jax.Array
    w_out: jax.Array


def kda_fwd(x, p: KDAParams, spec: GDNSpec, rec, conv, n_valid, fresh,
            eps: float = 1e-6):
    """The channel-gated mixer; arguments and results as
    `gated_delta_net_fwd`'s, `spec` with as many key as value heads."""
    b, c, _ = x.shape
    hv, dk, dv = spec.num_v_heads, spec.k_dim, spec.v_dim
    assert spec.num_k_heads == hv
    rank = p.w_fb.shape[0]
    with part("mixer.rule"):  # a fresh slot's state
        valid = jnp.arange(c)[None, :] < n_valid[:, None]  # (B, C)
        rec = jnp.where(fresh[:, None, None, None], 0.0, rec)
    with part("mixer.conv"):
        conv = jnp.where(fresh[:, None, None], jnp.zeros((), conv.dtype),
                         conv)

    f32 = jnp.float32
    with part("mixer.proj"):
        mixed = jnp.dot(x, p.w_qkv,
                        preferred_element_type=f32).astype(x.dtype)
        fgb = jnp.dot(x, p.w_fgb, preferred_element_type=f32)
        f_a = fgb[..., :rank].astype(x.dtype)
        g_a = fgb[..., rank:2 * rank].astype(x.dtype)
    mixed, conv = _causal_conv(mixed, conv, p.conv_w, n_valid)

    def heads_first(t):
        return jnp.moveaxis(t, 2, 1)

    with part("mixer.rule"):
        q = mixed[..., :hv * dk].reshape(b, c, hv, dk).astype(f32)
        k = mixed[..., hv * dk:2 * hv * dk].reshape(
            b, c, hv, dk).astype(f32)
        v = mixed[..., 2 * hv * dk:].reshape(b, c, hv, dv).astype(f32)
        beta = jnp.where(valid[..., None],
                         jax.nn.sigmoid(fgb[..., 2 * rank:]), 0.0)
        with part("mixer.proj"):  # the gate's second, low-rank factor
            g = jnp.dot(f_a, p.w_fb, preferred_element_type=f32)
        g = jax.nn.softplus(g + p.dt_bias.astype(f32)).reshape(
            b, c, hv, dk)
        g = -jnp.exp(p.a_log.astype(f32))[:, None] * g
        g = jnp.where(valid[..., None, None], g, 0.0)
        o, rec = chunk_gated_delta_rule(
            heads_first(_l2norm(q) * dk ** -0.5), heads_first(_l2norm(k)),
            heads_first(v), heads_first(g), heads_first(beta), rec,
            sub_chunk(c))
        o = jnp.moveaxis(o, 1, 2)  # (B, C, Hv, dv)
    with part("mixer.proj"):
        gate = jnp.dot(g_a, p.w_gb, preferred_element_type=f32)
        o = rms_norm(o, p.norm, eps) * jax.nn.sigmoid(
            gate.reshape(b, c, hv, dv))
        y = jnp.dot(o.reshape(b, c, hv * dv).astype(x.dtype), p.w_out,
                    preferred_element_type=f32).astype(x.dtype)
    return y, rec, conv
