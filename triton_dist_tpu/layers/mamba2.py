"""Mamba-2 — the hybrid family's state-space mixer (a selective scan,
not a delta rule), in the chunked (SSD, matmul) form over a carried
per-slot state.

Per head h of P channels, with state S (P, N) in float32 and ONE group,
so that B_t, C_t in R^N are shared by the heads:

    z | xBC | dt = h W_in                  (d_inner | d_inner + 2 N | heads)
    xBC = silu(conv1d_causal(xBC; w (K, d_inner + 2 N), bias))
    x | B | C = xBC                        (d_inner | N | N)
    dt = softplus(dt + dt_bias);  a = -exp(A_log)          (a head each)
    S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t
    out = rmsnorm(y * silu(z); gain (d_inner,)) W_out

The gate comes first and the norm runs over all d_inner channels (one
group). No bias on the projections; the convolution has one.

Chunked, for a slot's C columns with carried S_0. The decay is one
number a head and column, and every exponent below is at or below 0,
so there is no solve and no sub-chunk (the delta nets have both):

    c_t = cumsum(dt_t a)
    G = C B^T                                    (C, C) once a slot
    y = ((G * exp(c_i - c_j))_{i >= j}) (dt * x) + exp(c_i) C_i S_0 + D x
    S_end = exp(c_last) S_0 + sum_j exp(c_last - c_j) dt_j x_j B_j^T

A serve step hands this layer a fixed (slots, chunk) block in which
slot s has `n_valid[s]` real columns. A column at or past n_valid has
dt = 0 and feeds nothing to the convolution tail, so it leaves both
states bit for bit as they were; a slot whose length is 0 starts from
zero state (the delta nets' rule, layers/gated_delta_net.py). What is
carried between steps is S and the last K - 1 convolution inputs (the
projection's x | B | C channels). The source's `mamba_chunk_size` is
its own blocking of the same function; the block here is the serve
step's chunk.

Column layout of the weights (the builder's; the benchmark's reference
draws the same):
  w_in   (H, 2 d_inner + 2 N + heads)   z | x | B | C | dt, x and z
                                        head-major
  conv_w (K, d_inner + 2 N)             tap j multiplies the input
                                        K-1-j back; conv_b beside it
  a_log, dt_bias, d (heads,) · norm (d_inner,) · w_out (d_inner, H)
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from triton_dist_tpu.layers.gated_delta_net import _HI, _causal_conv
from triton_dist_tpu.layers.norm import rms_norm
from triton_dist_tpu.layers.parts import part


class Mamba2Spec(NamedTuple):
    num_heads: int
    head_dim: int  # P, channels a head
    state: int  # N
    conv: int  # convolution width K

    @property
    def inner(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def channels(self) -> int:
        """The convolved channels: x | B | C."""
        return self.inner + 2 * self.state


class Mamba2Params(NamedTuple):
    w_in: jax.Array
    conv_w: jax.Array
    conv_b: jax.Array
    a_log: jax.Array
    dt_bias: jax.Array
    d: jax.Array
    norm: jax.Array
    w_out: jax.Array


def chunk_selective_scan(x, dt, a, b_in, c_in, state):
    """The recurrence over C columns in matmul form. x (B, C, Hh, P),
    dt (B, C, Hh) (0 at a padding column), a (Hh,) negative, b_in and
    c_in (B, C, N), state (B, Hh, P, N), all float32. Returns
    (y (B, C, Hh, P) without the D x term, the state after the last
    column)."""
    c = x.shape[1]
    gc = jnp.cumsum(dt * a, axis=1)  # (B, C, Hh), only falls
    gch = jnp.moveaxis(gc, 2, 1)  # (B, Hh, C)
    rows = jnp.arange(c)
    lower = rows[:, None] >= rows[None, :]
    # exp only where it is used: above the diagonal the exponent is >= 0
    decay = jnp.exp(jnp.where(
        lower, gch[..., :, None] - gch[..., None, :], -jnp.inf))
    pairs = jnp.einsum("bin,bjn->bij", c_in, b_in, precision=_HI)
    x_dt = x * dt[..., None]
    y = jnp.einsum("bhij,bjhp->bihp", pairs[:, None] * decay, x_dt,
                   precision=_HI)
    y = y + jnp.exp(gc)[..., None] * jnp.einsum(
        "bin,bhpn->bihp", c_in, state, precision=_HI)
    last = gc[:, -1]  # (B, Hh)
    to_end = jnp.exp(last[:, None] - gc)  # (B, C, Hh)
    state = state * jnp.exp(last)[..., None, None] + jnp.einsum(
        "bjhp,bjn->bhpn", x_dt * to_end[..., None], b_in, precision=_HI)
    return y, state


def mamba2_fwd(hid, p: Mamba2Params, spec: Mamba2Spec, rec, conv, n_valid,
               fresh, eps: float = 1e-5):
    """hid (B, C, H); rec (B, heads, P, N) float32; conv (B, K-1,
    channels); n_valid (B,) real columns of each row; fresh (B,) bool,
    rows that start from zero state. Returns (y (B, C, H), rec, conv):
    the delta nets' signature (layers/gated_delta_net.py)."""
    b, c, _ = hid.shape
    hh, pd, n = spec.num_heads, spec.head_dim, spec.state
    di, ch = spec.inner, spec.channels
    f32 = jnp.float32
    with part("mixer.rule"):  # a fresh slot's state
        valid = jnp.arange(c)[None, :] < n_valid[:, None]  # (B, C)
        rec = jnp.where(fresh[:, None, None, None], 0.0, rec)
    with part("mixer.conv"):
        conv = jnp.where(fresh[:, None, None], jnp.zeros((), conv.dtype),
                         conv)

    with part("mixer.proj"):
        proj = jnp.dot(hid, p.w_in, preferred_element_type=f32)
        z = proj[..., :di].astype(hid.dtype)
        mixed = proj[..., di:di + ch].astype(hid.dtype)
        dt = proj[..., di + ch:]  # (B, C, heads) float32

    mixed, conv = _causal_conv(mixed, conv, p.conv_w, n_valid,
                               bias=p.conv_b)

    with part("mixer.rule"):
        x = mixed[..., :di].reshape(b, c, hh, pd).astype(f32)
        b_in = mixed[..., di:di + n].astype(f32)
        c_in = mixed[..., di + n:].astype(f32)
        dt = jnp.where(valid[..., None],
                       jax.nn.softplus(dt + p.dt_bias.astype(f32)), 0.0)
        y, rec = chunk_selective_scan(
            x, dt, -jnp.exp(p.a_log.astype(f32)), b_in, c_in, rec)
        y = y + p.d.astype(f32)[:, None] * x
    with part("mixer.proj"):
        y = y.reshape(b, c, di) * jax.nn.silu(z.astype(f32))
        y = rms_norm(y, p.norm, eps)
        out = jnp.dot(y.astype(hid.dtype), p.w_out,
                      preferred_element_type=f32).astype(hid.dtype)
    return out, rec, conv
