"""Qwen3-Next — a hybrid decoder served through the fixed-geometry step.

Block i is `x += Mixer_i(norm(x)); x += MoE(norm(x))`. The mixer is
gated full attention where (i + 1) % full_attention_interval == 0 and
a gated delta net otherwise (layers/gated_attn.py,
layers/gated_delta_net.py); every block's expert layer routes over
all experts and computes the ones this chip holds, plus the shared
expert (layers/held_moe.py). Every norm but the delta net's gated one
has the gain (1 + w).

The layer pattern is data: parameters are stacked by kind, and the
forward is ONE `lax.scan` over periods of `full_attention_interval`
blocks, the period's blocks unrolled inside it.

  embed (V, H) · final_ln (H,) · lm_head (H, V)
  every block, (L, ...):   input_ln, post_ln, w_router, w_gate_up,
                           w_down, ws_gate_up, ws_down, w_sgate
  delta-net blocks, (Ll, ...): w_qkvz, w_ba, conv_w, a_log, dt_bias,
                           gdn_norm, w_out
  attention blocks, (Lf, ...): w_q, w_kv, q_norm, k_norm, w_o

What a slot carries between steps (`Cache`): pages of keys and values
for the attention blocks, in the pool's layout, and for each delta-net
block a recurrent state and the convolution's last inputs.

This family runs on ONE chip of an expert-parallel group: attention
and the delta net for the chip's own requests, the experts it holds.
There is no exchange and no code in place of the absent chips; a mesh
of more than one device is refused.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from triton_dist_tpu.layers.gated_attn import (
    GatedAttnParams,
    GatedAttnSpec,
    gated_attn_fwd,
)
from triton_dist_tpu.layers.gated_delta_net import (
    GDNParams,
    GDNSpec,
    gated_delta_net_fwd,
)
from triton_dist_tpu.layers.held_moe import HeldMoEParams, held_moe_fwd
from triton_dist_tpu.layers.norm import rms_norm
from triton_dist_tpu.layers.rope import rope_table
from triton_dist_tpu.models.config import ModelConfig
from triton_dist_tpu.models.dense import _INIT_SCALE, _draw
from triton_dist_tpu.models.kv_cache import KVCache


class Cache(NamedTuple):
    """The serve step's cache pytree for this family."""

    k: jax.Array  # (Lf, P, page, Hkv, D)
    v: jax.Array
    rec: jax.Array  # (Ll, slots, Hv, dk, dv) float32
    conv: jax.Array  # (Ll, slots, K - 1, channels)


def gdn_spec(cfg: ModelConfig) -> GDNSpec:
    return GDNSpec(cfg.linear_num_key_heads, cfg.linear_num_value_heads,
                   cfg.linear_key_head_dim, cfg.linear_value_head_dim,
                   cfg.linear_conv_kernel_dim)


def attn_spec(cfg: ModelConfig) -> GatedAttnSpec:
    return GatedAttnSpec(cfg.num_q_heads, cfg.num_kv_heads, cfg.head_dim,
                         int(cfg.head_dim * cfg.partial_rotary_factor))


def check(cfg: ModelConfig, n_devices: int) -> None:
    if n_devices != 1:
        raise NotImplementedError(
            f"the hybrid family runs one chip of an expert-parallel group "
            f"(got a tp axis of {n_devices}): the mixers have no "
            "tensor-parallel form and the expert layer no exchange")
    period = cfg.full_attention_interval
    assert cfg.num_layers % period == 0, (
        f"{cfg.num_layers} layers are not whole periods of {period}")
    assert cfg.expert_offset + cfg.num_experts_held <= cfg.num_experts
    assert cfg.linear_num_value_heads % cfg.linear_num_key_heads == 0
    assert not cfg.tie_word_embeddings


# (name, shape, init) in the order that fixes each leaf's key,
# fold_in(PRNGKey(seed), position): "normal" is N(0, _INIT_SCALE); a
# gain starts at its identity, "zeros" for (1 + w) and "ones" for w
def leaves(cfg: ModelConfig):
    L, h, v = cfg.num_layers, cfg.hidden_size, cfg.vocab_size
    lf = cfg.num_kv_layers
    ll = L - lf
    g, a = gdn_spec(cfg), attn_spec(cfg)
    e, eh = cfg.num_experts, cfg.num_experts_held
    i, ish = cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
    hq, hkv, d = a.num_q_heads, a.num_kv_heads, a.head_dim
    vw = g.num_v_heads * g.v_dim
    return (
        ("embed", (v, h), "normal"),
        ("final_ln", (h,), "zeros"),
        ("lm_head", (h, v), "normal"),
        ("input_ln", (L, h), "zeros"),
        ("post_ln", (L, h), "zeros"),
        ("w_router", (L, h, e), "normal"),
        ("w_gate_up", (L, eh, h, 2 * i), "normal"),
        ("w_down", (L, eh, i, h), "normal"),
        ("ws_gate_up", (L, h, 2 * ish), "normal"),
        ("ws_down", (L, ish, h), "normal"),
        ("w_sgate", (L, h), "normal"),
        ("w_qkvz", (ll, h, g.channels + vw), "normal"),
        ("w_ba", (ll, h, 2 * g.num_v_heads), "normal"),
        ("conv_w", (ll, g.conv, g.channels), "normal"),
        ("a_log", (ll, g.num_v_heads), "normal"),
        ("dt_bias", (ll, g.num_v_heads), "normal"),
        ("gdn_norm", (ll, g.v_dim), "ones"),
        ("w_out", (ll, vw, h), "normal"),
        ("w_q", (lf, h, hq * 2 * d), "normal"),
        ("w_kv", (lf, h, 2 * hkv * d), "normal"),
        ("q_norm", (lf, d), "zeros"),
        ("k_norm", (lf, d), "zeros"),
        ("w_o", (lf, hq * d, h), "normal"),
    )


def init_params(cfg: ModelConfig, mesh, seed: int = 0,
                fast: bool = False) -> dict:
    """Random parameters on the mesh's one device. fast=True draws on
    the device (each leaf under its own folded key, in slabs, as
    `models.dense._draw` does); fast=False from one host numpy stream
    in the order of `leaves`."""
    dt = jnp.dtype(cfg.dtype)
    where = NamedSharding(mesh, P())
    spec = leaves(cfg)
    const = {"zeros": jnp.zeros, "ones": jnp.ones}
    if fast:
        def draw(key):
            return {name: const[init](shape, dt) if init != "normal"
                    else _draw(jax.random.fold_in(key, i), shape, dt)
                    for i, (name, shape, init) in enumerate(spec)}

        return jax.jit(draw, out_shardings=where)(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    host = {"zeros": np.zeros, "ones": np.ones}
    return {name: jax.device_put(
        (host[init](shape, np.float32) if init != "normal" else np.asarray(
            rng.standard_normal(shape) * _INIT_SCALE, np.float32)
         ).astype(dt), where) for name, shape, init in spec}


_BLOCK = ("input_ln", "post_ln", "w_router", "ws_gate_up", "ws_down",
          "w_sgate")
_GDN = ("w_qkvz", "w_ba", "conv_w", "a_log", "dt_bias", "gdn_norm", "w_out")
_ATTN = ("w_q", "w_kv", "q_norm", "k_norm", "w_o")


def _by_period(params: dict, names, per: int):
    """The stacked leaves of one kind as (periods, per, ...)."""
    return {n: params[n].reshape((-1, per) + params[n].shape[1:])
            for n in names}


def forward_chunk(cfg: ModelConfig, params: dict, tokens, cache: Cache,
                  table, lengths, n_valid, attn_impl: str):
    """One (slots, chunk) block through the model. Slot s holds
    `lengths[s]` cached positions and `n_valid[s]` real columns.
    Returns (logits (K, C, V) float32, (k, v): the chunk's new rows of
    the attention blocks (Lf, K, C, Hkv, D), rec, conv,
    {counter: () int32})."""
    period = cfg.full_attention_interval
    slots, chunk = tokens.shape
    g, a = gdn_spec(cfg), attn_spec(cfg)
    eps = cfg.rms_eps
    cos, sin = rope_table(a.rotary_dim, cfg.max_positions, cfg.rope_theta)
    positions = lengths[:, None] + jnp.arange(chunk)[None, :]
    kv_len = lengths + chunk
    valid = (jnp.arange(chunk)[None, :] < n_valid[:, None]).reshape(-1)
    fresh = lengths == 0
    pages = KVCache(cache.k, cache.v, lengths, table)

    def normed(x, gain):
        return rms_norm(x, gain, eps, zero_centred=True)

    def moe(x, blk, j, layer):
        # the experts' stacks whole, this block's by `layer`: a
        # per-period slice of them would be copied every step
        p = HeldMoEParams(blk["w_router"][j], params["w_gate_up"],
                          params["w_down"], blk["ws_gate_up"][j],
                          blk["ws_down"][j], blk["w_sgate"][j])
        y, here, absent = held_moe_fwd(
            normed(x, blk["post_ln"][j]).reshape(slots * chunk, -1),
            valid, p, cfg.num_experts_per_tok, cfg.expert_offset,
            layer=layer)
        return x + y.reshape(x.shape), here, absent

    def one_period(x, xs):
        i, blk, lin, att, rec, conv = xs
        here = absent = jnp.int32(0)
        recs, convs = [], []
        for j in range(period - 1):
            hid = normed(x, blk["input_ln"][j])
            y, r, c = gated_delta_net_fwd(
                hid, GDNParams(*(lin[n][j] for n in _GDN)), g, rec[j],
                conv[j], n_valid, fresh, eps)
            recs.append(r)
            convs.append(c)
            x, h_j, a_j = moe(x + y, blk, j, i * period + j)
            here, absent = here + h_j, absent + a_j
        j = period - 1
        hid = normed(x, blk["input_ln"][j])
        y, rows = gated_attn_fwd(
            hid, GatedAttnParams(*(att[n] for n in _ATTN)), a, cos, sin,
            positions, pages.layer_view(i), kv_len, attn_impl, eps)
        x, h_j, a_j = moe(x + y, blk, j, i * period + j)
        return x, (jnp.stack(recs), jnp.stack(convs), rows[0], rows[1],
                   here + h_j, absent + a_j)

    per = period - 1
    x = params["embed"][tokens]
    xs = (jnp.arange(cfg.num_layers // period),
          _by_period(params, _BLOCK, period), _by_period(params, _GDN, per),
          {n: params[n] for n in _ATTN},
          cache.rec.reshape((-1, per) + cache.rec.shape[1:]),
          cache.conv.reshape((-1, per) + cache.conv.shape[1:]))
    x, (rec, conv, k_rows, v_rows, here, absent) = jax.lax.scan(
        one_period, x, xs)
    x = normed(x, params["final_ln"])
    logits = jnp.einsum("bsh,hv->bsv", x, params["lm_head"],
                        preferred_element_type=jnp.float32)
    stats = {"moe_pairs_here": jnp.sum(here),
             "moe_pairs_absent": jnp.sum(absent)}
    return (logits, (k_rows, v_rows), rec.reshape(cache.rec.shape),
            conv.reshape(cache.conv.shape), stats)


def state_shapes(cfg: ModelConfig, slots: int):
    """Shapes of the per-slot state beside the pages: (rec, conv)."""
    g = gdn_spec(cfg)
    ll = cfg.num_layers - cfg.num_kv_layers
    return ((ll, slots, g.num_v_heads, g.k_dim, g.v_dim),
            (ll, slots, g.conv - 1, g.channels))
