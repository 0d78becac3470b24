"""The hybrid family — decoders whose blocks are DATA, served through
the fixed-geometry step.

Block i is `x += Mixer_i(norm(x)); x += FFN_i(norm(x))`, and what the
mixer and the FFN are is read off the configuration
(`ModelConfig.mixer_kinds`, `ffn_kinds`):

  mixer  "gdn"         scalar-gated delta net    layers/gated_delta_net.py
         "kda"         channel-gated delta net   layers/gated_delta_net.py
         "gated_attn"  gated full attention      layers/gated_attn.py
         "mla"         latent attention, no rotary  layers/latent_attn.py
         "window_attn" grouped-query attention over the last
                       `sliding_window` positions, rotary
         "global_attn" the same heads over every position, no
                       rotary                    layers/gqa_attn.py
         "mamba2"      state-space (selective scan)  layers/mamba2.py
  FFN    "moe"         a router over all experts, the ones this chip
                       holds, a shared expert    layers/held_moe.py
         "dense"       a SwiGLU MLP of `intermediate_size`

Four published members: Qwen3-Next (periods of three "gdn" blocks and
one "gated_attn", every FFN "moe", norms with the gain (1 + w)),
Kimi-Linear ("kda" and "mla" from two lists, the last period short,
the first block's FFN "dense", norms with the gain w, a sigmoid
router) and K-EXAONE ("window_attn" and "global_attn" from the
source's `layer_types`, three to one, no delta-net block at all; the
FFNs and the router Kimi-Linear's) and Granite 4.0-H ("mamba2" and
"global_attn" from the source's `layer_types`, nine to one, the
attention without q/k norm at the source's own scale; the embedding is
the head (`tie_word_embeddings`: no `lm_head` leaf) and four
multipliers scale the embedding, each residual branch, the attention
scores and the logits). Granite 4.0-H comes with experts and without:
h-micro has EVERY FFN "dense", so no expert leaf and no router, over
heads of 64 that a page keeps 128 wide; h-small has EVERY FFN "moe",
a softmax router and an ungated shared MLP under the one residual
multiplier, over heads of 128.

The pattern is cut into PERIODS, each ending with a block that keeps
pages (the last one may have none), and a run of equal periods is ONE
`lax.scan` with the period's blocks unrolled inside it: one scan for
Qwen3-Next, three for Kimi-Linear, two for K-EXAONE (the first period
holds the dense block), three for Granite 4.0-H (`M x5 A`, `M x9 A`
three times, `M x4`; two for h-small's first ten blocks, `M x5 A`,
`M x4`). Parameters are stacked by kind:

  embed (V, H) · final_ln (H,) · lm_head (H, V) unless tied
  every block, (L, ...):      input_ln, post_ln
  "moe" blocks, (Lm, ...):    w_router, [router_bias,] w_gate_up,
                              w_down, ws_gate_up, ws_down[, w_sgate]
  "dense" blocks, (Ld, ...):  wd_gate_up, wd_down
  a mixer kind's blocks:      `_MIXER_LEAVES` below ("window_attn" and
                              "global_attn" share ONE set, stacked
                              in the blocks' order)

What a slot carries between steps (`Cache`): pages for the blocks that
attend every position, in the pool's layout (keys and values, or one
latent row a token); for each delta-net or state-space block a
recurrent state and the convolution's last inputs (one kind of the
three a pattern: they share `rec` / `conv`); for each window block a
TAIL, the keys
and values of the slot's last `sliding_window` positions, which is all
it ever reads of the past. A kind the pattern lacks carries nothing:
no array stands in its place.

No stack is cut: a scan's body takes each block's row of every stacked
leaf, and of the state, by index (`lax.dynamic_index_in_dim`), so the
weights are read where they are. (Handing the scan a period's share of
a stack copies it every step: 0.85 GB of one leaf alone at
Kimi-Linear's widths; PERF.md, PR 35.)

This family runs on ONE chip of an expert-parallel group: the mixers
for the chip's own requests, the experts it holds. There is no
exchange and no code in place of the absent chips; a mesh of more than
one device is refused.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from triton_dist_tpu.layers.gated_attn import (
    GatedAttnParams,
    GatedAttnSpec,
    gated_attn_fwd,
)
from triton_dist_tpu.layers.gqa_attn import (
    GQAttnParams,
    GQAttnSpec,
    global_attn_fwd,
    window_attn_fwd,
)
from triton_dist_tpu.layers.gated_delta_net import (
    GDNParams,
    GDNSpec,
    KDAParams,
    gated_delta_net_fwd,
    kda_fwd,
)
from triton_dist_tpu.layers.held_moe import (
    HeldMoEParams,
    RouterForm,
    held_moe_counted,
    swiglu_fwd,
)
from triton_dist_tpu.layers.latent_attn import (
    LatentAttnParams,
    LatentAttnSpec,
    latent_attn_fwd,
)
from triton_dist_tpu.layers.mamba2 import (
    Mamba2Params,
    Mamba2Spec,
    mamba2_fwd,
)
from triton_dist_tpu.layers.norm import rms_norm
from triton_dist_tpu.layers.parts import part
from triton_dist_tpu.layers.rope import rope_table
from triton_dist_tpu.models.config import ModelConfig
from triton_dist_tpu.models.dense import _INIT_SCALE, _draw
from triton_dist_tpu.models.kv_cache import KVCache

# keep per-slot state beside the pages (one of them a pattern)
STATE_MIXERS = ("gdn", "kda", "mamba2")
PAGE_MIXERS = ("gated_attn", "mla", "global_attn")  # keep pages
WINDOW_MIXERS = ("window_attn",)  # keep a per-slot tail, no pages
GQA_MIXERS = ("window_attn", "global_attn")  # share their leaves


class Cache(NamedTuple):
    """The serve step's cache for this family (`KVPool.state`, named).
    `KVPool.state` is the flat tuple `flat()` returns, with nothing in
    the place of what the pattern has no block for."""

    pages: tuple  # (k, v) each (Lf, P, page, Hkv, D), or one latent pool
    rec: Optional[jax.Array]  # (Ll, slots, Hv, dk, dv) float32
    conv: Optional[jax.Array]  # (Ll, slots, K - 1, channels)
    win: tuple = ()  # (k, v) each (Lw, slots, window, Hkv, D)

    @staticmethod
    def of(cfg: ModelConfig, flat) -> "Cache":
        n = len(cfg.page_arrays)
        pages, rest = tuple(flat[:n]), tuple(flat[n:])
        rec = conv = None
        if any(k in STATE_MIXERS for k in cfg.mixer_kinds):
            (rec, conv), rest = rest[:2], rest[2:]
        return Cache(pages, rec, conv, rest)

    def flat(self) -> tuple:
        return (self.pages
                + (() if self.rec is None else (self.rec, self.conv))
                + self.win)


def gdn_spec(cfg: ModelConfig) -> GDNSpec:
    return GDNSpec(cfg.linear_num_key_heads, cfg.linear_num_value_heads,
                   cfg.linear_key_head_dim, cfg.linear_value_head_dim,
                   cfg.linear_conv_kernel_dim)


def attn_spec(cfg: ModelConfig) -> GatedAttnSpec:
    return GatedAttnSpec(cfg.num_q_heads, cfg.num_kv_heads, cfg.head_dim,
                         int(cfg.head_dim * cfg.partial_rotary_factor))


def gqa_spec(cfg: ModelConfig) -> GQAttnSpec:
    return GQAttnSpec(cfg.num_q_heads, cfg.num_kv_heads, cfg.head_dim,
                      cfg.use_qk_norm, cfg.attention_multiplier or None,
                      cfg.page_head_dim)


def mamba_spec(cfg: ModelConfig) -> Mamba2Spec:
    return Mamba2Spec(cfg.mamba_num_heads, cfg.mamba_head_dim,
                      cfg.mamba_state_dim, cfg.mamba_conv_kernel_dim)


def latent_spec(cfg: ModelConfig) -> LatentAttnSpec:
    return LatentAttnSpec(cfg.num_q_heads, cfg.kv_lora_rank,
                          cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)


def segments(cfg: ModelConfig):
    """The layer pattern as [(period, repeats)]: a period is the
    ((mixer, ffn), ...) of consecutive blocks up to and with an
    attention block; equal periods in a row are one entry."""
    out, period = [], []
    blocks = list(zip(cfg.mixer_kinds, cfg.ffn_kinds))
    for at, block in enumerate(blocks):
        period.append(block)
        if block[0] in PAGE_MIXERS or at == len(blocks) - 1:
            if out and out[-1][0] == tuple(period):
                out[-1][1] += 1
            else:
                out.append([tuple(period), 1])
            period = []
    return [(p, n) for p, n in out]


def check(cfg: ModelConfig, n_devices: int) -> None:
    if n_devices != 1:
        raise NotImplementedError(
            f"the hybrid family runs one chip of an expert-parallel group "
            f"(got a tp axis of {n_devices}): the mixers have no "
            "tensor-parallel form and the expert layer no exchange")
    assert cfg.expert_offset + cfg.num_experts_held <= cfg.num_experts
    assert cfg.router_score in ("softmax", "sigmoid")
    assert 0 <= cfg.first_k_dense <= cfg.num_layers
    kinds = set(cfg.mixer_kinds)  # the lists name every block once
    # `rec` / `conv` are one kind's: the pool stacks them by block
    assert len(kinds & set(STATE_MIXERS)) <= 1
    if kinds & {"gdn", "kda"}:
        assert cfg.linear_num_value_heads % cfg.linear_num_key_heads == 0
    if "mamba2" in kinds:
        assert min(mamba_spec(cfg)) > 0
    if "kda" in kinds:
        assert cfg.linear_num_key_heads == cfg.linear_num_value_heads
        assert cfg.linear_gate_rank > 0
    if "mla" in kinds:
        assert cfg.kv_lora_rank and cfg.v_head_dim <= cfg.kv_lora_rank


# (name, shape, init) in the order that fixes each leaf's key,
# fold_in(PRNGKey(seed), position): "normal" is N(0, _INIT_SCALE),
# "embed" the same over `embedding_multiplier` (`leaves`);
# a block's "gain" starts at its identity, 0 under (1 + w) and 1 under
# w (`norm_zero_centred`); a mixer's own norms are its kind's: (1 + w)
# in gated attention, w in the delta nets, the latent and the
# state-space mixer, whose "a_log", "dt_bias" and "conv" follow
# Mamba-2's published initialisation (`_mamba_init`)
def _mixer_leaves(cfg: ModelConfig, kind: str, n: int):
    h = cfg.hidden_size
    g = gdn_spec(cfg)
    vw = g.num_v_heads * g.v_dim
    if kind == "gdn":
        return (
            ("w_qkvz", (n, h, g.channels + vw), "normal"),
            ("w_ba", (n, h, 2 * g.num_v_heads), "normal"),
            ("conv_w", (n, g.conv, g.channels), "normal"),
            ("a_log", (n, g.num_v_heads), "normal"),
            ("dt_bias", (n, g.num_v_heads), "normal"),
            ("gdn_norm", (n, g.v_dim), "ones"),
            ("w_out", (n, vw, h), "normal"),
        )
    if kind == "kda":
        r = cfg.linear_gate_rank
        return (
            ("kda_w_qkv", (n, h, g.channels), "normal"),
            ("kda_w_fgb", (n, h, 2 * r + g.num_v_heads), "normal"),
            ("kda_w_fb", (n, r, g.num_k_heads * g.k_dim), "normal"),
            ("kda_w_gb", (n, r, vw), "normal"),
            ("kda_conv_w", (n, g.conv, g.channels), "normal"),
            ("kda_a_log", (n, g.num_v_heads), "normal"),
            ("kda_dt_bias", (n, g.num_k_heads * g.k_dim), "normal"),
            ("kda_norm", (n, g.v_dim), "ones"),
            ("kda_w_out", (n, vw, h), "normal"),
        )
    if kind == "gated_attn":
        a = attn_spec(cfg)
        hq, hkv, d = a.num_q_heads, a.num_kv_heads, a.head_dim
        return (
            ("w_q", (n, h, hq * 2 * d), "normal"),
            ("w_kv", (n, h, 2 * hkv * d), "normal"),
            ("q_norm", (n, d), "zeros"),
            ("k_norm", (n, d), "zeros"),
            ("w_o", (n, hq * d, h), "normal"),
        )
    if kind == "gqa":
        hq, hkv, d = gqa_spec(cfg)[:3]
        norms = (("attn_q_norm", (n, d), "ones"),
                 ("attn_k_norm", (n, d), "ones")) if cfg.use_qk_norm else ()
        return (
            ("attn_w_q", (n, h, hq * d), "normal"),
            ("attn_w_kv", (n, h, 2 * hkv * d), "normal"),
        ) + norms + (
            ("attn_w_o", (n, hq * d, h), "normal"),
        )
    if kind == "mamba2":
        ms = mamba_spec(cfg)
        return (
            ("m2_w_in", (n, h, 2 * ms.inner + 2 * ms.state + ms.num_heads),
             "normal"),
            ("m2_conv_w", (n, ms.conv, ms.channels), "conv"),
            ("m2_conv_b", (n, ms.channels), "conv"),
            ("m2_a_log", (n, ms.num_heads), "a_log"),
            ("m2_dt_bias", (n, ms.num_heads), "dt_bias"),
            ("m2_d", (n, ms.num_heads), "ones"),
            ("m2_norm", (n, ms.inner), "ones"),
            ("m2_w_out", (n, ms.inner, h), "normal"),
        )
    m = latent_spec(cfg)
    return (
        ("mla_w_q", (n, h, m.num_q_heads * (m.nope_dim + m.rope_dim)),
         "normal"),
        ("mla_w_a", (n, h, m.row), "normal"),
        ("mla_kv_norm", (n, m.rank), "ones"),
        ("mla_w_b", (n, m.rank, m.num_q_heads * (m.nope_dim + m.v_dim)),
         "normal"),
        ("mla_w_o", (n, m.num_q_heads * m.v_dim, h), "normal"),
    )


# the leaves' order; "gqa" is the one set of the window and the global
# grouped-query blocks together. A new kind goes at the END: a leaf's
# key is its position, and the accepted references draw by position
_MIXERS = ("gdn", "kda", "gated_attn", "mla", "gqa", "mamba2")


def _leaf_kind(mixer: str) -> str:
    return "gqa" if mixer in GQA_MIXERS else mixer


def _moe_leaves(cfg: ModelConfig, n: int):
    h, e, eh = cfg.hidden_size, cfg.num_experts, cfg.num_experts_held
    i, ish = cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
    return (
        (("w_router", (n, h, e), "normal"),)
        + ((("router_bias", (n, e), "normal"),) if cfg.router_bias else ())
        + (("w_gate_up", (n, eh, h, 2 * i), "normal"),
           ("w_down", (n, eh, i, h), "normal"),
           ("ws_gate_up", (n, h, 2 * ish), "normal"),
           ("ws_down", (n, ish, h), "normal"))
        + ((("w_sgate", (n, h), "normal"),) if cfg.shared_expert_gate
           else ()))


def _dense_leaves(cfg: ModelConfig, n: int):
    h, i = cfg.hidden_size, cfg.intermediate_size
    return (("wd_gate_up", (n, h, 2 * i), "normal"),
            ("wd_down", (n, i, h), "normal"))


def leaves(cfg: ModelConfig):
    """A leaf the configuration has no use for is ABSENT (a tied head,
    the experts of a pattern without an expert block, a kind's leaves
    without a block of the kind), never a stack of zero rows.

    Under an `embedding_multiplier` m_e the table is drawn m_e times
    smaller ("embed": N(0, _INIT_SCALE / m_e)), so that the stream
    receives m_e E[token] at the scale every other configuration's
    does. At _INIT_SCALE itself a TIED head scores the last token
    m_e |E[token]|^2 over the rest: at Granite's widths 10.9 standard
    deviations of a logit over the stream's rms, where the best of
    100,352 others reaches 4.4, so the model would echo its input
    whatever its blocks compute."""
    L, h, v = cfg.num_layers, cfg.hidden_size, cfg.vocab_size
    mixers = tuple(_leaf_kind(k) for k in cfg.mixer_kinds)
    ld = cfg.first_k_dense
    return (
        (("embed", (v, h),
          "normal" if cfg.embedding_multiplier == 1.0 else "embed"),
         ("final_ln", (h,), "gain"))
        + (() if cfg.tie_word_embeddings
           else (("lm_head", (h, v), "normal"),))
        + (("input_ln", (L, h), "gain"),
           ("post_ln", (L, h), "gain"))
        + (_moe_leaves(cfg, L - ld) if L > ld else ())
        + (_dense_leaves(cfg, ld) if ld else ())
        + tuple(leaf for kind in _MIXERS if kind in mixers
                for leaf in _mixer_leaves(cfg, kind, mixers.count(kind))))


def _mamba_init(u, init: str, xp, taps: int):
    """Mamba-2's published initialisation from `u` uniform in [0, 1):
    "a_log" is log(a), a uniform in [1, 16]; "dt_bias" the inverse
    softplus of a step log-uniform in [1e-3, 1e-1]; "conv" (the
    depthwise convolution's taps and bias) uniform within
    taps ** -0.5 of 0, its framework's default for such a layer.
    Under N(0, 0.02) the decay exp(-softplus(~0) exp(~0)) would halve
    the state every token, and x, B and C would come out of the
    convolution so small that the state's share of y, which the gated
    norm rescales, is 1e-4 of D x: a carry broken between steps would
    change nothing a comparison could see."""
    if init == "conv":
        return (2.0 * u - 1.0) * taps ** -0.5
    if init == "a_log":
        return xp.log(1.0 + 15.0 * u)
    step = xp.exp(u * math.log(100.0) + math.log(1e-3))
    return step + xp.log(-xp.expm1(-step))


def init_params(cfg: ModelConfig, mesh, seed: int = 0,
                fast: bool = False) -> dict:
    """Random parameters on the mesh's one device. fast=True draws on
    the device (each leaf under its own folded key, in slabs, as
    `models.dense._draw` does); fast=False from one host numpy stream
    in the order of `leaves`."""
    dt = jnp.dtype(cfg.dtype)
    where = NamedSharding(mesh, P())
    spec = leaves(cfg)
    gain = "zeros" if cfg.norm_zero_centred else "ones"
    spec = tuple((n, s, gain if i == "gain" else i) for n, s, i in spec)
    const = {"zeros": jnp.zeros, "ones": jnp.ones}
    taps = cfg.mamba_conv_kernel_dim
    if fast:
        def one(key, shape, init):
            if init in const:
                return const[init](shape, dt)
            if init == "normal":
                return _draw(key, shape, dt)
            if init == "embed":
                return (_draw(key, shape, jnp.float32)
                        / cfg.embedding_multiplier).astype(dt)
            return _mamba_init(jax.random.uniform(key, shape, jnp.float32),
                               init, jnp, taps).astype(dt)

        def draw(key):
            return {name: one(jax.random.fold_in(key, i), shape, init)
                    for i, (name, shape, init) in enumerate(spec)}

        return jax.jit(draw, out_shardings=where)(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    host = {"zeros": np.zeros, "ones": np.ones}

    def one(shape, init):
        if init in host:
            return host[init](shape, np.float32)
        if init in ("normal", "embed"):
            return (rng.standard_normal(shape) * _INIT_SCALE
                    / (cfg.embedding_multiplier if init == "embed" else 1.0))
        return _mamba_init(rng.uniform(size=shape), init, np, taps)

    return {name: jax.device_put(
        np.asarray(one(shape, init), np.float32).astype(dt), where)
        for name, shape, init in spec}


# a block's leaves by kind; the experts' own stacks (w_gate_up,
# w_down) go to the expert layer whole (`moe` below)
_MOE = ("w_router", "router_bias", "ws_gate_up", "ws_down", "w_sgate")
_MIXER_LEAVES = {
    "gdn": ("w_qkvz", "w_ba", "conv_w", "a_log", "dt_bias", "gdn_norm",
            "w_out"),
    "kda": ("kda_w_qkv", "kda_w_fgb", "kda_w_fb", "kda_w_gb", "kda_conv_w",
            "kda_a_log", "kda_dt_bias", "kda_norm", "kda_w_out"),
    "gated_attn": ("w_q", "w_kv", "q_norm", "k_norm", "w_o"),
    "mla": ("mla_w_q", "mla_w_a", "mla_kv_norm", "mla_w_b", "mla_w_o"),
    "gqa": ("attn_w_q", "attn_w_kv", "attn_q_norm", "attn_k_norm",
            "attn_w_o"),
    "mamba2": ("m2_w_in", "m2_conv_w", "m2_conv_b", "m2_a_log",
               "m2_dt_bias", "m2_d", "m2_norm", "m2_w_out"),
}
_MIXER_PARAMS = {"gdn": GDNParams, "kda": KDAParams,
                 "gated_attn": GatedAttnParams, "mla": LatentAttnParams,
                 "gqa": GQAttnParams, "mamba2": Mamba2Params}


def forward_chunk(cfg: ModelConfig, params: dict, tokens, cache: Cache,
                  table, lengths, n_valid, attn_impl: str,
                  window_impl: Optional[str] = None):
    """One (slots, chunk) block through the model. Slot s holds
    `lengths[s]` cached positions and `n_valid[s]` real columns.
    Returns (last (K, V) float32, the logits of each slot's column
    `n_valid - 1` (column 0 where `n_valid` is 0): the (K, H) hidden
    rows are taken BEFORE the final norm and the head, which see K
    rows and never the chunk's K x C; then what `chunk_hidden`
    returns after its hidden rows)."""
    x, *rest = chunk_hidden(cfg, params, tokens, cache, table, lengths,
                            n_valid, attn_impl, window_impl)
    with part("head"):
        x = x[jnp.arange(x.shape[0]), jnp.maximum(n_valid - 1, 0)]  # (K, H)
    return head_logits(cfg, params, x), *rest


@part("head")
def head_logits(cfg: ModelConfig, params: dict, x):
    """The final norm and the vocabulary projection over hidden rows
    (..., H): float32 logits (..., V). A tied head is the embedding
    transposed; `logits_scaling` divides them."""
    x = rms_norm(x, params["final_ln"], cfg.rms_eps,
                 zero_centred=cfg.norm_zero_centred)
    if cfg.tie_word_embeddings:
        logits = jnp.einsum("...h,vh->...v", x, params["embed"],
                            preferred_element_type=jnp.float32)
    else:
        logits = jnp.einsum("...h,hv->...v", x, params["lm_head"],
                            preferred_element_type=jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def chunk_hidden(cfg: ModelConfig, params: dict, tokens, cache: Cache,
                 table, lengths, n_valid, attn_impl: str,
                 window_impl: Optional[str] = None):
    """The blocks of `forward_chunk`: (hidden rows (K, C, H) before
    the final norm; the chunk's new rows of the page blocks, one
    (Lf, K, C, Hkv, D) an array of the pages; rec; conv (None without
    a delta-net block); the window blocks' new tails (k, v), or ();
    {counter: () int32}). `attn_impl` is the page blocks' route,
    `window_impl` the window blocks'."""
    slots, chunk = tokens.shape
    g, a, m = gdn_spec(cfg), attn_spec(cfg), latent_spec(cfg)
    gq = gqa_spec(cfg)
    state_spec = mamba_spec(cfg) if "mamba2" in cfg.mixer_kinds else g
    m_r = cfg.residual_multiplier
    eps = cfg.rms_eps
    router = RouterForm(cfg.router_score, cfg.routed_scaling_factor)
    if "gated_attn" in cfg.mixer_kinds:
        cos, sin = rope_table(a.rotary_dim, cfg.max_positions,
                              cfg.rope_theta)
    elif "window_attn" in cfg.mixer_kinds:
        cos, sin = rope_table(gq.head_dim, cfg.max_positions,
                              cfg.rope_theta)
    positions = lengths[:, None] + jnp.arange(chunk)[None, :]
    kv_len = lengths + chunk
    valid = (jnp.arange(chunk)[None, :] < n_valid[:, None]).reshape(-1)
    fresh = lengths == 0
    pages = KVCache.of(cache.pages, lengths, table)

    def normed(x, gain):
        return rms_norm(x, gain, eps, zero_centred=cfg.norm_zero_centred)

    def added(x, y):  # a residual branch, under the multiplier if any
        return x + y if m_r == 1.0 else x + (m_r * y).astype(x.dtype)

    def moe(x, gain, p, layer):
        # the experts' stacks whole, this block's by `layer`: a
        # per-period slice of them would be copied every step
        p = HeldMoEParams(p["w_router"], params["w_gate_up"],
                          params["w_down"], p["ws_gate_up"], p["ws_down"],
                          p.get("w_sgate"), p.get("router_bias"))
        with part("moe.route"):
            hid = normed(x, gain).reshape(slots * chunk, -1)
        y, *counts = held_moe_counted(
            hid, valid, p, cfg.num_experts_per_tok, cfg.expert_offset,
            layer=layer, router=router)
        with part("moe.combine"):
            return added(x, y.reshape(x.shape)), *counts

    @part("ffn.dense")
    def dense(x, gain, p):
        return added(x, swiglu_fwd(normed(x, gain), p["wd_gate_up"],
                                   p["wd_down"]).astype(x.dtype))

    def run(period, start, count):
        """The scan body of a run of `period`s whose first blocks are
        the `start[kind]`-th of their kinds, `count[kind]` a period.
        Every stacked leaf stays whole and a block takes its own row
        of it by index: a period's share cut out of a stack (or handed
        to the scan to cut) is a copy of those weights every step."""
        def one_period(x, i):
            def row(stack, kind, at):
                return jax.lax.dynamic_index_in_dim(
                    stack, start[kind] + i * count[kind] + at,
                    keepdims=False)

            # a block's row of what the slots carry between steps
            carried = part("pool.gather")(row)

            here = absent = tile_rows = combine_rows = combine_pairs = (
                jnp.int32(0))
            recs, convs, rows, tails = [], [], [], []
            at = {kind: 0 for kind in count}
            for j, (mixer, ffn) in enumerate(period):
                leaf = _leaf_kind(mixer)
                # a leaf the configuration lacks (the head norms
                # where the source has none) is None
                p = _MIXER_PARAMS[leaf](*(
                    row(params[n], leaf, at[leaf]) if n in params else None
                    for n in _MIXER_LEAVES[leaf]))
                # the block's norm goes with the projections it feeds,
                # the residual with the one it adds
                proj = part("mixer.proj" if mixer in STATE_MIXERS
                            else "attn.proj")
                with proj:
                    hid = normed(x, row(params["input_ln"], "block", j))
                if mixer in STATE_MIXERS:
                    fwd = (gated_delta_net_fwd if mixer == "gdn"
                           else kda_fwd if mixer == "kda" else mamba2_fwd)
                    y, r, c = fwd(
                        hid, p, state_spec,
                        carried(cache.rec, "state", at["state"]),
                        carried(cache.conv, "state", at["state"]),
                        n_valid, fresh, eps)
                    recs.append(r)
                    convs.append(c)
                    at["state"] += 1
                elif mixer in WINDOW_MIXERS:
                    y, tail = window_attn_fwd(
                        hid, p, gq, cos, sin, positions,
                        tuple(carried(w, "window", at["window"])
                              for w in cache.win),
                        lengths, n_valid, cfg.sliding_window, window_impl,
                        eps)
                    tails.append(tail)
                    at["window"] += 1
                else:
                    view = pages.layer_view(
                        start["page"] + i * count["page"] + at["page"])
                    if mixer == "gated_attn":
                        y, new = gated_attn_fwd(
                            hid, p, a, cos, sin, positions, view, kv_len,
                            attn_impl, eps)
                    elif mixer == "global_attn":
                        y, new = global_attn_fwd(
                            hid, p, gq, positions, view, kv_len, attn_impl,
                            eps)
                    else:
                        y, new = latent_attn_fwd(
                            hid, p, m, positions, view[0], kv_len,
                            n_valid, attn_impl, eps)
                    rows.append(new)
                    at["page"] += 1
                at[leaf] += 1
                gain = row(params["post_ln"], "block", j)
                with proj:
                    x = added(x, y)
                if ffn == "moe":
                    x, h_j, a_j, t_j, r_j, p_j = moe(
                        x, gain,
                        {n: row(params[n], "moe", at["moe"]) for n in _MOE
                         if n in params},
                        start["moe"] + i * count["moe"] + at["moe"])
                    here, absent = here + h_j, absent + a_j
                    tile_rows = tile_rows + t_j
                    combine_rows = combine_rows + r_j
                    combine_pairs = combine_pairs + p_j
                else:
                    x = dense(x, gain, {
                        n: row(params[n], "dense", at["dense"])
                        for n in ("wd_gate_up", "wd_down")})
                at[ffn] += 1
            # a period's one page block hands its rows on as they are;
            # several are stacked, like the state and the tails
            with part("pool.scatter"):
                if len(rows) == 1:
                    rows, = rows
                else:
                    rows = tuple(jnp.stack(r) for r in zip(*rows))
                state = ((jnp.stack(recs), jnp.stack(convs)) if recs
                         else ())
                tails = tuple(jnp.stack(t) for t in zip(*tails))
            return x, (state, rows, tails, here, absent, tile_rows,
                       combine_rows, combine_pairs)

        return one_period

    with part("embed"):
        x = params["embed"][tokens]
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
    start = {kind: 0 for kind in _MIXERS + ("moe", "dense", "block",
                                            "state", "page", "window")}
    outs, pages_a_period = [], []
    for period, n in segments(cfg):
        count = {kind: 0 for kind in start}
        for mixer, ffn in period:
            for kind in (_leaf_kind(mixer), ffn, "block"):
                count[kind] += 1
            count["state"] += mixer in STATE_MIXERS
            count["page"] += mixer in PAGE_MIXERS
            count["window"] += mixer in WINDOW_MIXERS
        x, out = jax.lax.scan(run(period, dict(start), count), x,
                              jnp.arange(n))
        outs.append(out)
        pages_a_period.append(count["page"])
        for kind in start:
            start[kind] += n * count[kind]

    def joined(parts):
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    def flat(p):  # (repeats, a period's blocks, ...) -> (blocks, ...)
        return p.reshape((-1,) + p.shape[2:])

    def per_block(at: int, width: int):
        """`width` arrays a block from the scans' stacked results, of
        the periods that have any."""
        return tuple(joined([flat(o[at][k]) for o in outs if o[at]])
                     for k in range(width))

    with part("pool.scatter"):  # the scans' results, as the pool's
        rec, conv = per_block(0, 2) if start["state"] else (None, None)
        rows = tuple(
            joined([flat(o[1][k]) if n > 1 else o[1][k]
                    for o, n in zip(outs, pages_a_period) if n])
            for k in range(len(cache.pages)))
    # a pattern without an expert block counts nothing on the device
    stats = {} if "moe" not in cfg.ffn_kinds else {
        "moe_pairs_here": sum(jnp.sum(o[3]) for o in outs),
        "moe_pairs_absent": sum(jnp.sum(o[4]) for o in outs),
        "moe_gmm_tile_rows": sum(jnp.sum(o[5]) for o in outs),
        "moe_combine_rows": sum(jnp.sum(o[6]) for o in outs),
        "moe_combine_pairs": sum(jnp.sum(o[7]) for o in outs)}
    with part("pool.scatter"):
        win = per_block(2, len(cache.win))
    return x, rows, rec, conv, win, stats


def state_shapes(cfg: ModelConfig, slots: int):
    """Shapes of the delta-net or state-space blocks' per-slot state
    beside the pages, (rec, conv); () for a pattern without such a
    block."""
    ll = sum(k in STATE_MIXERS for k in cfg.mixer_kinds)
    if not ll:
        return ()
    if "mamba2" in cfg.mixer_kinds:
        ms = mamba_spec(cfg)
        return ((ll, slots, ms.num_heads, ms.head_dim, ms.state),
                (ll, slots, ms.conv - 1, ms.channels))
    g = gdn_spec(cfg)
    return ((ll, slots, g.num_v_heads, g.k_dim, g.v_dim),
            (ll, slots, g.conv - 1, g.channels))


def window_shapes(cfg: ModelConfig, slots: int):
    """Shapes of the window blocks' per-slot tails, (k, v): the last
    `sliding_window` positions a block and slot, whatever the context;
    () for a pattern without a window block."""
    lw = cfg.num_window_layers
    if not lw:
        return ()
    return ((lw, slots, cfg.sliding_window, cfg.num_kv_heads,
             cfg.head_dim),) * 2


def slot_state(cfg: ModelConfig) -> str:
    """What this configuration's slots carry beside their pages, as
    the refusals name it (serve/scheduler.py, serve/kv_pool.py,
    models/engine.py, mega/qwen3.py)."""
    kinds = set(cfg.mixer_kinds)
    what = []
    if kinds & {"gdn", "kda"}:
        what.append("recurrent (gated-delta-net) state")
    if "mamba2" in kinds:
        what.append("state-space (Mamba-2) state")
    if kinds & set(WINDOW_MIXERS):
        what.append("a window block's tail (the last sliding_window keys "
                    "and values a slot)")
    return " and ".join(what)
