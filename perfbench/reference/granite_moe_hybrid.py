"""Plain reference of the Granite 4.0-H decoder WITH an expert layer
(`granitemoehybrid`, `num_local_experts` > 0), kept with the benchmark.

Straightforward `jax.numpy`, float32 at `highest` matmul precision, one
full causal pass over [prompt + served tokens]: no kernel, no cache, no
carried state, no paging, no batching, the state-space mixer as the
recurrence position by position and the expert layer one held expert
at a time over every row. It imports nothing of the program and takes
nothing the program has made: the weights are drawn here, from the
seed, by this file's own copy of the leaves' order and shapes (the
program's `models.hybrid.leaves` is the original; a seed names the same
tensors in both, and tests pin that bit for bit). The two mixers are
`granite_hybrid`'s, imported.

The model (the source's `config.json`, the published GraniteMoeHybrid
description; what the `config` does not state is under `assumed` in
the configuration's file). With m_e, m_r, m_l the multipliers:

    x = m_e E[tokens]
    block i:  x = x + m_r Mixer_i(norm(x))
              h = norm(x);  x = x + m_r (MoE(h) + SharedMLP(h))
    logits = norm(x) E^T / m_l                         (tied: no head)

- Mixer: "mamba" or "attention" as in `granite_hybrid`.
- MoE(h): logits h W_r over ALL `router_width` experts; the k largest
  go through a softmax over those k (Granite's top-k gating, written
  here in its own order: choose, then normalise); the chip's share is
  the term of each (token, choice) pair whose expert lies in [offset,
  offset + held), each expert (silu(h W_g) * h W_u) W_d of
  `intermediate_size`.
- SharedMLP(h): the same SwiGLU at `shared_intermediate_size`, no gate.

Departures from the published model: those of `granite_hybrid` (random
weights, the embedding drawn at 0.02 / m_e, Mamba-2's initialisation
for its own leaves, this file's column layout); the experts held and
the vocabulary's slice are the chip's share (the configuration's
`reduced`).

`quant` is the CONTROL, as in `qwen3_dense`: both operands of every
linear layer (the router's and the tied head's among them) rounded; the
recurrence and the attention products stay float32. `gap_quantile` as
in `qwen3_next`.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from perfbench.reference import granite_hybrid as gh
from perfbench.reference.qwen3_next import cut_to_quantile

_mm, _rms, _swiglu = gh._mm, gh._rms, gh._swiglu
replicated = gh.replicated


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    hidden: int
    layers: int
    attends: Tuple[bool, ...]  # a block: attention (else state-space)
    q_heads: int
    kv_heads: int
    head_dim: int
    m_heads: int
    m_head_dim: int
    m_state: int
    m_conv: int
    routed: int
    held: int
    offset: int
    per_token: int
    expert_inter: int
    shared_inter: int
    m_e: float
    m_r: float
    m_a: float
    m_l: float
    rms_eps: float
    max_len: int
    dtype: str
    gap_quantile: float = 1.0

    @property
    def inner(self) -> int:
        return self.m_heads * self.m_head_dim

    @property
    def channels(self) -> int:
        return self.inner + 2 * self.m_state

    @staticmethod
    def from_config(cfg: dict) -> "Sizes":
        ep = cfg["expert_parallel"]
        L = cfg["num_hidden_layers"]
        kinds = {"attention": True, "mamba": False}
        assert len(cfg["layer_types"]) == L and cfg["mamba_n_groups"] == 1
        assert cfg["position_embedding_type"] == "nope"
        assert cfg["tie_word_embeddings"] and cfg["mamba_conv_bias"]
        assert not cfg["mamba_proj_bias"] and not cfg["attention_bias"]
        assert (cfg["mamba_n_heads"] * cfg["mamba_d_head"]
                == cfg["mamba_expand"] * cfg["hidden_size"])
        return Sizes(
            vocab=cfg["vocab_size"], hidden=cfg["hidden_size"], layers=L,
            attends=tuple(kinds[t] for t in cfg["layer_types"]),
            q_heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
            m_heads=cfg["mamba_n_heads"], m_head_dim=cfg["mamba_d_head"],
            m_state=cfg["mamba_d_state"], m_conv=cfg["mamba_d_conv"],
            routed=ep["router_width"], held=cfg["num_local_experts"],
            offset=ep["expert_offset"],
            per_token=cfg["num_experts_per_tok"],
            expert_inter=cfg["intermediate_size"],
            shared_inter=cfg["shared_intermediate_size"],
            m_e=float(cfg["embedding_multiplier"]),
            m_r=float(cfg["residual_multiplier"]),
            m_a=float(cfg["attention_multiplier"]),
            m_l=float(cfg["logits_scaling"]),
            rms_eps=cfg["rms_norm_eps"], max_len=cfg["serve"]["max_len"],
            dtype=cfg["torch_dtype"],
            gap_quantile=float(
                cfg.get("check", {}).get("gap_quantile", 1.0)))


MOE = ("w_router", "ws_gate_up", "ws_down")
EXPERTS = ("w_gate_up", "w_down")
ATTN, MAMBA = gh.ATTN, gh.MAMBA


# (name, shape, init) in the order that fixes each leaf's key,
# fold_in(PRNGKey(seed), position); the inits are `granite_hybrid`'s
def _leaves(s: Sizes):
    L, h = s.layers, s.hidden
    lf = sum(s.attends)
    ls = L - lf
    hq, hkv, d = s.q_heads, s.kv_heads, s.head_dim
    di, ch = s.inner, s.channels
    i, ish = s.expert_inter, s.shared_inter
    return (
        ("embed", (s.vocab, h), "embed"),
        ("final_ln", (h,), "ones"),
        ("input_ln", (L, h), "ones"),
        ("post_ln", (L, h), "ones"),
        ("w_router", (L, h, s.routed), "normal"),
        ("w_gate_up", (L, s.held, h, 2 * i), "normal"),
        ("w_down", (L, s.held, i, h), "normal"),
        ("ws_gate_up", (L, h, 2 * ish), "normal"),
        ("ws_down", (L, ish, h), "normal"),
        ("attn_w_q", (lf, h, hq * d), "normal"),
        ("attn_w_kv", (lf, h, 2 * hkv * d), "normal"),
        ("attn_w_o", (lf, hq * d, h), "normal"),
        ("m2_w_in", (ls, h, di + ch + s.m_heads), "normal"),
        ("m2_conv_w", (ls, s.m_conv, ch), "conv"),
        ("m2_conv_b", (ls, ch), "conv"),
        ("m2_a_log", (ls, s.m_heads), "a_log"),
        ("m2_dt_bias", (ls, s.m_heads), "dt_bias"),
        ("m2_d", (ls, s.m_heads), "ones"),
        ("m2_norm", (ls, di), "ones"),
        ("m2_w_out", (ls, di, h), "normal"),
    )


def draw_weights(s: Sizes, n: int, seed: int, devices) -> dict:
    """The weight set that `seed` names; the family runs one chip of
    its group, so `n` is 1."""
    if n != 1:
        raise ValueError(f"this family has no tensor-parallel form (tp={n})")
    assert 0 < sum(s.attends) < s.layers, "both kinds hold a position"
    dt = jnp.dtype(s.dtype)

    def draw(key):
        return {name: gh._draw_leaf(jax.random.fold_in(key, i), shape,
                                    init, dt, s.m_conv, s.m_e)
                for i, (name, shape, init) in enumerate(_leaves(s))}

    with jax.default_device(list(devices)[0]):
        return jax.jit(draw)(jax.random.PRNGKey(seed))


def experts(s: Sizes, h, w, quant):
    """MoE(h) + SharedMLP(h) over h (S, H): the chip's share of the
    routed experts, one held expert at a time over every row, and the
    ungated shared MLP. `w` holds one block's router and shared MLP
    and its (held, ...) expert stacks."""
    S = h.shape[0]
    logits = _mm("sh,he->se", h, w["w_router"], quant)
    top, ids = jax.lax.top_k(logits, s.per_token)
    gates = jax.nn.softmax(top, axis=-1)
    combine = jnp.zeros((S, s.routed), jnp.float32).at[
        jnp.arange(S)[:, None], ids].set(gates)
    mine = combine[:, s.offset:s.offset + s.held]

    def one(acc, xs):
        w_gu, w_dn, c = xs
        return acc + c[:, None] * _swiglu(h, w_gu, w_dn, quant), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (w["w_gate_up"], w["w_down"], mine.T))
    return out + _swiglu(h, w["ws_gate_up"], w["ws_down"], quant)


def hidden_rows(s: Sizes, w: dict, tokens, quant: Optional[str] = None):
    """The residual stream (S, H) float32 after the last block of one
    causal pass over `tokens` (S,) int32. ONE scan over the blocks; a
    block's kind picks its mixer, and its place among its kind the
    mixer's weights; every block has the expert layer."""
    attends = jnp.asarray(s.attends)
    nth_attn = jnp.maximum(jnp.cumsum(attends) - 1, 0)
    nth_mamba = jnp.maximum(jnp.cumsum(~attends) - 1, 0)
    x = s.m_e * w["embed"][tokens].astype(jnp.float32)

    def pick(names, at):
        return {n: jax.lax.dynamic_index_in_dim(w[n], at, keepdims=False)
                for n in names}

    def block(x, xs):
        ln_in, ln_post, at, attn_here, at_attn, at_mamba = xs
        h = _rms(x, ln_in, s.rms_eps)
        x = x + s.m_r * jax.lax.cond(
            attn_here,
            lambda: gh.attention(s, h, pick(ATTN, at_attn), quant),
            lambda: gh.mamba(s, h, pick(MAMBA, at_mamba), quant))
        h = _rms(x, ln_post, s.rms_eps)
        return x + s.m_r * experts(s, h, pick(MOE + EXPERTS, at),
                                   quant), None

    x, _ = jax.lax.scan(block, x, (
        w["input_ln"], w["post_ln"], jnp.arange(s.layers), attends,
        nth_attn, nth_mamba))
    return x


def logits_rows(s: Sizes, w: dict, tokens, first, rows: int,
                quant: Optional[str] = None):
    """Logits (rows, V) float32 at positions first .. first+rows-1 of
    one causal pass over `tokens` (S,) int32, over the vocabulary's
    slice the tied table holds. Positions past the real sequence are
    padding: causality keeps them from reaching a row before them."""
    x = jax.lax.dynamic_slice_in_dim(hidden_rows(s, w, tokens, quant),
                                     first, rows)
    return _mm("sh,vh->sv", _rms(x, w["final_ln"], s.rms_eps),
               w["embed"], quant) / s.m_l


def make_scorer(s: Sizes, width: int, rows: int,
                quant: Optional[str] = None):
    """jitted (weights, tokens (width,), first) -> (rows, V) logits."""
    return jax.jit(lambda w, tokens, first: logits_rows(
        s, w, tokens, first, rows, quant))


def make_gap_scorer(s: Sizes, width: int, rows: int):
    """jitted (weights, tokens (width,), first, scored (rows,)) ->
    (rows,) float32: how far the logit of scored[j] lies under the
    reference's best at position first + j (0 where it IS the best);
    under a `gap_quantile` below 1, cut to that quantile of the
    request's served rows, which are found as `qwen3_next`'s scorer
    finds them (its doc: the harness hands no count of them)."""
    def fn(w, tokens, first, scored):
        logits = logits_rows(s, w, tokens, first, rows)
        got = jnp.take_along_axis(logits, scored[:, None], axis=1)[:, 0]
        gaps = jnp.max(logits, axis=1) - got
        if s.gap_quantile >= 1.0:
            return gaps
        end = jnp.max(jnp.where(tokens != 0, jnp.arange(width), -1))
        at = first + 1 + jnp.arange(rows)
        follows = tokens[jnp.clip(at, 0, width - 1)]
        served = (at <= end) & ~((scored == 0) & (follows != 0))
        return cut_to_quantile(gaps, served, s.gap_quantile)

    return jax.jit(fn)


def make_top_scorer(s: Sizes, width: int, rows: int, quant: str):
    """jitted (weights, tokens, first) -> (rows,) int32: the token the
    CONTROL precision puts first at each position."""
    return jax.jit(lambda w, tokens, first: jnp.argmax(
        logits_rows(s, w, tokens, first, rows, quant),
        axis=1).astype(jnp.int32))
