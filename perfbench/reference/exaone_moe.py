"""Plain reference of the K-EXAONE decoder (`exaone_moe`), kept with
the benchmark.

Straightforward `jax.numpy`, float32 at `highest` matmul precision, one
full causal pass over [prompt + served tokens] with the sliding window
as a MASK: no kernel, no cache, no per-slot tail, no paging, no
batching. It imports nothing of the program and takes nothing the
program has made: the weights are drawn here, from the seed, by this
file's own copy of the leaves' order and shapes (the program's
`models.hybrid.leaves` is the original; a seed names the same tensors
in both, and tests pin that bit for bit).

The model (the source's `config.json` and `described_as`; what the
`config` does not state is under `assumed` in the configuration's
file). H hidden; RMSNorm x / rms(x) * w with eps `rms_norm_eps`; block
i is `x += Attn_i(norm(x)); x += FFN_i(norm(x))` (both norms BEFORE
their layer); final norm; untied head.
- Attention, every block: q, k, v = h W_q, h W_k, h W_v (64 / 8 / 8
  heads of 128); q and k RMS-normalised a head under a gain of 128
  each; in a block whose `layer_types` entry is "sliding_attention",
  and ONLY there, rotary (half-split, `rope_theta`) on q and k over the
  whole head; score(i, j) = q_i . k_j / sqrt(128) over j <= i, and in a
  sliding block also j > i - `sliding_window`; a "full_attention" block
  takes q and k as they are (no rotary) and every j <= i; softmax;
  y = W_o concat(heads); queries in blocks of `Q_BLOCK` rows.
- FFN: a block whose `mlp_layer_types` entry is "dense" is SwiGLU of
  `intermediate_size`; a "sparse" one: s = sigmoid(h W_r) over ALL
  routed experts (float32); the k chosen are the largest of s + bias;
  their weights s[chosen] / sum(s[chosen]) * routed_scaling_factor; the
  chip's share is the term of each pair whose expert lies in [offset,
  offset + held), one expert at a time over all rows; plus
  SwiGLU_shared(h), no gate.

Departures from the published model: weights are random; the column
layout (W_kv k | v, every projection head-major) is the builder's; the
experts held and the vocabulary's slice are the chip's share (the
configuration's `reduced`); the multi-token-prediction block is not
here (`assumed.not_served`).

`quant` is the CONTROL, as in `qwen3_dense`. `gap_quantile` as in
`qwen3_next` (its module doc): a sigmoid router over 128 experts puts
the eighth and the ninth score close, one other expert of width 2,048
moves a hidden state by tens of percent, so the widest gap of a served
token reads 0.20-0.68 where the fp8 control's reads 0.92-1.95 (my chip
runs, PR 38: too close for a limit) and a quantile of each request's
gaps tells the two apart.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from perfbench.reference import qwen3_dense as dense
from perfbench.reference.qwen3_next import cut_to_quantile

_mm, _draw, _rms, _rope = dense._mm, dense._draw, dense._rms, dense._rope
replicated = dense.replicated
_HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 256  # query rows of one attention block


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    hidden: int
    layers: int
    sliding: Tuple[bool, ...]  # a block: window attention with rotary
    window: int
    dense_layers: int
    dense_inter: int
    q_heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    routed: int
    held: int
    offset: int
    per_token: int
    expert_inter: int
    shared_inter: int
    scale: float
    rms_eps: float
    max_len: int
    dtype: str
    gap_quantile: float = 1.0

    @staticmethod
    def from_config(cfg: dict) -> "Sizes":
        ep = cfg["expert_parallel"]
        L, ld = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
        assert cfg["scoring_func"] == "sigmoid" and cfg["norm_topk_prob"]
        assert cfg["n_group"] == 1 and cfg["topk_group"] == 1
        kinds = {"sliding_attention": True, "full_attention": False}
        assert len(cfg["layer_types"]) == L
        assert cfg["mlp_layer_types"] == ["dense"] * ld + ["sparse"] * (L - ld)
        assert cfg["sliding_windows"] == [
            cfg["sliding_window"] if t == "sliding_attention" else 0
            for t in cfg["layer_types"]]
        return Sizes(
            vocab=cfg["vocab_size"], hidden=cfg["hidden_size"], layers=L,
            sliding=tuple(kinds[t] for t in cfg["layer_types"]),
            window=cfg["sliding_window"], dense_layers=ld,
            dense_inter=cfg["intermediate_size"],
            q_heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
            routed=ep["router_width"], held=cfg["num_experts"],
            offset=ep["expert_offset"],
            per_token=cfg["num_experts_per_tok"],
            expert_inter=cfg["moe_intermediate_size"],
            shared_inter=cfg["num_shared_experts"]
            * cfg["moe_intermediate_size"],
            scale=cfg["routed_scaling_factor"],
            rms_eps=cfg["rms_norm_eps"], max_len=cfg["serve"]["max_len"],
            dtype=cfg["torch_dtype"],
            gap_quantile=float(
                cfg.get("check", {}).get("gap_quantile", 1.0)))


MOE = ("w_router", "router_bias", "w_gate_up", "w_down", "ws_gate_up",
       "ws_down")
DENSE = ("wd_gate_up", "wd_down")
ATTN = ("attn_w_q", "attn_w_kv", "attn_q_norm", "attn_k_norm", "attn_w_o")


# (name, shape, init) in the order that fixes each leaf's key,
# fold_in(PRNGKey(seed), position); every gain starts at 1
def _leaves(s: Sizes):
    L, h, v = s.layers, s.hidden, s.vocab
    ld = s.dense_layers
    lm = L - ld
    hq, hkv, d = s.q_heads, s.kv_heads, s.head_dim
    i, ish = s.expert_inter, s.shared_inter
    return (
        ("embed", (v, h), "normal"),
        ("final_ln", (h,), "ones"),
        ("lm_head", (h, v), "normal"),
        ("input_ln", (L, h), "ones"),
        ("post_ln", (L, h), "ones"),
        ("w_router", (lm, h, s.routed), "normal"),
        ("router_bias", (lm, s.routed), "normal"),
        ("w_gate_up", (lm, s.held, h, 2 * i), "normal"),
        ("w_down", (lm, s.held, i, h), "normal"),
        ("ws_gate_up", (lm, h, 2 * ish), "normal"),
        ("ws_down", (lm, ish, h), "normal"),
        ("wd_gate_up", (ld, h, 2 * s.dense_inter), "normal"),
        ("wd_down", (ld, s.dense_inter, h), "normal"),
        ("attn_w_q", (L, h, hq * d), "normal"),
        ("attn_w_kv", (L, h, 2 * hkv * d), "normal"),
        ("attn_q_norm", (L, d), "ones"),
        ("attn_k_norm", (L, d), "ones"),
        ("attn_w_o", (L, hq * d, h), "normal"),
    )


def draw_weights(s: Sizes, n: int, seed: int, devices) -> dict:
    """The weight set that `seed` names; the family runs one chip of
    its group, so `n` is 1."""
    if n != 1:
        raise ValueError(f"this family has no tensor-parallel form (tp={n})")
    assert s.dense_layers >= 1, "the leaves' positions count the dense ones"
    dt = jnp.dtype(s.dtype)

    def draw(key):
        return {name: jnp.ones(shape, dt) if init == "ones"
                else _draw(jax.random.fold_in(key, i), shape, dt)
                for i, (name, shape, init) in enumerate(_leaves(s))}

    with jax.default_device(list(devices)[0]):
        return jax.jit(draw)(jax.random.PRNGKey(seed))


def _swiglu(h, w_gu, w_dn, quant):
    gu = _mm("sh,hc->sc", h, w_gu, quant)
    i = gu.shape[-1] // 2
    return _mm("si,ih->sh", jax.nn.silu(gu[:, :i]) * gu[:, i:], w_dn, quant)


def attention(s: Sizes, h, w, sliding, quant):
    """One attention mixer over h (S, H); `sliding` (a traced bool)
    says whether this block turns q and k by rotary and sees the last
    `window` positions alone."""
    S = h.shape[0]
    hq, hkv, d = s.q_heads, s.kv_heads, s.head_dim
    g = hq // hkv
    pos = jnp.arange(S)
    q = _mm("sh,hc->sc", h, w["attn_w_q"], quant).reshape(S, hq, d)
    kv = _mm("sh,hc->sc", h, w["attn_w_kv"], quant)
    k = kv[:, :hkv * d].reshape(S, hkv, d)
    v = kv[:, hkv * d:].reshape(S, hkv, d)
    q = _rms(q, w["attn_q_norm"], s.rms_eps)
    k = _rms(k, w["attn_k_norm"], s.rms_eps)
    q = jnp.where(sliding, _rope(q, pos, s.rope_theta), q)
    k = jnp.where(sliding, _rope(k, pos, s.rope_theta), k)
    blk = min(Q_BLOCK, S)
    assert S % blk == 0

    def block(xs):
        q_b, pos_b = xs
        att = jnp.einsum("sjgd,tjd->jgst",
                         q_b.reshape(blk, hkv, g, d) * d ** -0.5, k,
                         precision=_HI)
        seen = pos[None, :] <= pos_b[:, None]
        seen &= jnp.logical_not(sliding) | (
            pos[None, :] > pos_b[:, None] - s.window)
        att = jnp.where(seen[None, None], att, -jnp.inf)
        return jnp.einsum("jgst,tjd->sjgd", jax.nn.softmax(att, axis=-1), v,
                          precision=_HI).reshape(blk, hq * d)

    o = jax.lax.map(block, (q.reshape(S // blk, blk, hq, d),
                            pos.reshape(S // blk, blk)))
    return _mm("sc,ch->sh", o.reshape(S, hq * d), w["attn_w_o"], quant)


def experts(s: Sizes, h, w, quant):
    """The chip's share of the expert layer over h (S, H), plus the
    shared expert: routing over all, one held expert at a time."""
    S = h.shape[0]
    score = jax.nn.sigmoid(_mm("sh,he->se", h, w["w_router"], quant))
    _, ids = jax.lax.top_k(score + w["router_bias"].astype(jnp.float32),
                           s.per_token)
    top = jnp.take_along_axis(score, ids, axis=1)
    top = top / jnp.sum(top, axis=-1, keepdims=True) * s.scale
    combine = jnp.zeros((S, s.routed), jnp.float32).at[
        jnp.arange(S)[:, None], ids].set(top)
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
    block's kind picks its mask and its FFN, and its place among its
    kind the weights."""
    is_dense = jnp.arange(s.layers) < s.dense_layers
    nth_dense = jnp.minimum(jnp.arange(s.layers), s.dense_layers - 1)
    nth_moe = jnp.maximum(jnp.arange(s.layers) - s.dense_layers, 0)
    x = w["embed"][tokens].astype(jnp.float32)

    def pick(names, at):
        return {n: jax.lax.dynamic_index_in_dim(w[n], at, keepdims=False)
                for n in names}

    def block(x, xs):
        ln_in, ln_post, sliding, dense_here, at, at_dense, at_moe = xs
        x = x + attention(s, _rms(x, ln_in, s.rms_eps), pick(ATTN, at),
                          sliding, quant)
        h = _rms(x, ln_post, s.rms_eps)
        x = x + jax.lax.cond(
            dense_here,
            lambda: _swiglu(h, *pick(DENSE, at_dense).values(), quant),
            lambda: experts(s, h, pick(MOE, at_moe), quant))
        return x, None

    x, _ = jax.lax.scan(block, x, (
        w["input_ln"], w["post_ln"], jnp.asarray(s.sliding), is_dense,
        jnp.arange(s.layers), nth_dense, nth_moe))
    return x


def logits_rows(s: Sizes, w: dict, tokens, first, rows: int,
                quant: Optional[str] = None):
    """Logits (rows, V) float32 at positions first .. first+rows-1 of
    one causal pass over `tokens` (S,) int32. Positions past the real
    sequence are padding: causality keeps them from reaching a row
    before them."""
    x = jax.lax.dynamic_slice_in_dim(hidden_rows(s, w, tokens, quant),
                                     first, rows)
    return _mm("sh,hv->sv", _rms(x, w["final_ln"], s.rms_eps),
               w["lm_head"], quant)


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
