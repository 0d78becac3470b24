"""Plain reference of the Kimi-Linear decoder, kept with the benchmark.

Straightforward `jax.numpy`, float32 at `highest` matmul precision, one
full causal pass over [prompt + served tokens]: no kernel, no cache, no
paging, no chunked recurrence, and the latent attention EXPANDED (keys
and values a head from the latent row), where the program computes the
absorbed form over the latent rows themselves. It imports nothing of
the program and takes nothing the program has made: the weights are
drawn here, from the seed, by this file's own copy of the leaves' order
and shapes (the program's `models.hybrid.leaves` is the original; a
seed names the same tensors in both, and tests pin that bit for bit).

The model (the source's `config.json` and `described_as`; what the
`config` does not state is under `assumed` in the configuration's
file). H hidden; RMSNorm x / rms(x) * w with eps `rms_norm_eps`;
block i (counting from 1, as the source's lists do) is
`x += Mixer_i(norm(x)); x += FFN_i(norm(x))`; final norm; untied head.
- KDA mixer, block i in `kda_layers` (Hh heads, dk = dv = `head_dim` of
  `linear_attn_config`, convolution width K): q~ | k~ | v~ = x W_qkv,
  through a causal depthwise convolution, no bias, then SiLU; q =
  l2norm(q~) dk^-0.5, k = l2norm(k~), v = v~; f_a | g_a | b = x W_fgb;
  the gate, a vector over each head's KEY CHANNELS, g_t = -exp(A_log[h])
  softplus(f_a W_fb + dt_bias); beta_t = sigmoid(b), one a head; per
  head, as a SCAN OVER TOKENS from zero state: S = diag(exp(g_t)) S;
  r = v_t - S^T k_t; S = S + k_t (beta_t r)^T; o_t = S^T q_t; then
  y = W_o [rms(o_t) w sigmoid(g_a W_gb)].
- Latent attention, block i in `full_attn_layers` (`mla_use_nope`: no
  rotary anywhere): a head's q_n | q_r = x W_q; c | k_r = x W_a, c_kv =
  RMSNorm(c); a head's k_n | v = c_kv W_b; score_h(t, s) = (q_n . k_n +
  q_r . k_r) / sqrt(dn + dr), causal softmax, o_h = sum p v_h, y = W_o
  concat(o_h); queries in blocks of `Q_BLOCK` rows.
- Experts, blocks after the first `first_k_dense_replace`: s = sigmoid(
  x W_r) over ALL routed experts; the k chosen are the largest of s +
  bias; their weights s[chosen] / sum(s[chosen]) * routed_scaling_factor;
  the chip's share is the term of each pair whose expert lies in
  [offset, offset + held), one expert at a time over all rows; plus
  SwiGLU_shared(x), no gate. The leading blocks: SwiGLU of
  `intermediate_size`.

Departures from the published model: weights are random; the column
layouts (W_qkv q | k | v head-major, W_fgb f_a | g_a | b, W_q a head
q_n | q_r, W_a c | k_r, W_b a head k_n | v) are the builder's; the
experts held are the chip's share (the configuration's `reduced`).

`quant` is the CONTROL, as in `qwen3_dense`. `gap_quantile` as in
`qwen3_next` (its module doc): a sigmoid router over 256 experts puts
the eighth and the ninth score close, one other expert moves a hidden
state by tens of percent, so the widest gap says nothing about the
arithmetic and a quantile of each request's gaps does.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from perfbench.reference import qwen3_dense as dense
from perfbench.reference.qwen3_next import cut_to_quantile

_mm, _draw, _rms = dense._mm, dense._draw, dense._rms
replicated = dense.replicated
_HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 1024  # query rows of one attention block


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    hidden: int
    layers: int
    kda_layers: Tuple[int, ...]  # counting from 1
    full_layers: Tuple[int, ...]
    dense_layers: int
    dense_inter: int
    heads: int
    rank: int
    nope: int
    rope: int
    v_dim: int
    lin_heads: int
    lin_dim: int
    conv: int
    gate_rank: int
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
        ep, lin = cfg["expert_parallel"], cfg["linear_attn_config"]
        assert cfg["moe_router_activation_func"] == "sigmoid"
        assert cfg["moe_renormalize"] and cfg["mla_use_nope"]
        return Sizes(
            vocab=cfg["vocab_size"], hidden=cfg["hidden_size"],
            layers=cfg["num_hidden_layers"],
            kda_layers=tuple(lin["kda_layers"]),
            full_layers=tuple(lin["full_attn_layers"]),
            dense_layers=cfg["first_k_dense_replace"],
            dense_inter=cfg["intermediate_size"],
            heads=cfg["num_attention_heads"], rank=cfg["kv_lora_rank"],
            nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
            v_dim=cfg["v_head_dim"], lin_heads=lin["num_heads"],
            lin_dim=lin["head_dim"], conv=lin["short_conv_kernel_size"],
            gate_rank=lin["head_dim"],  # assumed: the gates' low rank
            routed=ep["router_width"], held=cfg["num_experts"],
            offset=ep["expert_offset"],
            per_token=cfg["num_experts_per_token"],
            expert_inter=cfg["moe_intermediate_size"],
            shared_inter=cfg["num_shared_experts"]
            * cfg["moe_intermediate_size"],
            scale=cfg["routed_scaling_factor"],
            rms_eps=cfg["rms_norm_eps"], max_len=cfg["serve"]["max_len"],
            dtype=cfg["torch_dtype"],
            gap_quantile=float(
                cfg.get("check", {}).get("gap_quantile", 1.0)))

    @property
    def channels(self) -> int:
        return 3 * self.lin_heads * self.lin_dim


MOE = ("w_router", "router_bias", "w_gate_up", "w_down", "ws_gate_up",
       "ws_down")
DENSE = ("wd_gate_up", "wd_down")
KDA = ("kda_w_qkv", "kda_w_fgb", "kda_w_fb", "kda_w_gb", "kda_conv_w",
       "kda_a_log", "kda_dt_bias", "kda_norm", "kda_w_out")
MLA = ("mla_w_q", "mla_w_a", "mla_kv_norm", "mla_w_b", "mla_w_o")


# (name, shape, init) in the order that fixes each leaf's key,
# fold_in(PRNGKey(seed), position); every gain starts at 1
def _leaves(s: Sizes):
    L, h, v = s.layers, s.hidden, s.vocab
    lk, lf, ld = len(s.kda_layers), len(s.full_layers), s.dense_layers
    lm = L - ld
    hd, r = s.lin_heads * s.lin_dim, s.gate_rank
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
        ("kda_w_qkv", (lk, h, s.channels), "normal"),
        ("kda_w_fgb", (lk, h, 2 * r + s.lin_heads), "normal"),
        ("kda_w_fb", (lk, r, hd), "normal"),
        ("kda_w_gb", (lk, r, hd), "normal"),
        ("kda_conv_w", (lk, s.conv, s.channels), "normal"),
        ("kda_a_log", (lk, s.lin_heads), "normal"),
        ("kda_dt_bias", (lk, hd), "normal"),
        ("kda_norm", (lk, s.lin_dim), "ones"),
        ("kda_w_out", (lk, hd, h), "normal"),
        ("mla_w_q", (lf, h, s.heads * (s.nope + s.rope)), "normal"),
        ("mla_w_a", (lf, h, s.rank + s.rope), "normal"),
        ("mla_kv_norm", (lf, s.rank), "ones"),
        ("mla_w_b", (lf, s.rank, s.heads * (s.nope + s.v_dim)), "normal"),
        ("mla_w_o", (lf, s.heads * s.v_dim, h), "normal"),
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


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _swiglu(h, w_gu, w_dn, quant):
    gu = _mm("sh,hc->sc", h, w_gu, quant)
    i = gu.shape[-1] // 2
    return _mm("si,ih->sh", jax.nn.silu(gu[:, :i]) * gu[:, i:], w_dn, quant)


def kda(s: Sizes, h, w, quant):
    """One channel-gated delta-net mixer over h (S, H): the recurrence
    as a scan over tokens from zero state."""
    S = h.shape[0]
    hh, d, r = s.lin_heads, s.lin_dim, s.gate_rank
    mixed = _mm("sh,hc->sc", h, w["kda_w_qkv"], quant)
    fgb = _mm("sh,hc->sc", h, w["kda_w_fgb"], quant)
    padded = jnp.pad(mixed, ((s.conv - 1, 0), (0, 0)))
    taps = w["kda_conv_w"].astype(jnp.float32)
    mixed = jax.nn.silu(sum(padded[j:j + S] * taps[j]
                            for j in range(s.conv)))
    q, k, v = (mixed[:, j * hh * d:(j + 1) * hh * d].reshape(S, hh, d)
               for j in range(3))
    q, k = _l2(q) * d ** -0.5, _l2(k)
    g = jax.nn.softplus(
        _mm("sr,rc->sc", fgb[:, :r], w["kda_w_fb"], quant)
        + w["kda_dt_bias"].astype(jnp.float32)).reshape(S, hh, d)
    g = -jnp.exp(w["kda_a_log"].astype(jnp.float32))[:, None] * g
    beta = jax.nn.sigmoid(fgb[:, 2 * r:])

    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[:, :, None]
        rest = v_t - jnp.einsum("hk,hkv->hv", k_t, state, precision=_HI)
        state = state + k_t[:, :, None] * (b_t[:, None] * rest)[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", q_t, state, precision=_HI)

    _, o = jax.lax.scan(token, jnp.zeros((hh, d, d), jnp.float32),
                        (q, k, v, g, beta))
    gate = _mm("sr,rc->sc", fgb[:, r:2 * r], w["kda_w_gb"], quant)
    o = _rms(o, w["kda_norm"], s.rms_eps) * jax.nn.sigmoid(
        gate.reshape(S, hh, d))
    return _mm("sc,ch->sh", o.reshape(S, hh * d), w["kda_w_out"], quant)


def latent_attention(s: Sizes, h, w, quant):
    """One latent-attention mixer over h (S, H), expanded: every head's
    keys and values out of the latent row, queries in blocks."""
    S = h.shape[0]
    hq, dn, dr, dv = s.heads, s.nope, s.rope, s.v_dim
    q = _mm("sh,hc->sc", h, w["mla_w_q"], quant).reshape(S, hq, dn + dr)
    a = _mm("sh,hc->sc", h, w["mla_w_a"], quant)
    c_kv = _rms(a[:, :s.rank], w["mla_kv_norm"], s.rms_eps)
    k_r = a[:, s.rank:]
    kv = _mm("sr,rc->sc", c_kv, w["mla_w_b"], quant).reshape(S, hq, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r[:, None, :], (S, hq, dr))],
        axis=-1)
    v = kv[..., dn:]
    pos = jnp.arange(S)
    blk = min(Q_BLOCK, S)
    assert S % blk == 0

    def block(xs):
        q_b, pos_b = xs
        att = jnp.einsum("shd,thd->hst", q_b * (dn + dr) ** -0.5, k,
                         precision=_HI)
        att = jnp.where((pos[None, :] <= pos_b[:, None])[None], att,
                        -jnp.inf)
        return jnp.einsum("hst,thd->shd", jax.nn.softmax(att, axis=-1), v,
                          precision=_HI)

    o = jax.lax.map(block, (q.reshape(S // blk, blk, hq, dn + dr),
                            pos.reshape(S // blk, blk)))
    return _mm("sc,ch->sh", o.reshape(S, hq * dv), w["mla_w_o"], quant)


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


def logits_rows(s: Sizes, w: dict, tokens, first, rows: int,
                quant: Optional[str] = None):
    """Logits (rows, V) float32 at positions first .. first+rows-1 of
    one causal pass over `tokens` (S,) int32. Positions past the real
    sequence are padding: causality keeps them from reaching a row
    before them. ONE scan over the blocks; a block's kind picks its
    mixer and its FFN, and its place among its kind the weights."""
    assert sorted(s.kda_layers + s.full_layers) == list(
        range(1, s.layers + 1))
    is_kda = jnp.asarray([i + 1 in s.kda_layers for i in range(s.layers)])
    is_dense = jnp.arange(s.layers) < s.dense_layers
    # a block's place among the blocks of its kind
    nth_kda = jnp.cumsum(is_kda) - 1
    nth_full = jnp.cumsum(~is_kda) - 1
    nth_dense = jnp.minimum(jnp.arange(s.layers), s.dense_layers - 1)
    nth_moe = jnp.maximum(jnp.arange(s.layers) - s.dense_layers, 0)
    x = w["embed"][tokens].astype(jnp.float32)

    def pick(names, at):
        return {n: jax.lax.dynamic_index_in_dim(w[n], at, keepdims=False)
                for n in names}

    def block(x, xs):
        (ln_in, ln_post, kda_here, dense_here, at_kda, at_full, at_dense,
         at_moe) = xs
        h = _rms(x, ln_in, s.rms_eps)
        x = x + jax.lax.cond(
            kda_here,
            lambda: kda(s, h, pick(KDA, at_kda), quant),
            lambda: latent_attention(s, h, pick(MLA, at_full), quant))
        h = _rms(x, ln_post, s.rms_eps)
        x = x + jax.lax.cond(
            dense_here,
            lambda: _swiglu(h, *pick(DENSE, at_dense).values(), quant),
            lambda: experts(s, h, pick(MOE, at_moe), quant))
        return x, None

    x, _ = jax.lax.scan(block, x, (w["input_ln"], w["post_ln"], is_kda,
                                   is_dense, nth_kda, nth_full, nth_dense,
                                   nth_moe))
    x = jax.lax.dynamic_slice_in_dim(x, first, rows)
    x = _rms(x, w["final_ln"], s.rms_eps)
    return _mm("sh,hv->sv", x, w["lm_head"], quant)


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
