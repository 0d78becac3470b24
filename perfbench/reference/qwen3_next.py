"""Plain reference of the Qwen3-Next decoder, kept with the benchmark.

Straightforward `jax.numpy`, float32 at `highest` matmul precision, one
full causal pass over [prompt + served tokens]: no kernel, no cache, no
paging, no chunked recurrence. It imports nothing of the program and
takes nothing the program has made: the weights are drawn here, from
the seed, by this file's own copy of the leaves' order and shapes (the
program's `models.qwen3_next.leaves` is the original; a seed names the
same tensors in both, and tests/perfbench pins that bit for bit).

The model (the source's `config.json`; H hidden, eps 1e-6, every norm
but the delta net's gated one is x / rms(x) * (1 + w), float32):
block i is `x += Mixer_i(norm(x)); x += MoE(norm(x))`, the mixer full
attention where (i + 1) % full_attention_interval == 0 and a gated
delta net otherwise.
- Gated delta net (Hk key heads, Hv value heads, dk, dv): q, k, v, z =
  x W_qkvz, b, a = x W_ba; q | k | v through a causal depthwise
  convolution of width K, no bias, then SiLU; beta = sigmoid(b), g =
  -exp(A_log) softplus(a + dt_bias); q, k L2-normalised over dk, q
  scaled by dk ** -0.5, both repeated to the value heads; per head, as
  a SCAN OVER TOKENS: S = exp(g_t) S; r = v_t - S^T k_t; S = S + k_t
  (beta_t r)^T; o_t = S^T q_t; then rms(o_t) w silu(z_t), and W_out.
- Gated attention: per head q | gate = x W_q; k, v; per-head norms on q
  and k; rotate-half rotary on the first `partial_rotary_factor` of the
  head's dims; causal grouped-query softmax attention at D ** -0.5,
  computed in blocks of queries; o sigmoid(gate); W_o.
- Experts: p = softmax(x W_r) over ALL routed experts, the k largest,
  their weights divided by their sum; the chip's share is the term of
  each pair whose expert lies in [offset, offset + held), one expert at
  a time over all rows; plus sigmoid(x w_sg) Shared(x).

Departures from the published model: the multi-token-prediction module
is not in the source's `config` and is not served; the column layout
of W_qkvz (q | k | v | z, head-major), W_ba (b | a) and W_q (per head
q | gate) is the builder's; weights are random; the experts held and
the vocabulary are the chip's share (the configuration's `reduced`).

`quant` is the CONTROL, as in `qwen3_dense`: both operands of every
linear layer rounded to the precision named.

What `make_gap_scorer` returns for a configuration whose `check` gives
a `gap_quantile` q under 1: each request's gaps CUT DOWN to the
smallest of them that at least q of them lie within, so that the
widest gap the harness then takes is that quantile of the request.
Why (PERF.md, PR 30, measured on the chip): a router over 512 experts
puts the tenth and the eleventh score 0.026 apart (median), two correct
computations of the hidden state differ by 0.5-2%, so the program's
experts differ from this file's for 8% of the positions in the first
block and 91% in the twelfth, and for all but 3 in 10,000 somewhere.
Most such tokens keep the reference's first choice; a tail of them
does not, by gaps as wide as a random token's (3.2 in 1,500 served
tokens; 0.29 with this file's experts forced on the program). The
widest gap therefore says nothing about the arithmetic here, while the
gap three quarters of a request's tokens lie within does: 0.11-0.27
for the program, 1.6-1.8 for the fp8 control. Without the key (a
float32 configuration, the tests' tiny one) q is 1 and every gap is
returned as it is.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from perfbench.reference import qwen3_dense as dense

_mm, _draw = dense._mm, dense._draw
replicated = dense.replicated
_HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 1024  # query rows of one attention block


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    hidden: int
    layers: int
    period: int
    q_heads: int
    kv_heads: int
    head_dim: int
    rotary: int
    lin_k_heads: int
    lin_v_heads: int
    lin_k_dim: int
    lin_v_dim: int
    conv: int
    routed: int
    held: int
    offset: int
    per_token: int
    expert_inter: int
    shared_inter: int
    rope_theta: float
    rms_eps: float
    max_len: int
    dtype: str
    gap_quantile: float = 1.0

    @staticmethod
    def from_config(cfg: dict) -> "Sizes":
        ep = cfg["expert_parallel"]
        return Sizes(
            vocab=cfg["vocab_size"], hidden=cfg["hidden_size"],
            layers=cfg["num_hidden_layers"],
            period=cfg["full_attention_interval"],
            q_heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            rotary=int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
            lin_k_heads=cfg["linear_num_key_heads"],
            lin_v_heads=cfg["linear_num_value_heads"],
            lin_k_dim=cfg["linear_key_head_dim"],
            lin_v_dim=cfg["linear_value_head_dim"],
            conv=cfg["linear_conv_kernel_dim"],
            routed=ep["router_width"], held=cfg["num_experts"],
            offset=ep["expert_offset"],
            per_token=cfg["num_experts_per_tok"],
            expert_inter=cfg["moe_intermediate_size"],
            shared_inter=cfg["shared_expert_intermediate_size"],
            rope_theta=float(cfg["rope_theta"]),
            rms_eps=cfg["rms_norm_eps"], max_len=cfg["serve"]["max_len"],
            dtype=cfg["torch_dtype"],
            gap_quantile=float(
                cfg.get("check", {}).get("gap_quantile", 1.0)))

    @property
    def channels(self) -> int:
        return 2 * self.lin_k_heads * self.lin_k_dim \
            + self.lin_v_heads * self.lin_v_dim


BLOCK = ("input_ln", "post_ln", "w_router", "w_gate_up", "w_down",
         "ws_gate_up", "ws_down", "w_sgate")
GDN = ("w_qkvz", "w_ba", "conv_w", "a_log", "dt_bias", "gdn_norm", "w_out")
ATTN = ("w_q", "w_kv", "q_norm", "k_norm", "w_o")


# (name, shape, init) in the order that fixes each leaf's key,
# fold_in(PRNGKey(seed), position); a gain starts at its identity
def _leaves(s: Sizes):
    L, h, v = s.layers, s.hidden, s.vocab
    lf = L // s.period
    ll = L - lf
    vw = s.lin_v_heads * s.lin_v_dim
    i, ish, d = s.expert_inter, s.shared_inter, s.head_dim
    return (
        ("embed", (v, h), "normal"),
        ("final_ln", (h,), "zeros"),
        ("lm_head", (h, v), "normal"),
        ("input_ln", (L, h), "zeros"),
        ("post_ln", (L, h), "zeros"),
        ("w_router", (L, h, s.routed), "normal"),
        ("w_gate_up", (L, s.held, h, 2 * i), "normal"),
        ("w_down", (L, s.held, i, h), "normal"),
        ("ws_gate_up", (L, h, 2 * ish), "normal"),
        ("ws_down", (L, ish, h), "normal"),
        ("w_sgate", (L, h), "normal"),
        ("w_qkvz", (ll, h, s.channels + vw), "normal"),
        ("w_ba", (ll, h, 2 * s.lin_v_heads), "normal"),
        ("conv_w", (ll, s.conv, s.channels), "normal"),
        ("a_log", (ll, s.lin_v_heads), "normal"),
        ("dt_bias", (ll, s.lin_v_heads), "normal"),
        ("gdn_norm", (ll, s.lin_v_dim), "ones"),
        ("w_out", (ll, vw, h), "normal"),
        ("w_q", (lf, h, s.q_heads * 2 * d), "normal"),
        ("w_kv", (lf, h, 2 * s.kv_heads * d), "normal"),
        ("q_norm", (lf, d), "zeros"),
        ("k_norm", (lf, d), "zeros"),
        ("w_o", (lf, s.q_heads * d, h), "normal"),
    )


def draw_weights(s: Sizes, n: int, seed: int, devices) -> dict:
    """The weight set that `seed` names; the family runs one chip of
    its group, so `n` is 1."""
    if n != 1:
        raise ValueError(f"this family has no tensor-parallel form (tp={n})")
    dt = jnp.dtype(s.dtype)
    const = {"zeros": jnp.zeros, "ones": jnp.ones}

    def draw(key):
        return {name: const[init](shape, dt) if init != "normal"
                else _draw(jax.random.fold_in(key, i), shape, dt)
                for i, (name, shape, init) in enumerate(_leaves(s))}

    with jax.default_device(list(devices)[0]):
        return jax.jit(draw)(jax.random.PRNGKey(seed))


def _rms(x, gain, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain.astype(jnp.float32)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _rope_first(x, positions, theta, rot):
    """Rotate-half rotary on the first `rot` dims of x (S, heads, D)."""
    half = rot // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    c, sn = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate(
        [x1 * c - x2 * sn, x2 * c + x1 * sn, x[..., rot:]], axis=-1)


def delta_net(s: Sizes, h, w, quant):
    """One gated-delta-net mixer over h (S, H): the recurrence as a
    scan over tokens from zero state."""
    S = h.shape[0]
    hk, hv, dk, dv = s.lin_k_heads, s.lin_v_heads, s.lin_k_dim, s.lin_v_dim
    ch = s.channels
    qkvz = _mm("sh,hc->sc", h, w["w_qkvz"], quant)
    ba = _mm("sh,hc->sc", h, w["w_ba"], quant)
    mixed, z = qkvz[:, :ch], qkvz[:, ch:]
    padded = jnp.pad(mixed, ((s.conv - 1, 0), (0, 0)))
    taps = w["conv_w"].astype(jnp.float32)
    mixed = jax.nn.silu(sum(padded[j:j + S] * taps[j]
                            for j in range(s.conv)))
    q = mixed[:, :hk * dk].reshape(S, hk, dk)
    k = mixed[:, hk * dk:2 * hk * dk].reshape(S, hk, dk)
    v = mixed[:, 2 * hk * dk:].reshape(S, hv, dv)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(w["a_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[:, hv:] + w["dt_bias"].astype(jnp.float32))
    q = jnp.repeat(_l2(q) * dk ** -0.5, hv // hk, axis=1)
    k = jnp.repeat(_l2(k), hv // hk, axis=1)

    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[:, None, None]
        r = v_t - jnp.einsum("hk,hkv->hv", k_t, state, precision=_HI)
        state = state + k_t[:, :, None] * (b_t[:, None] * r)[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", q_t, state, precision=_HI)

    _, o = jax.lax.scan(token, jnp.zeros((hv, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    o = _rms(o, w["gdn_norm"], s.rms_eps) * jax.nn.silu(
        z.reshape(S, hv, dv))
    return _mm("sc,ch->sh", o.reshape(S, hv * dv), w["w_out"], quant)


def attention(s: Sizes, h, w, pos, quant):
    """One gated-attention mixer over h (S, H), queries in blocks."""
    S = h.shape[0]
    hq, hkv, d = s.q_heads, s.kv_heads, s.head_dim
    grp = hq // hkv
    qg = _mm("sh,hc->sc", h, w["w_q"], quant).reshape(S, hq, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    kv = _mm("sh,hc->sc", h, w["w_kv"], quant)
    k = kv[:, :hkv * d].reshape(S, hkv, d)
    v = kv[:, hkv * d:].reshape(S, hkv, d)
    q = _rope_first(_rms(q, 1.0 + w["q_norm"].astype(jnp.float32), s.rms_eps),
                    pos, s.rope_theta, s.rotary)
    k = _rope_first(_rms(k, 1.0 + w["k_norm"].astype(jnp.float32), s.rms_eps),
                    pos, s.rope_theta, s.rotary)
    blk = min(Q_BLOCK, S)
    assert S % blk == 0

    def block(xs):
        q_b, pos_b = xs
        att = jnp.einsum("sjgd,tjd->jgst",
                         q_b.reshape(blk, hkv, grp, d) * d ** -0.5, k,
                         precision=_HI)
        att = jnp.where((pos[None, :] <= pos_b[:, None])[None, None], att,
                        -jnp.inf)
        return jnp.einsum("jgst,tjd->sjgd", jax.nn.softmax(att, axis=-1),
                          v, precision=_HI)

    o = jax.lax.map(block, (q.reshape(S // blk, blk, hq, d),
                            pos.reshape(S // blk, blk)))
    o = o.reshape(S, hq, d) * jax.nn.sigmoid(gate)
    return _mm("sc,ch->sh", o.reshape(S, hq * d), w["w_o"], quant)


def experts(s: Sizes, h, w, quant):
    """The chip's share of the expert layer over h (S, H), plus the
    shared expert: routing over all, one held expert at a time."""
    S = h.shape[0]
    i = s.expert_inter
    probs = jax.nn.softmax(_mm("sh,he->se", h, w["w_router"], quant), -1)
    top, ids = jax.lax.top_k(probs, s.per_token)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    combine = jnp.zeros((S, s.routed), jnp.float32).at[
        jnp.arange(S)[:, None], ids].set(top)
    mine = combine[:, s.offset:s.offset + s.held]

    def one(acc, xs):
        w_gu, w_dn, c = xs
        gu = _mm("sh,hc->sc", h, w_gu, quant)
        y = _mm("si,ih->sh", jax.nn.silu(gu[:, :i]) * gu[:, i:], w_dn,
                quant)
        return acc + c[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (w["w_gate_up"], w["w_down"], mine.T))
    ish = s.shared_inter
    gu = _mm("sh,hc->sc", h, w["ws_gate_up"], quant)
    shared = _mm("si,ih->sh", jax.nn.silu(gu[:, :ish]) * gu[:, ish:],
                 w["ws_down"], quant)
    gate = jax.nn.sigmoid(_mm("sh,h->s", h, w["w_sgate"], quant))
    return out + gate[:, None] * shared


def logits_rows(s: Sizes, w: dict, tokens, first, rows: int,
                quant: Optional[str] = None):
    """Logits (rows, V) float32 at positions first .. first+rows-1 of
    one causal pass over `tokens` (S,) int32. Positions past the real
    sequence are padding: causality keeps them from reaching a row
    before them."""
    S = tokens.shape[0]
    per = s.period
    pos = jnp.arange(S)
    x = w["embed"][tokens].astype(jnp.float32)

    def gain(g):
        return 1.0 + g.astype(jnp.float32)

    def period(x, xs):
        blk, lin, att = xs
        for j in range(per):
            b_j = {n: blk[n][j] for n in BLOCK}
            h = _rms(x, gain(b_j["input_ln"]), s.rms_eps)
            if j < per - 1:
                x = x + delta_net(s, h, {n: lin[n][j] for n in GDN}, quant)
            else:
                x = x + attention(s, h, att, pos, quant)
            h = _rms(x, gain(b_j["post_ln"]), s.rms_eps)
            x = x + experts(s, h, b_j, quant)
        return x, None

    def by_period(names, n):
        return {k: w[k].reshape((-1, n) + w[k].shape[1:]) for k in names}

    x, _ = jax.lax.scan(period, x, (by_period(BLOCK, per),
                                    by_period(GDN, per - 1),
                                    {k: w[k] for k in ATTN}))
    x = jax.lax.dynamic_slice_in_dim(x, first, rows)
    x = _rms(x, gain(w["final_ln"]), s.rms_eps)
    return _mm("sh,hv->sv", x, w["lm_head"], quant)


def make_scorer(s: Sizes, width: int, rows: int,
                quant: Optional[str] = None):
    """jitted (weights, tokens (width,), first) -> (rows, V) logits."""
    return jax.jit(lambda w, tokens, first: logits_rows(
        s, w, tokens, first, rows, quant))


def cut_to_quantile(gaps, scored_rows, q: float):
    """`gaps` (rows,) cut down to the smallest gap of `scored_rows`
    (rows,) bool that at least q of those rows' gaps lie within."""
    n = jnp.sum(scored_rows)
    ordered = jnp.sort(jnp.where(scored_rows, gaps, jnp.inf))
    at = jnp.clip(jnp.ceil(q * n).astype(jnp.int32) - 1, 0,
                  gaps.shape[0] - 1)
    return jnp.minimum(gaps, ordered[at])


def make_gap_scorer(s: Sizes, width: int, rows: int):
    """jitted (weights, tokens (width,), first, scored (rows,)) ->
    (rows,) float32: how far the logit of scored[j] lies under the
    reference's best at position first + j (0 where it IS the best);
    under a `gap_quantile` below 1, cut to that quantile of the
    request's served rows (module doc). The harness hands no count of
    the served tokens, so the served rows are found as it lays them
    out: row j scores the token at position first + j + 1, a served
    one while that position lies before the sequence's zero padding
    (a served token 0 at the very end falls out of the count, and is
    still cut); a row whose `scored` is the padding's 0 where the
    sequence holds a prompt token is a row before the first served
    one (with the CONTROL's choices in `scored` such rows count too,
    where a prompt reaches past width - rows: they are the control's
    choices as well)."""
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
