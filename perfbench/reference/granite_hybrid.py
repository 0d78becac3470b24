"""Plain reference of the Granite 4.0-H decoder (`granitemoehybrid`
without an expert layer), kept with the benchmark.

Straightforward `jax.numpy`, float32 at `highest` matmul precision, one
full causal pass over [prompt + served tokens]: no kernel, no cache, no
carried state, no paging, no batching, and the state-space mixer as the
RECURRENCE itself, one position after another (`lax.scan` over
positions), never the chunked form the program computes. It imports
nothing of the program and takes nothing the program has made: the
weights are drawn here, from the seed, by this file's own copy of the
leaves' order, shapes and initialisations (the program's
`models.hybrid.leaves` is the original; a seed names the same tensors
in both, and tests pin that bit for bit).

The model (the source's `config.json`, the published Mamba-2 / Bamba /
GraniteMoeHybrid description; what the `config` does not state is under
`assumed` in the configuration's file). H hidden; RMSNorm
x / rms(x) * w with eps `rms_norm_eps`; with m_e, m_r, m_a, m_l the
four multipliers:

    x = m_e E[tokens]
    block i:  x = x + m_r Mixer_i(norm(x));  x = x + m_r SwiGLU(norm(x))
    logits = norm(x) E^T / m_l                         (tied: no head)

- SwiGLU(h) = (silu(h W_g) * (h W_u)) W_d of `shared_intermediate_size`
  in EVERY block (no router, no experts).
- "attention": q, k, v = h W_q, h W_k, h W_v (32 / 8 / 8 heads of
  hidden_size / num_attention_heads = 64), no bias, no rotary, no q/k
  norm; score(i, j) = m_a q_i . k_j over j <= i; softmax; W_o.
- "mamba": d_inner = heads x P channels, a state of N a channel, one
  group (B_t, C_t in R^N shared by the heads):
      z | xBC | dt = h W_in
      xBC = silu(conv1d_causal(xBC; w, bias));  x | B | C = xBC
      dt = softplus(dt + dt_bias);  a = -exp(A_log)        (a head each)
      S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T           (from S = 0)
      y_t = S_t C_t + D x_t
      out = rmsnorm(y * silu(z); gain (d_inner,)) W_out

Departures from the published model: weights are random (N(0, 0.02)
but for the embedding, N(0, 0.02 / m_e), so that m_e E[token] enters
the stream at 0.02 and the tied head does not score the last token
m_e |E|^2 over every other (at 0.02: 10.9 standard deviations of a
logit over the stream's rms, an echo of the input), and for
`A_log`, `dt_bias`, `D` and the convolution, which follow
Mamba-2's published initialisation: a uniform in [1, 16], the step
log-uniform in [1e-3, 1e-1] through the inverse softplus, D = 1, the
convolution's taps and bias uniform within K ** -0.5 of 0); the
column layout (W_in z | x | B | C | dt, W_kv k | v, the MLP's
gate | up, every projection head-major) is the builder's.

`quant` is the CONTROL, as in `qwen3_dense`: both operands of every
linear layer (the tied head's among them) rounded; the recurrence and
the attention products stay float32. `gap_quantile` as in `qwen3_next`.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from perfbench.reference import qwen3_dense as dense
from perfbench.reference.qwen3_next import cut_to_quantile

_mm, _draw, _rms = dense._mm, dense._draw, dense._rms
replicated = dense.replicated
_HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 256  # query rows of one attention block


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    hidden: int
    layers: int
    attends: Tuple[bool, ...]  # a block: attention (else state-space)
    inter: int
    q_heads: int
    kv_heads: int
    head_dim: int
    m_heads: int
    m_head_dim: int
    m_state: int
    m_conv: int
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
        L = cfg["num_hidden_layers"]
        kinds = {"attention": True, "mamba": False}
        assert len(cfg["layer_types"]) == L
        assert cfg["num_local_experts"] == 0 and cfg["mamba_n_groups"] == 1
        assert cfg["position_embedding_type"] == "nope"
        assert cfg["tie_word_embeddings"] and cfg["mamba_conv_bias"]
        assert not cfg["mamba_proj_bias"] and not cfg["attention_bias"]
        assert (cfg["mamba_n_heads"] * cfg["mamba_d_head"]
                == cfg["mamba_expand"] * cfg["hidden_size"])
        return Sizes(
            vocab=cfg["vocab_size"], hidden=cfg["hidden_size"], layers=L,
            attends=tuple(kinds[t] for t in cfg["layer_types"]),
            inter=cfg["shared_intermediate_size"],
            q_heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
            m_heads=cfg["mamba_n_heads"], m_head_dim=cfg["mamba_d_head"],
            m_state=cfg["mamba_d_state"], m_conv=cfg["mamba_d_conv"],
            m_e=float(cfg["embedding_multiplier"]),
            m_r=float(cfg["residual_multiplier"]),
            m_a=float(cfg["attention_multiplier"]),
            m_l=float(cfg["logits_scaling"]),
            rms_eps=cfg["rms_norm_eps"], max_len=cfg["serve"]["max_len"],
            dtype=cfg["torch_dtype"],
            gap_quantile=float(
                cfg.get("check", {}).get("gap_quantile", 1.0)))


DENSE = ("wd_gate_up", "wd_down")
ATTN = ("attn_w_q", "attn_w_kv", "attn_w_o")
MAMBA = ("m2_w_in", "m2_conv_w", "m2_conv_b", "m2_a_log", "m2_dt_bias",
         "m2_d", "m2_norm", "m2_w_out")


# (name, shape, init) in the order that fixes each leaf's key,
# fold_in(PRNGKey(seed), position); every gain starts at 1, and so
# does D
def _leaves(s: Sizes):
    L, h = s.layers, s.hidden
    lf = sum(s.attends)
    ls = L - lf
    hq, hkv, d = s.q_heads, s.kv_heads, s.head_dim
    di, ch = s.inner, s.channels
    return (
        ("embed", (s.vocab, h), "embed"),
        ("final_ln", (h,), "ones"),
        ("input_ln", (L, h), "ones"),
        ("post_ln", (L, h), "ones"),
        ("wd_gate_up", (L, h, 2 * s.inter), "normal"),
        ("wd_down", (L, s.inter, h), "normal"),
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


def _draw_leaf(key, shape, init: str, dt, taps: int = 4, m_e: float = 1.0):
    if init == "ones":
        return jnp.ones(shape, dt)
    if init == "normal":
        return _draw(key, shape, dt)
    if init == "embed":  # N(0, 0.02 / m_e): the stream receives N(0, 0.02)
        return (_draw(key, shape, jnp.float32) / m_e).astype(dt)
    u = jax.random.uniform(key, shape, jnp.float32)
    if init == "conv":  # within taps ** -0.5 of 0
        return ((2.0 * u - 1.0) * taps ** -0.5).astype(dt)
    if init == "a_log":  # a uniform in [1, 16]
        return jnp.log(1.0 + 15.0 * u).astype(dt)
    # the step log-uniform in [1e-3, 1e-1], through the inverse softplus
    step = jnp.exp(u * math.log(100.0) + math.log(1e-3))
    return (step + jnp.log(-jnp.expm1(-step))).astype(dt)


def draw_weights(s: Sizes, n: int, seed: int, devices) -> dict:
    """The weight set that `seed` names; the family runs whole on one
    chip, so `n` is 1."""
    if n != 1:
        raise ValueError(f"this family has no tensor-parallel form (tp={n})")
    assert 0 < sum(s.attends) < s.layers, "both kinds hold a position"
    dt = jnp.dtype(s.dtype)

    def draw(key):
        return {name: _draw_leaf(jax.random.fold_in(key, i), shape, init, dt,
                                 s.m_conv, s.m_e)
                for i, (name, shape, init) in enumerate(_leaves(s))}

    with jax.default_device(list(devices)[0]):
        return jax.jit(draw)(jax.random.PRNGKey(seed))


def _swiglu(h, w_gu, w_dn, quant):
    gu = _mm("sh,hc->sc", h, w_gu, quant)
    i = gu.shape[-1] // 2
    return _mm("si,ih->sh", jax.nn.silu(gu[:, :i]) * gu[:, i:], w_dn, quant)


def attention(s: Sizes, h, w, quant):
    """One attention mixer over h (S, H): no rotary, no q/k norm, the
    scores under `attention_multiplier`."""
    S = h.shape[0]
    hq, hkv, d = s.q_heads, s.kv_heads, s.head_dim
    g = hq // hkv
    pos = jnp.arange(S)
    q = _mm("sh,hc->sc", h, w["attn_w_q"], quant).reshape(S, hq, d)
    kv = _mm("sh,hc->sc", h, w["attn_w_kv"], quant)
    k = kv[:, :hkv * d].reshape(S, hkv, d)
    v = kv[:, hkv * d:].reshape(S, hkv, d)
    blk = min(Q_BLOCK, S)
    assert S % blk == 0

    def block(xs):
        q_b, pos_b = xs
        att = jnp.einsum("sjgd,tjd->jgst",
                         q_b.reshape(blk, hkv, g, d) * s.m_a, k,
                         precision=_HI)
        seen = pos[None, :] <= pos_b[:, None]
        att = jnp.where(seen[None, None], att, -jnp.inf)
        return jnp.einsum("jgst,tjd->sjgd", jax.nn.softmax(att, axis=-1), v,
                          precision=_HI).reshape(blk, hq * d)

    o = jax.lax.map(block, (q.reshape(S // blk, blk, hq, d),
                            pos.reshape(S // blk, blk)))
    return _mm("sc,ch->sh", o.reshape(S, hq * d), w["attn_w_o"], quant)


def mamba(s: Sizes, h, w, quant):
    """One state-space mixer over h (S, H): the recurrence, position by
    position from a zero state."""
    S = h.shape[0]
    hh, p, n, di, ch = s.m_heads, s.m_head_dim, s.m_state, s.inner, s.channels
    f32 = jnp.float32
    proj = _mm("sh,hc->sc", h, w["m2_w_in"], quant)
    z, xbc, dt = proj[:, :di], proj[:, di:di + ch], proj[:, di + ch:]
    # tap j multiplies the input K-1-j back; nothing before position 0
    taps = w["m2_conv_w"].astype(f32)
    past = jnp.concatenate([jnp.zeros((s.m_conv - 1, ch), f32), xbc])
    xbc = jax.nn.silu(sum(past[j:j + S] * taps[j] for j in range(s.m_conv))
                      + w["m2_conv_b"].astype(f32))
    x = xbc[:, :di].reshape(S, hh, p)
    b_in, c_in = xbc[:, di:di + n], xbc[:, di + n:]
    dt = jax.nn.softplus(dt + w["m2_dt_bias"].astype(f32))  # (S, heads)
    a = -jnp.exp(w["m2_a_log"].astype(f32))

    def step(state, xs):
        x_t, b_t, c_t, dt_t = xs
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t)
        return state, jnp.sum(state * c_t, axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((hh, p, n), f32),
                        (x, b_in, c_in, dt))
    y = y + w["m2_d"].astype(f32)[:, None] * x
    y = _rms(y.reshape(S, di) * jax.nn.silu(z), w["m2_norm"], s.rms_eps)
    return _mm("sc,ch->sh", y, w["m2_w_out"], quant)


def hidden_rows(s: Sizes, w: dict, tokens, quant: Optional[str] = None):
    """The residual stream (S, H) float32 after the last block of one
    causal pass over `tokens` (S,) int32. ONE scan over the blocks; a
    block's kind picks its mixer, and its place among its kind the
    weights."""
    attends = jnp.asarray(s.attends)
    nth_attn = jnp.maximum(jnp.cumsum(attends) - 1, 0)
    nth_mamba = jnp.maximum(jnp.cumsum(~attends) - 1, 0)
    x = s.m_e * w["embed"][tokens].astype(jnp.float32)

    def pick(names, at):
        return {n: jax.lax.dynamic_index_in_dim(w[n], at, keepdims=False)
                for n in names}

    def block(x, xs):
        ln_in, ln_post, w_gu, w_dn, attn_here, at_attn, at_mamba = xs
        h = _rms(x, ln_in, s.rms_eps)
        x = x + s.m_r * jax.lax.cond(
            attn_here,
            lambda: attention(s, h, pick(ATTN, at_attn), quant),
            lambda: mamba(s, h, pick(MAMBA, at_mamba), quant))
        h = _rms(x, ln_post, s.rms_eps)
        return x + s.m_r * _swiglu(h, w_gu, w_dn, quant), None

    x, _ = jax.lax.scan(block, x, (
        w["input_ln"], w["post_ln"], w["wd_gate_up"], w["wd_down"], attends,
        nth_attn, nth_mamba))
    return x


def logits_rows(s: Sizes, w: dict, tokens, first, rows: int,
                quant: Optional[str] = None):
    """Logits (rows, V) float32 at positions first .. first+rows-1 of
    one causal pass over `tokens` (S,) int32. Positions past the real
    sequence are padding: causality keeps them from reaching a row
    before them."""
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
