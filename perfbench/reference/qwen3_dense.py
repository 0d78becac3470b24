"""Plain reference of the Qwen3 dense decoder, kept with the benchmark.

Straightforward `jax.numpy`, float32 at `highest` matmul precision, one
full causal pass over [prompt + served tokens]: no kernel, no cache, no
batching, no paging. It imports nothing of the program and takes
nothing the program has made: the weights are drawn here, from the
seed, by this file's own copy of the arithmetic that defines what a
seed means (`draw_weights`; the program's `models.dense._init_on_mesh`
is the original — a seed names the same bfloat16 tensors in both, and
tests/perfbench pins that bit for bit).

Layout of a weight set (`n` = the tensor-parallel degree the seed's
meaning depends on; the mathematics contracts over it):
  embed (V, H) · final_ln (H,) · lm_head (n, H, V/n)
  input_ln, post_attn_ln (L, H) · q_norm, k_norm (L, D)
  w_qkv (L, n, H, (Hq/n + 2 Hkv/n) D)  q | k | v column blocks per rank
  w_o (L, n, Hq/n D, H) · w_gate, w_up (L, n, H, I/n) · w_down (L, n, I/n, H)

Departures from the published model: none in the mathematics (RMSNorm,
per-head q/k RMSNorm before rope, half-split rope at theta, grouped
causal attention, SwiGLU, untied head). Weights are random.

`quant="fp8"` is the CONTROL, not the reference: the same pass with
both operands of every linear layer rounded to float8_e4m3fn under a
per-tensor scale, the precision a later PR would be tempted by below
the configuration's bfloat16. `quant="bf16"` rounds them to bfloat16
(the control of a float32 configuration, used by the CPU tests).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS = "tp"
INIT_SCALE = 0.02
DRAW_ELEMS = 1 << 26


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    hidden: int
    inter: int
    layers: int
    q_heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    rms_eps: float
    max_len: int
    dtype: str

    @staticmethod
    def from_config(cfg: dict) -> "Sizes":
        """From a configuration file's published keys."""
        return Sizes(
            vocab=cfg["vocab_size"], hidden=cfg["hidden_size"],
            inter=cfg["intermediate_size"],
            layers=cfg["num_hidden_layers"],
            q_heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"], rope_theta=cfg["rope_theta"],
            rms_eps=cfg["rms_norm_eps"], max_len=cfg["serve"]["max_len"],
            dtype=cfg["torch_dtype"])


# (name, global shape, tp-sharded dim or None, is a norm gain) in the
# order that fixes each leaf's key: fold_in(PRNGKey(seed), position)
def _leaves(s: Sizes, n: int):
    L, h, d = s.layers, s.hidden, s.head_dim
    hq, hkv, i = s.q_heads // n, s.kv_heads // n, s.inter // n
    return (
        ("embed", (s.vocab, h), None, False),
        ("final_ln", (h,), None, True),
        ("lm_head", (n, h, s.vocab // n), 0, False),
        ("input_ln", (L, h), None, True),
        ("post_attn_ln", (L, h), None, True),
        ("w_qkv", (L, n, h, (hq + 2 * hkv) * d), 1, False),
        ("w_o", (L, n, hq * d, h), 1, False),
        ("q_norm", (L, d), None, True),
        ("k_norm", (L, d), None, True),
        ("w_down", (L, n, i, h), 1, False),
        ("w_gate", (L, n, h, i), 1, False),
        ("w_up", (L, n, h, i), 1, False),
    )


def _draw(key, shape, dt):
    """N(0, INIT_SCALE) in slabs of at most DRAW_ELEMS elements along
    the leading dim; the slab structure is part of what a seed means."""
    total = int(np.prod(shape))
    if total <= DRAW_ELEMS or len(shape) == 1:
        return (jax.random.normal(key, shape, jnp.float32)
                * INIT_SCALE).astype(dt)
    lead, rest = shape[0], shape[1:]
    c = max((c for c in range(1, lead + 1)
             if lead % c == 0 and c * (total // lead) <= DRAW_ELEMS),
            default=0)
    if c:
        def slab(k):
            return _draw(k, (c,) + rest, dt)
    else:
        c = 1

        def slab(k):
            return _draw(k, rest, dt)
    return jax.lax.map(slab, jax.random.split(key, lead // c)).reshape(
        shape)


def draw_weights(s: Sizes, n: int, seed: int, devices) -> dict:
    """The weight set that `seed` names at tensor-parallel degree `n`,
    each rank's shard drawn on its own device in one jitted call."""
    mesh = Mesh(np.asarray(list(devices)[:n]), (AXIS,))
    leaves = _leaves(s, n)
    dt = jnp.dtype(s.dtype)

    def spec_of(shape, dim):
        return P() if dim is None else P(*([None] * dim + [AXIS]))

    def per_rank(key):
        rank = jax.lax.axis_index(AXIS)
        out = {}
        for i, (name, shape, dim, is_norm) in enumerate(leaves):
            local = tuple(1 if j == dim else x for j, x in enumerate(shape))
            if is_norm:
                out[name] = jnp.ones(local, dt)
                continue
            k = jax.random.fold_in(key, i)
            if local != shape:  # at n == 1 no leaf is rank-folded
                k = jax.random.fold_in(k, rank)
            out[name] = _draw(k, local, dt)
        return out

    specs = {name: spec_of(shape, dim) for name, shape, dim, _ in leaves}
    return jax.jit(jax.shard_map(
        per_rank, mesh=mesh, in_specs=P(), out_specs=specs,
        check_vma=False))(jax.random.PRNGKey(seed))


def _rms(x, gain, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain.astype(jnp.float32)


def _rope(x, positions, theta):
    """x (S, heads, D); half-split convention."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    c, sn = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], axis=-1)


def _qdq(x, quant: Optional[str]):
    """Round a matmul operand to the control's precision (no-op for
    the reference itself)."""
    if quant is None:
        return x
    if quant == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if quant == "fp8":
        scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
        return (x / scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) * scale
    raise ValueError(f"unknown control precision {quant!r}")


def _mm(eq, a, b, quant):
    return jnp.einsum(eq, _qdq(a, quant), _qdq(b.astype(jnp.float32), quant),
                      precision=jax.lax.Precision.HIGHEST)


def logits_rows(s: Sizes, w: dict, tokens, first, rows: int,
                quant: Optional[str] = None):
    """Logits (rows, V) float32 at positions first .. first+rows-1 of
    one causal pass over `tokens` (S,) int32. Positions past the real
    sequence are padding: causality keeps them from reaching a row
    before them."""
    n = w["w_qkv"].shape[1]
    S = tokens.shape[0]
    hq, hkv, d = s.q_heads, s.kv_heads, s.head_dim
    hq_l, hkv_l = hq // n, hkv // n
    g = hq // hkv
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]
    x = w["embed"][tokens].astype(jnp.float32)

    def layer(x, lw):
        h = _rms(x, lw["input_ln"], s.rms_eps)
        qkv = _mm("sh,nhc->snc", h, lw["w_qkv"], quant)
        q = qkv[..., :hq_l * d].reshape(S, hq, d)
        k = qkv[..., hq_l * d:(hq_l + hkv_l) * d].reshape(S, hkv, d)
        v = qkv[..., (hq_l + hkv_l) * d:].reshape(S, hkv, d)
        q = _rope(_rms(q, lw["q_norm"], 1e-6), pos, s.rope_theta)
        k = _rope(_rms(k, lw["k_norm"], 1e-6), pos, s.rope_theta)
        qg = q.reshape(S, hkv, g, d) * d ** -0.5
        att = jnp.einsum("sjgd,tjd->jgst", qg, k,
                         precision=jax.lax.Precision.HIGHEST)
        att = jnp.where(causal[None, None], att, -jnp.inf)
        att = jax.nn.softmax(att, axis=-1)
        o = jnp.einsum("jgst,tjd->sjgd", att, v,
                       precision=jax.lax.Precision.HIGHEST)
        x = x + _mm("snc,nch->sh", o.reshape(S, n, hq_l * d), lw["w_o"],
                    quant)
        h = _rms(x, lw["post_attn_ln"], s.rms_eps)
        gate = _mm("sh,nhi->sni", h, lw["w_gate"], quant)
        up = _mm("sh,nhi->sni", h, lw["w_up"], quant)
        x = x + _mm("sni,nih->sh", jax.nn.silu(gate) * up, lw["w_down"],
                    quant)
        return x, None

    per_layer = {k: w[k] for k in (
        "input_ln", "post_attn_ln", "w_qkv", "w_o", "q_norm", "k_norm",
        "w_down", "w_gate", "w_up")}
    x, _ = jax.lax.scan(layer, x, per_layer)
    x = jax.lax.dynamic_slice_in_dim(x, first, rows)
    x = _rms(x, w["final_ln"], s.rms_eps)
    out = _mm("sh,nhv->snv", x, w["lm_head"], quant)
    return out.reshape(rows, s.vocab)


def make_scorer(s: Sizes, width: int, rows: int,
                quant: Optional[str] = None):
    """jitted (weights, tokens (width,), first) -> (rows, V) logits."""
    return jax.jit(lambda w, tokens, first: logits_rows(
        s, w, tokens, first, rows, quant))


def make_gap_scorer(s: Sizes, width: int, rows: int):
    """jitted (weights, tokens (width,), first, scored (rows,)) ->
    (rows,) float32: how far the logit of scored[j] lies under the
    reference's best at position first + j (0 where it IS the best)."""
    def fn(w, tokens, first, scored):
        logits = logits_rows(s, w, tokens, first, rows)
        got = jnp.take_along_axis(logits, scored[:, None], axis=1)[:, 0]
        return jnp.max(logits, axis=1) - got

    return jax.jit(fn)


def make_top_scorer(s: Sizes, width: int, rows: int, quant: str):
    """jitted (weights, tokens, first) -> (rows,) int32: the token the
    CONTROL precision puts first at each position."""
    return jax.jit(lambda w, tokens, first: jnp.argmax(
        logits_rows(s, w, tokens, first, rows, quant),
        axis=1).astype(jnp.int32))


def replicated(x, devices, n: int):
    mesh = Mesh(np.asarray(list(devices)[:n]), (AXIS,))
    return jax.device_put(x, NamedSharding(mesh, P()))
