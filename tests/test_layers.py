"""Layer tests: TP MLP / TP Attn mode parity + building-block units.

Analog of the reference's layer tests (ref:
python/triton_dist/test/nvidia/test_tp_mlp.py, test_tp_attn.py): each dist
mode is checked against the unfused xla parity mode and against a dense
single-device reference.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.layers import (
    PPCommOp,
    TPAttnParams,
    TPAttnSpec,
    TPMLPParams,
    apply_rope,
    gqa_attention,
    pp_schedule_fwd,
    rms_norm,
    rope_table,
    tp_attn_fwd,
    tp_mlp_fwd,
)

TP = 8


def _rand(rng, shape, dtype=jnp.float32, scale=0.1):
    return jnp.asarray(rng.standard_normal(shape) * scale, dtype)


# ---------- building blocks ----------


def test_rms_norm_matches_numpy():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((4, 32)).astype(np.float32)
    w = rng.standard_normal((32,)).astype(np.float32)
    got = np.asarray(rms_norm(jnp.asarray(x), jnp.asarray(w)))
    ref = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * w
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_rope_rotation_preserves_norm_and_is_position_dependent():
    cos, sin = rope_table(64, 128)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, 5, 2, 64)), jnp.float32)
    pos = jnp.arange(5)[None, :]
    y = apply_rope(x, cos, sin, pos)
    # rotation preserves the per-head L2 norm
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(y), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1),
        rtol=1e-5,
    )
    # position 0 is identity
    np.testing.assert_allclose(
        np.asarray(y[:, 0]), np.asarray(x[:, 0]), rtol=1e-5, atol=1e-6
    )
    # relative-position property: scores depend only on distance
    q = apply_rope(x, cos, sin, pos)
    k = apply_rope(x, cos, sin, pos)
    s1 = np.asarray(jnp.einsum("bshd,bthd->bhst", q, k))
    pos2 = pos + 7
    q2 = apply_rope(x, cos, sin, pos2)
    k2 = apply_rope(x, cos, sin, pos2)
    s2 = np.asarray(jnp.einsum("bshd,bthd->bhst", q2, k2))
    np.testing.assert_allclose(s1, s2, rtol=1e-4, atol=1e-4)


def test_gqa_attention_matches_naive():
    rng = np.random.default_rng(0)
    b, s, hq, hkv, d = 2, 8, 4, 2, 16
    q = _rand(rng, (b, s, hq, d))
    k = _rand(rng, (b, s, hkv, d))
    v = _rand(rng, (b, s, hkv, d))
    got = np.asarray(gqa_attention(q, k, v, causal=True))

    # naive reference
    g = hq // hkv
    kr = np.repeat(np.asarray(k), g, axis=2)
    vr = np.repeat(np.asarray(v), g, axis=2)
    qn = np.asarray(q)
    ref = np.zeros_like(got)
    for bi in range(b):
        for h in range(hq):
            logits = qn[bi, :, h] @ kr[bi, :, h].T / np.sqrt(d)
            mask = np.tril(np.ones((s, s), bool))
            logits = np.where(mask, logits, -1e30)
            p = np.exp(logits - logits.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            ref[bi, :, h] = p @ vr[bi, :, h]
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


def test_gqa_attention_kv_len_masks_tail():
    rng = np.random.default_rng(0)
    b, s, t, h, d = 2, 1, 8, 2, 16
    q = _rand(rng, (b, s, h, d))
    k = _rand(rng, (b, t, h, d))
    v = _rand(rng, (b, t, h, d))
    kv_len = jnp.asarray([3, 8])
    got = np.asarray(gqa_attention(q, k, v, causal=False, kv_len=kv_len))
    # batch 0 must ignore kv beyond 3: recompute with truncated kv
    got_trunc = np.asarray(
        gqa_attention(q[:1], k[:1, :3], v[:1, :3], causal=False)
    )
    np.testing.assert_allclose(got[0], got_trunc[0], rtol=1e-5, atol=1e-5)


# ---------- TP MLP ----------


def _mk_mlp(rng, hidden, inter, n, dtype=jnp.float32):
    """Full weights + per-rank shards with gate/up column interleave
    matching the (hidden, 2*I/n) per-rank layout."""
    w_gate = rng.standard_normal((hidden, inter)).astype(np.float32) * 0.1
    w_up = rng.standard_normal((hidden, inter)).astype(np.float32) * 0.1
    w_down = rng.standard_normal((inter, hidden)).astype(np.float32) * 0.1
    il = inter // n
    # per-rank fused w_gate_up: columns [rank*il:(rank+1)*il] of gate then up
    shards = np.stack(
        [
            np.concatenate(
                [w_gate[:, r * il:(r + 1) * il], w_up[:, r * il:(r + 1) * il]],
                axis=1,
            )
            for r in range(n)
        ]
    )  # (n, hidden, 2*il)
    down_shards = np.stack(
        [w_down[r * il:(r + 1) * il] for r in range(n)]
    )  # (n, il, hidden)
    return (
        jnp.asarray(w_gate, dtype), jnp.asarray(w_up, dtype),
        jnp.asarray(w_down, dtype),
        jnp.asarray(shards, dtype), jnp.asarray(down_shards, dtype),
    )


def _dense_mlp_ref(x, w_gate, w_up, w_down):
    g = np.asarray(x, np.float32) @ np.asarray(w_gate, np.float32)
    u = np.asarray(x, np.float32) @ np.asarray(w_up, np.float32)
    act = g / (1 + np.exp(-g)) * u
    return act @ np.asarray(w_down, np.float32)


@pytest.mark.parametrize("mode", ["xla", "dist"])
def test_tp_mlp_sharded_modes_match_dense(mesh8, mode):
    rng = np.random.default_rng(1)
    m, hidden, inter = 64, 128, 256
    x = _rand(rng, (m, hidden))
    w_gate, w_up, w_down, w1_shards, w2_shards = _mk_mlp(
        rng, hidden, inter, TP
    )

    def per_rank(xs, w1, w2):
        return tp_mlp_fwd(xs, TPMLPParams.from_fused(w1[0], w2[0]), mode=mode)

    y = jax.jit(
        jax.shard_map(
            per_rank,
            mesh=mesh8,
            in_specs=(P("tp"), P("tp"), P("tp")),
            out_specs=P("tp"),
            check_vma=False,
        )
    )(x, w1_shards, w2_shards)
    ref = _dense_mlp_ref(x, w_gate, w_up, w_down)
    np.testing.assert_allclose(np.asarray(y), ref, rtol=2e-3, atol=2e-3)


def test_tp_mlp_ar_mode_matches_dense(mesh8):
    rng = np.random.default_rng(2)
    m, hidden, inter = 16, 128, 256
    x = _rand(rng, (m, hidden))
    w_gate, w_up, w_down, w1_shards, w2_shards = _mk_mlp(
        rng, hidden, inter, TP
    )

    def per_rank(xf, w1, w2):
        return tp_mlp_fwd(xf, TPMLPParams.from_fused(w1[0], w2[0]), mode="ar")

    y = jax.jit(
        jax.shard_map(
            per_rank,
            mesh=mesh8,
            in_specs=(P(), P("tp"), P("tp")),
            out_specs=P(),
            check_vma=False,
        )
    )(x, w1_shards, w2_shards)
    ref = _dense_mlp_ref(x, w_gate, w_up, w_down)
    np.testing.assert_allclose(np.asarray(y), ref, rtol=2e-3, atol=2e-3)


# ---------- TP Attn ----------


def _mk_attn(rng, hidden, hq, hkv, d, n, dtype=jnp.float32):
    wq = rng.standard_normal((hidden, hq * d)).astype(np.float32) * 0.1
    wk = rng.standard_normal((hidden, hkv * d)).astype(np.float32) * 0.1
    wv = rng.standard_normal((hidden, hkv * d)).astype(np.float32) * 0.1
    wo = rng.standard_normal((hq * d, hidden)).astype(np.float32) * 0.1
    hq_l, hkv_l = hq // n, hkv // n
    qkv_shards = np.stack(
        [
            np.concatenate(
                [
                    wq[:, r * hq_l * d:(r + 1) * hq_l * d],
                    wk[:, r * hkv_l * d:(r + 1) * hkv_l * d],
                    wv[:, r * hkv_l * d:(r + 1) * hkv_l * d],
                ],
                axis=1,
            )
            for r in range(n)
        ]
    )
    o_shards = np.stack(
        [wo[r * hq_l * d:(r + 1) * hq_l * d] for r in range(n)]
    )
    return (
        jnp.asarray(wq, dtype), jnp.asarray(wk, dtype), jnp.asarray(wv, dtype),
        jnp.asarray(wo, dtype),
        jnp.asarray(qkv_shards, dtype), jnp.asarray(o_shards, dtype),
    )


def _dense_attn_ref(x, wq, wk, wv, wo, b, hq, hkv, d, cos, sin):
    """Dense single-device reference over the full heads."""
    m, hidden = x.shape
    s = m // b
    q = (np.asarray(x) @ np.asarray(wq)).reshape(b, s, hq, d)
    k = (np.asarray(x) @ np.asarray(wk)).reshape(b, s, hkv, d)
    v = (np.asarray(x) @ np.asarray(wv)).reshape(b, s, hkv, d)
    pos = jnp.tile(jnp.arange(s)[None], (b, 1))
    q = np.asarray(apply_rope(jnp.asarray(q), cos, sin, pos))
    k = np.asarray(apply_rope(jnp.asarray(k), cos, sin, pos))
    out = np.asarray(
        gqa_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True
        )
    )
    return out.reshape(m, hq * d) @ np.asarray(wo)


@pytest.mark.parametrize("mode", ["xla", "dist"])
def test_tp_attn_sharded_modes_match_dense(mesh8, mode):
    rng = np.random.default_rng(3)
    b, s, hidden = 2, 32, 128
    hq, hkv, d = 16, 8, 32
    m = b * s
    x = _rand(rng, (m, hidden))
    wq, wk, wv, wo, qkv_shards, o_shards = _mk_attn(
        rng, hidden, hq, hkv, d, TP
    )
    cos, sin = rope_table(d, 64)
    spec = TPAttnSpec(hq // TP, hkv // TP, d)
    pos = jnp.tile(jnp.arange(s)[None], (b, 1))

    def per_rank(xs, wqkv, wo_s):
        params = TPAttnParams(wqkv[0], wo_s[0])
        y, _ = tp_attn_fwd(xs, params, spec, cos, sin, pos, b, mode=mode)
        return y

    y = jax.jit(
        jax.shard_map(
            per_rank,
            mesh=mesh8,
            in_specs=(P("tp"), P("tp"), P("tp")),
            out_specs=P("tp"),
            check_vma=False,
        )
    )(x, qkv_shards, o_shards)
    ref = _dense_attn_ref(x, wq, wk, wv, wo, b, hq, hkv, d, cos, sin)
    np.testing.assert_allclose(np.asarray(y), ref, rtol=2e-3, atol=2e-3)


def test_tp_attn_decode_with_cache_matches_prefill(mesh8):
    """Decode one extra token with the KV cache == recomputing attention
    over the full prefix (the kv-cache correctness contract,
    ref: models/kv_cache.py:29-66)."""
    rng = np.random.default_rng(4)
    b, s, hidden = 2, 8, 128
    hq, hkv, d = 16, 8, 32
    t_max = 16
    x_prefix = _rand(rng, (b * s, hidden))
    x_new = _rand(rng, (b * 1, hidden))
    wq, wk, wv, wo, qkv_shards, o_shards = _mk_attn(
        rng, hidden, hq, hkv, d, TP
    )
    cos, sin = rope_table(d, t_max)
    spec = TPAttnSpec(hq // TP, hkv // TP, d)

    def per_rank(xp, xn, wqkv, wo_s):
        params = TPAttnParams(wqkv[0], wo_s[0])
        # prefill writes into a preallocated cache
        kc = jnp.zeros((b, t_max, spec.num_kv_heads, d), xp.dtype)
        vc = jnp.zeros_like(kc)
        pos = jnp.tile(jnp.arange(s)[None], (b, 1))
        # the layer hands back the step's rows (B, S, Hkv, D); where
        # they are kept is the caller's (models/dense.py `forward`)
        _, (k_rows, v_rows) = tp_attn_fwd(
            xp, params, spec, cos, sin, pos, b, mode="ar",
            kv_cache=(kc, vc), kv_len=jnp.full((b,), s),
        )
        assert k_rows.shape == v_rows.shape == (b, s, spec.num_kv_heads, d)
        kc, vc = kc.at[:, :s].set(k_rows), vc.at[:, :s].set(v_rows)
        # decode 1 token at position s
        pos_d = jnp.full((b, 1), s)
        y, _ = tp_attn_fwd(
            xn, params, spec, cos, sin, pos_d, b, mode="ar",
            kv_cache=(kc, vc), kv_len=jnp.full((b,), s + 1),
        )
        return y

    y = jax.jit(
        jax.shard_map(
            per_rank,
            mesh=mesh8,
            in_specs=(P(), P(), P("tp"), P("tp")),
            out_specs=P(),
            check_vma=False,
        )
    )(x_prefix, x_new, qkv_shards, o_shards)

    # reference: full-sequence causal attention, take the last token
    x_all = jnp.concatenate(
        [x_prefix.reshape(b, s, hidden), x_new.reshape(b, 1, hidden)], axis=1
    ).reshape(b * (s + 1), hidden)
    ref_full = _dense_attn_ref(
        x_all, wq, wk, wv, wo, b, hq, hkv, d, cos, sin
    ).reshape(b, s + 1, hidden)
    np.testing.assert_allclose(
        np.asarray(y).reshape(b, hidden), ref_full[:, -1], rtol=2e-3,
        atol=2e-3,
    )


# ---------- PP schedule ----------


def test_pp_schedule_runs_all_stages(mesh8):
    """Each stage adds its stage index +1; after 8 stages every microbatch
    accumulates sum(1..8) = 36 (ref: test/nvidia/test_pp.py)."""
    n_mb, mb, feat = 4, 2, 128
    x = jnp.ones((n_mb, mb, feat), jnp.float32)

    def per_rank(xs):
        comm = PPCommOp(axis="tp")

        def stage_fn(stage, act):
            return act + (stage.astype(jnp.float32) + 1.0)

        return pp_schedule_fwd(comm, stage_fn, xs, n_mb)

    y = jax.jit(
        jax.shard_map(
            per_rank, mesh=mesh8, in_specs=P(), out_specs=P(),
            check_vma=False,
        )
    )(x)
    np.testing.assert_allclose(np.asarray(y), 1.0 + 36.0)


def test_blockwise_prefill_matches_dense():
    """gqa_attention_blockwise == the dense einsum path, causal + ragged
    kv_len, at a size where both run (round-4 verdict missing #1)."""
    from triton_dist_tpu.layers import gqa_attention, gqa_attention_blockwise

    rng = np.random.default_rng(11)
    b, s, t, hq, hkv, d = 2, 64, 1024, 4, 2, 16
    q = jnp.asarray(rng.standard_normal((b, s, hq, d)) * 0.5, jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, hkv, d)) * 0.5, jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, hkv, d)) * 0.5, jnp.float32)
    kv_len = jnp.asarray([700, 1024])
    qpos = jnp.tile(jnp.arange(s)[None] + 600, (b, 1))
    dense = jax.jit(functools.partial(
        gqa_attention, causal=True))(q, k, v, q_positions=qpos,
                                     kv_len=kv_len)
    block = jax.jit(functools.partial(
        gqa_attention_blockwise, causal=True, chunk=128))(
            q, k, v, q_positions=qpos, kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(block), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


def test_blockwise_prefill_ctx8k_auto():
    """ctx=8192 prefill-into-cache: gqa_attention auto-takes the
    blockwise path (no S x T logits materialized) and matches an inline
    dense oracle computed on a narrow q block."""
    from triton_dist_tpu.layers import gqa_attention

    rng = np.random.default_rng(12)
    b, s, t, hq, hkv, d = 1, 128, 8192, 2, 1, 16
    g = hq // hkv
    q = jnp.asarray(rng.standard_normal((b, s, hq, d)) * 0.5, jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, hkv, d)) * 0.5, jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, hkv, d)) * 0.5, jnp.float32)
    qpos = jnp.tile(jnp.arange(s)[None] + (t - s), (b, 1))
    got = jax.jit(functools.partial(gqa_attention, causal=True))(
        q, k, v, q_positions=qpos)

    # inline oracle (f64, dense over the narrow q block only)
    qf = np.asarray(q, np.float64).reshape(b, s, hkv, g, d) * d ** -0.5
    kf = np.asarray(k, np.float64)
    vf = np.asarray(v, np.float64)
    lg = np.einsum("bskgd,btkd->bkgst", qf, kf)
    mask = np.arange(t)[None, :] <= np.asarray(qpos)[0][:, None]
    lg = np.where(mask[None, None, None], lg, -1e30)
    p = np.exp(lg - lg.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("bkgst,btkd->bskgd", p, vf).reshape(b, s, hq, d)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4,
                               atol=2e-4)


def test_blockwise_prefill_ragged_t():
    """T not a multiple of the chunk (incl. odd): KV is padded and
    tail-masked, not chunk-degraded (round-5 review)."""
    from triton_dist_tpu.layers import gqa_attention, gqa_attention_blockwise

    rng = np.random.default_rng(14)
    for t in (555, 1023):
        b, s, hq, hkv, d = 2, 16, 4, 2, 16
        q = jnp.asarray(rng.standard_normal((b, s, hq, d)) * 0.5,
                        jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, t, hkv, d)) * 0.5,
                        jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, t, hkv, d)) * 0.5,
                        jnp.float32)
        qpos = jnp.tile(jnp.arange(s)[None] + (t - s), (b, 1))
        dense = gqa_attention(q, k, v, causal=True, q_positions=qpos)
        block = gqa_attention_blockwise(q, k, v, causal=True,
                                        q_positions=qpos, chunk=128)
        np.testing.assert_allclose(np.asarray(block), np.asarray(dense),
                                   rtol=2e-5, atol=2e-5, err_msg=f"T={t}")
