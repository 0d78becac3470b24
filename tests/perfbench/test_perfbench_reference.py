"""The plain reference against the program at a small size on the CPU:
a seed names the same weights in both, prefill then decode through the
cache agree with the reference's one pass, and the control — the
reference in the next precision down, put in the program's place —
reads a gap the program does not."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import qwen3_dense as R

SEED = 2**31 + 7
LAYER_LEAVES = ("input_ln", "post_attn_ln", "w_qkv", "w_o", "q_norm",
                "k_norm", "w_down", "w_gate", "w_up")


def tiny_sizes(dtype="float32"):
    return R.Sizes(vocab=256, hidden=128, inter=256, layers=3, q_heads=8,
                   kv_heads=4, head_dim=32, rope_theta=1e6, rms_eps=1e-6,
                   max_len=64, dtype=dtype)


def engine_for(n):
    from triton_dist_tpu.models import Engine, ModelConfig
    from triton_dist_tpu.runtime import make_mesh

    cfg = ModelConfig.tiny(num_layers=3, max_positions=64, num_q_heads=8,
                           num_kv_heads=4)
    return Engine(cfg, make_mesh((n,), ("tp",)), max_len=64, seed=SEED,
                  fast_init=True)


@pytest.mark.parametrize("n", [1, 4])
def test_a_seed_names_the_same_weights_in_both(n):
    eng = engine_for(n)
    w = R.draw_weights(tiny_sizes(), n, SEED, jax.devices())
    for name in ("embed", "final_ln", "lm_head"):
        assert np.array_equal(np.asarray(getattr(eng.params, name)),
                              np.asarray(w[name])), name
    for name in LAYER_LEAVES:
        assert np.array_equal(np.asarray(getattr(eng.params.layers, name)),
                              np.asarray(w[name])), name


@pytest.mark.parametrize("n", [1, 4])
def test_prefill_then_decode_agree_with_the_one_pass_reference(n):
    eng = engine_for(n)
    s = tiny_sizes()
    w = R.draw_weights(s, n, SEED, jax.devices())
    toks = np.random.default_rng(0).integers(0, 256, 40).astype(np.int32)
    pad = np.zeros(64, np.int32)
    pad[:40] = toks
    ref = np.asarray(R.make_scorer(s, 64, 8)(w, jnp.asarray(pad), 23))
    logits, cache = eng.prefill(jnp.asarray([toks[:24]]), eng.new_cache(1))
    assert np.abs(np.asarray(logits[0]) - ref[0]).max() < 1e-4
    for j in range(1, 4):
        logits, cache = eng.decode_step([int(toks[23 + j])], cache)
        assert np.abs(np.asarray(logits[0]) - ref[j]).max() < 1e-4


def test_padding_behind_a_row_cannot_reach_it():
    s = tiny_sizes()
    w = R.draw_weights(s, 1, SEED, jax.devices())
    score = R.make_scorer(s, 64, 4)
    a = np.zeros(64, np.int32)
    a[:20] = np.arange(20)
    b = a.copy()
    b[24:] = 99
    assert np.array_equal(np.asarray(score(w, jnp.asarray(a), 16)),
                          np.asarray(score(w, jnp.asarray(b), 16)))


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_the_control_reads_a_gap_the_reference_does_not(seed):
    """The control is the reference computed one precision down (fp8
    here as on the chip) at the same positions: the token it puts
    first lies under the reference's best by far more than the
    reference's own first choice does (0)."""
    s = tiny_sizes()
    w = R.draw_weights(s, 1, seed, jax.devices())
    toks = np.random.default_rng(seed).integers(0, 256, 64).astype(np.int32)
    gap = R.make_gap_scorer(s, 64, 48)
    own = R.make_top_scorer(s, 64, 48, None)(w, jnp.asarray(toks), 8)
    low = R.make_top_scorer(s, 64, 48, "fp8")(w, jnp.asarray(toks), 8)
    g_own = np.asarray(gap(w, jnp.asarray(toks), 8, own))
    g_low = np.asarray(gap(w, jnp.asarray(toks), 8, low))
    assert g_own.max() == 0.0
    assert g_low.max() > 0.001  # the tiny cell's limit (perfbench_tiny)


def test_unknown_control_precision_is_an_error():
    with pytest.raises(ValueError):
        R._qdq(jnp.ones((2, 2)), "int3")
