"""Granite 4.0-H WITH an expert layer (models/hybrid.py with a Mamba-2
mixer and a softmax router in every block) at a small size: `M A`
twice, `M M` (M a Mamba-2 mixer of 8 heads x 16 over a state of 16, A
grouped-query attention without rotary or q/k norm over heads of 16),
in EVERY block 8 experts of 32 of which this chip holds 4, 2 a token,
and an ungated shared MLP of 48; tied embeddings, Granite's four
multipliers, float32, on the CPU.

Against the benchmark's plain reference (perfbench/reference/
granite_moe_hybrid.py: one full causal pass, the recurrence position by
position, the experts one at a time over every row): prefill in chunks
then decode through the Scheduler; the router as Granite writes it; the
two shares of a layer; the tied head over a slice; the counters.
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from triton_dist_tpu.kernels.moe_utils import topk_routing
from triton_dist_tpu.layers.held_moe import (
    HeldMoEParams,
    RouterForm,
    held_moe_fwd,
)
from triton_dist_tpu.models import Engine, ModelConfig, hybrid
from triton_dist_tpu.runtime import make_mesh
from triton_dist_tpu.serve import Scheduler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from perfbench import harness  # noqa: E402

GEO = dict(slots=3, chunk=4, page=8)
MAX_LEN = 64
SEED = 7
HELD, OFFSET = 4, 2
# float32 against float32, logits of order 0.003 (the tied table at
# 0.02 / m_e, the logits over m_l = 16): the served path read 1.9e-9
# off the reference here; the held experts' output or the shared MLP
# left out moves a logit by 1.6e-5 or 3.2e-5 (the mutations below)
ATOL = 5e-8
M, A = "mamba", "attention"
TYPES = [M, A] * 2 + [M, M]

# the configuration as a benchmark file would state it
FILE = {
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 32,
    "shared_intermediate_size": 48, "num_hidden_layers": 6,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "layer_types": TYPES, "mamba_n_heads": 8, "mamba_d_head": 16,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "attention_bias": False, "num_local_experts": HELD,
    "num_experts_per_tok": 2, "position_embedding_type": "nope",
    "normalization_function": "rmsnorm", "hidden_act": "silu",
    "tie_word_embeddings": True, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "attention_multiplier": 0.0625,
    "logits_scaling": 16, "rms_norm_eps": 1e-5, "torch_dtype": "float32",
    "expert_parallel": {"router_width": 8, "expert_offset": OFFSET},
    "serve": {"max_len": MAX_LEN, "tp": 1},
}


@pytest.fixture(scope="module")
def mesh1():
    return make_mesh(mesh_shape=(1,), axis_names=("tp",))


@pytest.fixture(scope="module")
def cfg():
    return ModelConfig.tiny_granite_moe(
        experts_held=HELD, expert_offset=OFFSET, max_positions=MAX_LEN)


@pytest.fixture(scope="module")
def eng(mesh1, cfg):
    return Engine(cfg, mesh1, max_len=MAX_LEN, seed=SEED, fast_init=True,
                  donate_cache=False)


@pytest.fixture(scope="module")
def ref():
    return harness.load_reference(REPO, "granite_moe_hybrid")


@pytest.fixture(scope="module")
def sizes(ref):
    return ref.Sizes.from_config(FILE)


@pytest.fixture(scope="module")
def weights(ref, sizes):
    return ref.draw_weights(sizes, 1, SEED, jax.devices()[:1])


@pytest.fixture(scope="module")
def prompts():
    # several chunks each, none a whole number of them
    rng = np.random.default_rng(1)
    return [list(map(int, rng.integers(0, 256, n))) for n in (29, 14, 21)]


def _served_against_reference(eng, ref, sizes, weights, prompts, gen=6):
    """[(the `last` rows the step returned for a request's emitted
    tokens, the reference's logits at those positions, the request,
    the scheduler)]: chunked prefill across several steps, then
    decode, through the state and the pages, against ONE causal
    pass."""
    sch = Scheduler(eng, **GEO)
    fn, seen = sch.worker._fn, []

    def recording(*a):
        out = fn(*a)
        seen.append(np.asarray(out[1]))
        return out

    sch.worker._fn = recording
    reqs = [sch.submit(p, max_new_tokens=gen) for p in prompts]
    emitted = {r.request_id: [] for r in reqs}
    while sch.step():
        for slot, (rid, _state, _n) in sch.history[-1]["slots"].items():
            emitted[rid].append((len(seen) - 1, slot))
    score = ref.make_scorer(sizes, MAX_LEN, gen)
    out = []
    for r in reqs:
        seq = np.zeros((MAX_LEN,), np.int32)
        full = list(r.prompt) + list(r.out_tokens)
        seq[:len(full)] = full
        want = np.asarray(score(weights, jnp.asarray(seq),
                                len(r.prompt) - 1))
        got = np.stack([seen[i][slot]
                        for i, slot in emitted[r.request_id][-gen:]])
        out.append((got, want, r))
    return out, sch


@pytest.fixture(scope="module")
def served(eng, ref, sizes, weights, prompts):
    return _served_against_reference(eng, ref, sizes, weights, prompts)


def test_prefill_in_chunks_then_decode_agrees_with_the_reference(served):
    """Logits, not tokens: prompts of 4-8 chunks prefilled through the
    carried state beside the pages, then six decode steps."""
    out, _ = served
    for got, want, r in out:
        assert float(np.abs(want).max()) > 1e-3
        np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
        assert list(np.argmax(want, -1)) == list(r.out_tokens)


def test_seed_names_the_same_weights_in_program_and_reference(eng, weights):
    assert set(weights) == set(eng.params)
    for name, leaf in eng.params.items():
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.asarray(weights[name]),
                                      err_msg=name)


def _mutants(eng):
    """name -> params that compute something other than the model: the
    held experts' part gone, or the shared MLP's."""
    p = dict(eng.params)
    return {
        "experts' output dropped": dict(
            p, w_down=jnp.zeros_like(p["w_down"])),
        "shared MLP dropped": dict(
            p, ws_down=jnp.zeros_like(p["ws_down"])),
    }


@pytest.mark.parametrize("name", ["experts' output dropped",
                                  "shared MLP dropped"])
def test_a_broken_expert_layer_fails_the_comparison(
        mesh1, cfg, eng, ref, sizes, weights, prompts, name):
    broken = Engine(cfg, mesh1, max_len=MAX_LEN, seed=SEED,
                    params=_mutants(eng)[name], donate_cache=False)
    out, _ = _served_against_reference(broken, ref, sizes, weights,
                                       prompts[:1], gen=3)
    worst = max(np.abs(got - want).max() for got, want, _ in out)
    assert worst > 100 * ATOL, (name, worst)


def test_the_router_is_granite_s_top_k_then_softmax():
    """Granite chooses the k largest logits and normalises them by a
    softmax over those k; the program's form (a softmax over all,
    its top k divided by their sum) gives the same experts and
    weights."""
    logits = jax.random.normal(jax.random.PRNGKey(5), (64, 72)) * 3.0
    weights, ids = topk_routing(logits, 10, score="softmax")
    top, want_ids = jax.lax.top_k(logits, 10)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))
    np.testing.assert_allclose(np.asarray(weights),
                               np.asarray(jax.nn.softmax(top, axis=-1)),
                               rtol=2e-6, atol=0)
    # and a sigmoid form would not
    sig, _ = topk_routing(logits, 10, score="sigmoid")
    assert float(np.abs(np.asarray(sig - weights)).max()) > 1e-2


def test_two_shares_and_the_shared_mlp_add_up(ref, sizes):
    """Each chip of the pair computes its half of the routed experts
    of one layer and the ungated shared MLP; the two parts, with the
    shared MLP counted once, are the uncut reference's expert layer."""
    rng = np.random.default_rng(3)
    h, e, i, ish = 64, 8, 32, 48
    x = jnp.asarray(rng.standard_normal((12, h)), jnp.float32)

    def w(*shape):
        return jnp.asarray(rng.standard_normal(shape) * 0.2, jnp.float32)

    full = dict(w_router=w(h, e), w_gate_up=w(e, h, 2 * i),
                w_down=w(e, i, h), ws_gate_up=w(h, 2 * ish),
                ws_down=w(ish, h))
    base = dataclasses.replace(sizes, routed=e)
    want = ref.experts(dataclasses.replace(base, held=e, offset=0), x, full,
                       None)
    shared = ref.experts(
        dataclasses.replace(base, held=0, offset=0), x,
        dict(full, w_gate_up=full["w_gate_up"][:0],
             w_down=full["w_down"][:0]), None)
    valid = jnp.ones((12,), bool)
    total, pairs = 0.0, 0
    for off in (0, e // 2):
        p = HeldMoEParams(full["w_router"],
                          full["w_gate_up"][off:off + e // 2],
                          full["w_down"][off:off + e // 2],
                          full["ws_gate_up"], full["ws_down"])
        y, here, absent = held_moe_fwd(x, valid, p, 2, off,
                                       router=RouterForm("softmax"))
        assert int(here) + int(absent) == 12 * 2
        total, pairs = total + y, pairs + int(here)
    assert pairs == 12 * 2  # every pair computed on exactly one chip
    np.testing.assert_allclose(np.asarray(total - shared), np.asarray(want),
                               atol=2e-5, rtol=0)


def test_the_tied_head_over_a_slice_is_the_uncut_head_s_columns(
        eng, cfg, ref, sizes, weights):
    """The chip's slice of the vocabulary is the first half of the
    table: its logits are the whole table's for those ids, in the
    program and in the reference."""
    x = jax.random.normal(jax.random.PRNGKey(9), (5, cfg.hidden_size))
    half = cfg.vocab_size // 2
    sliced = dict(eng.params, embed=eng.params["embed"][:half])
    whole = np.asarray(hybrid.head_logits(cfg, eng.params, x))
    cut = np.asarray(hybrid.head_logits(
        dataclasses.replace(cfg, vocab_size=half), sliced, x))
    assert cut.shape == (5, half)
    np.testing.assert_array_equal(cut, whole[:, :half])
    tokens = jnp.asarray(np.arange(MAX_LEN) % half, jnp.int32)
    full = np.asarray(ref.logits_rows(sizes, weights, tokens, 0, 8))
    small = np.asarray(ref.logits_rows(
        dataclasses.replace(sizes, vocab=half),
        dict(weights, embed=weights["embed"][:half]), tokens, 0, 8))
    np.testing.assert_array_equal(small, full[:, :half])


def test_the_step_counts_its_pairs_and_expert_steps(served, cfg):
    """The step returns the `moe_*` counters, and `moe_expert_steps`
    is steps x expert blocks x experts held: every block of this family
    has the expert layer."""
    _, sch = served
    c = sch.obs.snapshot()["counters"]
    assert cfg.num_moe_layers == cfg.num_layers == 6
    assert c["moe_expert_steps"] == c["serve_steps"] * 6 * HELD
    assert set(sch.worker.last_stats) == {
        "moe_pairs_here", "moe_pairs_absent", "moe_gmm_tile_rows",
        "moe_combine_rows", "moe_combine_pairs"}
    here, absent = c["moe_pairs{held=here}"], c["moe_pairs{held=absent}"]
    # every valid row's two choices in each of the six blocks
    assert here + absent == 2 * 6 * sum(
        v for k, v in c.items() if k.startswith("serve_rows"))
    assert 0 < here < here + absent
    assert c["moe_gmm_tile_rows"] == 0  # the interpreter's ragged_dot
    # every pair of the (slots, chunk) step, two choices in six blocks;
    # the interpreter's jnp combine reads every one of them
    assert c["moe_combine_pairs"] == (
        c["serve_steps"] * 6 * 2 * GEO["slots"] * GEO["chunk"])
    assert c["moe_combine_rows"] == c["moe_combine_pairs"]
    # the benchmark's configuration: ten expert blocks of 36 held
    big = ModelConfig.granite_4_h_small(num_layers=10, experts_held=36)
    assert big.num_moe_layers * big.num_experts_held == 10 * 36


def test_the_pattern_and_the_leaves(cfg):
    mo, ao = ("mamba2", "moe"), ("global_attn", "moe")
    assert hybrid.segments(cfg) == [((mo, ao), 2), ((mo, mo), 1)]
    shapes = {n: s for n, s, _ in hybrid.leaves(cfg)}
    assert shapes["w_router"] == (6, 64, 8)
    assert shapes["w_gate_up"] == (6, HELD, 64, 2 * 32)
    assert shapes["ws_gate_up"] == (6, 64, 2 * 48)
    # no dense MLP, no gate on the shared MLP, no router bias, no head
    assert not {"wd_gate_up", "w_sgate", "router_bias", "lm_head",
                "attn_q_norm"} & set(shapes)
    big = ModelConfig.granite_4_h_small(num_layers=10)
    assert [n for _, n in hybrid.segments(big)] == [1, 1]
    assert big.mixer_kinds.count("mamba2") == 9
    assert (big.head_dim, big.page_head_dim) == (128, 128)
