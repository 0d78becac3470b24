"""The hybrid family's fourth member (models/hybrid.py with a
state-space mixer, layers/mamba2.py) at a small size: `M A`, `M M A`
twice, `M` (M a Mamba-2 mixer of 8 heads x 16 over a state of 16, A
grouped-query attention without rotary or q/k norm over heads of 16
that a page keeps 128 wide), a dense SwiGLU in EVERY block, tied
embeddings, Granite's four multipliers, float32, on the CPU.

Against the benchmark's plain reference (perfbench/reference/
granite_hybrid.py: one full causal pass, the recurrence position by
position): the chunked mixer over three carried steps with ragged
`n_valid`; prefill in chunks then decode through the Scheduler, the
state beside the pages; seven mutations that each have to FAIL; what
the state refuses; what the counters say.
"""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from triton_dist_tpu.layers import gqa_attn
from triton_dist_tpu.layers.mamba2 import (
    Mamba2Params,
    Mamba2Spec,
    mamba2_fwd,
)
from triton_dist_tpu.models import Engine, ModelConfig, hybrid
from triton_dist_tpu.runtime import make_mesh
from triton_dist_tpu.serve import Scheduler
from triton_dist_tpu.serve.request import RequestState

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from perfbench import harness  # noqa: E402

GEO = dict(slots=3, chunk=4, page=8)
MAX_LEN = 64
SEED = 7
# float32 against float32, logits of order 0.006 (the tied table is
# drawn at 0.02 / m_e and the logits are over m_l): the served path
# read 2.8e-9 off the reference here; the least of the mutations
# below, the attention scale, moves a logit by 6.8e-6
ATOL = 5e-8
M, A = "mamba", "attention"
TYPES = [M, A] + [M, M, A] * 2 + [M]

# the configuration as a benchmark file would state it
FILE = {
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "shared_intermediate_size": 96, "num_hidden_layers": 9,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "layer_types": TYPES, "mamba_n_heads": 8, "mamba_d_head": 16,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "attention_bias": False, "num_local_experts": 0,
    "num_experts_per_tok": 0, "position_embedding_type": "nope",
    "normalization_function": "rmsnorm", "hidden_act": "silu",
    "tie_word_embeddings": True, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "attention_multiplier": 0.0625,
    "logits_scaling": 8, "rms_norm_eps": 1e-5, "torch_dtype": "float32",
    "serve": {"max_len": MAX_LEN, "tp": 1},
}


@pytest.fixture(scope="module")
def mesh1():
    return make_mesh(mesh_shape=(1,), axis_names=("tp",))


@pytest.fixture(scope="module")
def cfg():
    return ModelConfig.tiny_granite(max_positions=MAX_LEN)


@pytest.fixture(scope="module")
def eng(mesh1, cfg):
    return Engine(cfg, mesh1, max_len=MAX_LEN, seed=SEED, fast_init=True,
                  donate_cache=False)


@pytest.fixture(scope="module")
def ref():
    return harness.load_reference(REPO, "granite_hybrid")


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


def _serve(eng, prompts, gen, **kw):
    sch = Scheduler(eng, **{**GEO, **kw})
    reqs = [sch.submit(p, max_new_tokens=gen) for p in prompts]
    sch.run()
    return sch, [list(r.out_tokens) for r in reqs]


# -- (a) the mixer alone: chunked and carried against the recurrence --------


SPEC = Mamba2Spec(num_heads=8, head_dim=16, state=16, conv=4)
# real columns of each of four slots in three steps: none, one, a part,
# the whole chunk; slot 3 ends a sequence in step 0, sits out step 1 and
# starts ANOTHER in step 2 (fresh among warm ones)
CHUNK = 8
N_VALID = np.asarray([[8, 3, 0, 8], [1, 8, 5, 0], [4, 0, 8, 8]], np.int32)


@pytest.fixture(scope="module")
def mixer(ref, sizes, weights):
    """One state-space block's weights, as the program's parameters and
    as the reference's dictionary."""
    w = {n: weights[n][1] for n in ref.MAMBA}
    return Mamba2Params(*(w[n] for n in ref.MAMBA)), w


def _carried_steps(p, hid, alter=None):
    """`mamba2_fwd` over the three steps of `N_VALID` with the state
    carried: [(y, rec, conv) a step]. `alter` changes the state between
    steps."""
    b = N_VALID.shape[1]
    rec = jnp.zeros((b, SPEC.num_heads, SPEC.head_dim, SPEC.state),
                    jnp.float32)
    conv = jnp.zeros((b, SPEC.conv - 1, SPEC.channels), jnp.float32)
    lengths = np.zeros((b,), np.int32)
    out = []
    for step, n_valid in enumerate(N_VALID):
        if step == 2:
            lengths[3] = 0  # a new request takes the slot
        y, rec, conv = mamba2_fwd(hid[step], p, SPEC, rec, conv,
                                  jnp.asarray(n_valid),
                                  jnp.asarray(lengths == 0), 1e-5)
        if alter is not None:
            rec, conv = alter(rec, conv)
        out.append((y, rec, conv))
        lengths += n_valid
    return out


def _sequences(hid):
    """{(slot, which sequence): [(step, columns)]}: the valid columns a
    slot's sequences are made of, in order."""
    seqs = {(s, 0): [] for s in range(4)}
    seqs[(3, 1)] = []
    for step, n_valid in enumerate(N_VALID):
        for s, n in enumerate(n_valid):
            if n:
                seqs[(s, int(s == 3 and step == 2))].append((step, int(n)))
    return seqs


@pytest.fixture(scope="module")
def hid():
    """Rows of rms 6: x, B and C then come out of order 1, as at the
    published widths, where W_in sums 2,048 terms and not 64."""
    return 6.0 * jax.random.normal(jax.random.PRNGKey(3),
                                   (3, 4, CHUNK, 64), jnp.float32)


def _worst_against_the_recurrence(ref, sizes, mixer, hid, steps):
    p, w = mixer
    worst = 0.0
    for (slot, _), pieces in _sequences(hid).items():
        rows = jnp.concatenate([hid[st, slot, :n] for st, n in pieces])
        want = np.asarray(ref.mamba(sizes, rows, w, None))
        got = np.concatenate([np.asarray(steps[st][0][slot, :n])
                              for st, n in pieces])
        assert float(np.abs(want).max()) > 0.1
        worst = max(worst, float(np.abs(got - want).max()))
    return worst


# float32 at `highest` on both sides, outputs of order 1: the chunked
# form read 1.8e-7 off the recurrence; a state rounded to bfloat16
# between steps moves an output by 5e-5, a state dropped by 0.03
LAYER_ATOL = 2e-6


def test_the_chunked_mixer_is_the_recurrence_over_three_carried_steps(
        ref, sizes, mixer, hid):
    steps = _carried_steps(mixer[0], hid)
    assert _worst_against_the_recurrence(ref, sizes, mixer, hid,
                                         steps) < LAYER_ATOL


@pytest.mark.parametrize("name", ["state in bfloat16", "state dropped",
                                  "tail dropped"])
def test_a_state_broken_between_steps_fails_the_mixer_s_tolerance(
        ref, sizes, mixer, hid, name):
    alter = {
        "state in bfloat16": lambda rec, conv: (
            rec.astype(jnp.bfloat16).astype(jnp.float32), conv),
        "state dropped": lambda rec, conv: (jnp.zeros_like(rec), conv),
        "tail dropped": lambda rec, conv: (rec, jnp.zeros_like(conv)),
    }[name]
    steps = _carried_steps(mixer[0], hid, alter)
    assert _worst_against_the_recurrence(ref, sizes, mixer, hid,
                                         steps) > 10 * LAYER_ATOL


def test_padding_columns_leave_both_states_bit_for_bit(mixer, hid):
    p, _ = mixer
    first = _carried_steps(p, hid)
    # a slot without a valid column keeps what it had
    for step, n_valid in enumerate(N_VALID):
        for slot in np.flatnonzero(n_valid == 0):
            if step == 0:
                assert not np.asarray(first[0][1][slot]).any()
                continue
            for at in (1, 2):
                np.testing.assert_array_equal(
                    np.asarray(first[step][at][slot]),
                    np.asarray(first[step - 1][at][slot]))
    # and what lies in the columns at or past n_valid reaches neither
    cols = np.arange(CHUNK)[None, None, :, None]
    pad = jnp.asarray(cols >= N_VALID[:, :, None, None])
    again = _carried_steps(p, jnp.where(pad, 1e3 * hid + 7.0, hid))
    for (_, rec, conv), (_, rec2, conv2) in zip(first, again):
        np.testing.assert_array_equal(np.asarray(rec), np.asarray(rec2))
        np.testing.assert_array_equal(np.asarray(conv), np.asarray(conv2))
    assert np.asarray(first[-1][1]).any() and np.asarray(first[-1][2]).any()


# -- (b) the served path against the reference ------------------------------


def _served_against_reference(eng, ref, sizes, weights, prompts, gen=6,
                              **kw):
    """[(the `last` rows the step returned for a request's emitted
    tokens, the reference's logits at those positions, the request)]:
    chunked prefill across several steps, then decode, through the
    carried state and the pages, against ONE causal pass."""
    sch = Scheduler(eng, **{**GEO, **kw})
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
    return out


@pytest.mark.parametrize("chunk", [4, 16])
def test_prefill_in_chunks_then_decode_agrees_with_the_reference(
        eng, ref, sizes, weights, prompts, chunk):
    """Logits, not tokens, across chunk boundaries: every prompt takes
    several steps, then six decode steps of one column each."""
    for got, want, r in _served_against_reference(
            eng, ref, sizes, weights, prompts, chunk=chunk):
        np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
        assert list(np.argmax(want, -1)) == list(r.out_tokens)


def _mutations(cfg):
    """name -> (configuration, {attribute of models.hybrid: its broken
    stand-in}): each computes something other than the model."""
    mamba, glob = hybrid.mamba2_fwd, hybrid.global_attn_fwd

    def state_not_carried(hid, p, spec, rec, conv, *rest):
        y, _, conv = mamba(hid, p, spec, rec, conv, *rest)
        return y, rec, conv

    def tail_not_carried(hid, p, spec, rec, conv, *rest):
        y, rec, _ = mamba(hid, p, spec, rec, conv, *rest)
        return y, rec, conv

    def no_skip(hid, p, *rest):
        return mamba(hid, p._replace(d=jnp.zeros_like(p.d)), *rest)

    def pages_zeroed(x, p, spec, positions, kv_cache, *rest):
        return glob(x, p, spec, positions,
                    tuple(jnp.zeros_like(c) for c in kv_cache), *rest)

    def replaced(**kw):
        return dataclasses.replace(cfg, **kw)

    return {
        "the state not carried": (cfg, {"mamba2_fwd": state_not_carried}),
        "the convolution tail not carried": (
            cfg, {"mamba2_fwd": tail_not_carried}),
        "D x dropped": (cfg, {"mamba2_fwd": no_skip}),
        "the cached pages zeroed": (cfg, {"global_attn_fwd": pages_zeroed}),
        "the residual multiplier taken as 1": (
            replaced(residual_multiplier=1.0), {}),
        "the attention scale taken as d ** -0.5": (
            replaced(attention_multiplier=0.0), {}),
        "the embedding multiplier taken as 1": (
            replaced(embedding_multiplier=1.0), {}),
    }


@pytest.mark.parametrize("name", [
    "the state not carried", "the convolution tail not carried",
    "D x dropped", "the cached pages zeroed",
    "the residual multiplier taken as 1",
    "the attention scale taken as d ** -0.5",
    "the embedding multiplier taken as 1"])
def test_a_broken_model_fails_the_comparison(
        monkeypatch, mesh1, cfg, eng, ref, sizes, weights, prompts, name):
    broken_cfg, patches = _mutations(cfg)[name]
    for attr, fn in patches.items():
        monkeypatch.setattr(hybrid, attr, fn)
    broken = Engine(broken_cfg, mesh1, max_len=MAX_LEN, seed=SEED,
                    params=eng.params, donate_cache=False)
    worst = max(np.abs(got - want).max() for got, want, _ in
                _served_against_reference(broken, ref, sizes, weights,
                                          prompts))
    assert worst > 25 * ATOL, (name, worst)


def test_seed_names_the_same_weights_in_program_and_reference(eng, weights):
    assert set(weights) == set(eng.params)
    for name, w in weights.items():
        np.testing.assert_array_equal(np.asarray(w),
                                      np.asarray(eng.params[name]), name)
    # the tied table at 0.02 / m_e, every other matrix at 0.02
    assert abs(np.asarray(weights["embed"]).std() * 12 / 0.02 - 1) < 0.05
    assert abs(np.asarray(weights["wd_down"]).std() / 0.02 - 1) < 0.05
    # Mamba-2's published initialisation, not N(0, 0.02)
    a = np.exp(np.asarray(weights["m2_a_log"], np.float64))
    assert 1.0 <= a.min() < 3.0 and 12.0 < a.max() <= 16.0
    step = np.log1p(np.exp(np.asarray(weights["m2_dt_bias"], np.float64)))
    assert 1e-3 <= step.min() < 3e-3 and 0.03 < step.max() <= 0.1
    assert np.all(np.asarray(weights["m2_d"]) == 1.0)
    taps = np.asarray(weights["m2_conv_w"])
    assert 0.45 < np.abs(taps).max() <= 0.5 and abs(taps.mean()) < 0.02


def test_the_host_stream_draws_the_same_kinds(mesh1, cfg):
    """`fast=False` (one numpy stream in the leaves' order) names other
    numbers but the same initialisations."""
    params = hybrid.init_params(cfg, mesh1, SEED, fast=False)
    assert list(params) == [n for n, _, _ in hybrid.leaves(cfg)]
    a = np.exp(np.asarray(params["m2_a_log"], np.float64))
    assert 1.0 <= a.min() and a.max() <= 16.0
    assert np.abs(np.asarray(params["m2_conv_b"])).max() <= 0.5
    assert np.all(np.asarray(params["m2_d"]) == 1.0)


def test_the_pattern_is_the_sources_list_cut_into_periods(cfg):
    m, a = ("mamba2", "dense"), ("global_attn", "dense")
    assert hybrid.segments(cfg) == [((m, a), 1), ((m, m, a), 2), ((m,), 1)]
    assert cfg.mixer_kinds.count("mamba2") == 6 and cfg.num_kv_layers == 3
    assert set(cfg.ffn_kinds) == {"dense"} and cfg.num_moe_layers == 0
    # no expert leaf, no head leaf, no head norms: absent, not empty
    names = [n for n, _, _ in hybrid.leaves(cfg)]
    assert names[:4] == ["embed", "final_ln", "input_ln", "post_ln"]
    assert not {"lm_head", "w_router", "w_gate_up", "attn_q_norm",
                "attn_k_norm"} & set(names)
    big = ModelConfig.granite_4_h_micro()
    m9 = (m,) * 9 + (a,)
    assert hybrid.segments(big) == [((m,) * 5 + (a,), 1), (m9, 3),
                                    ((m,) * 4, 1)]


def test_an_accepted_configuration_s_leaves_keep_their_positions():
    """A leaf's key is its position: the new kind's leaves come last
    and an accepted configuration lists what it listed."""
    for make, last in ((ModelConfig.tiny_next, "w_o"),
                       (ModelConfig.tiny_kimi, "mla_w_o"),
                       (ModelConfig.tiny_exaone, "attn_w_o")):
        names = [n for n, _, _ in hybrid.leaves(make())]
        assert names[:5] == ["embed", "final_ln", "lm_head", "input_ln",
                             "post_ln"]
        assert names[5] == "w_router" and names[-1] == last
        assert not any(n.startswith("m2_") for n in names)
    assert hybrid._MIXERS[-1] == "mamba2"


# -- (c) a head narrower than a lane tile ------------------------------------


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_a_padded_head_attends_as_the_head_itself(eng, cfg, impl):
    """Zero columns to the page's width change no score and no value:
    the padded block's output is the unpadded block's, through the
    kernel (the chip's route, in the interpreter here) and through
    XLA's chain."""
    gq = hybrid.gqa_spec(cfg)
    assert (gq.head_dim, gq.store, gq.scale, gq.qk_norm) == (
        16, 128, 0.0625, False)
    bare = gq._replace(store=0)
    rng = jax.random.PRNGKey(5)
    b, c, t = 2, 4, 16
    x = jax.random.normal(rng, (b, c, 64), jnp.float32)
    p = gqa_attn.GQAttnParams(
        eng.params["attn_w_q"][0], eng.params["attn_w_kv"][0], None, None,
        eng.params["attn_w_o"][0])
    positions = jnp.asarray([[5, 6, 7, 8], [0, 1, 2, 3]])
    kv_len = jnp.asarray([9, 4])
    cached = jax.random.normal(jax.random.PRNGKey(6), (2, b, t, 2, 16),
                               jnp.float32)
    wide = jnp.pad(cached, ((0, 0),) * 4 + ((0, 112),))
    y, (k, v) = gqa_attn.global_attn_fwd(x, p, gq, positions, tuple(wide),
                                         kv_len, impl, 1e-5)
    y0, (k0, v0) = gqa_attn.global_attn_fwd(x, p, bare, positions,
                                            tuple(cached), kv_len, "xla",
                                            1e-5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0), atol=1e-6)
    assert k.shape == v.shape == (b, c, 2, 128)
    np.testing.assert_array_equal(np.asarray(k[..., :16]), np.asarray(k0))
    assert not np.asarray(k[..., 16:]).any()
    assert not np.asarray(v[..., 16:]).any()


def test_the_route_is_asked_for_the_head_a_page_keeps(cfg):
    from triton_dist_tpu.kernels.flash_prefill import supports_flash_prefill

    big = ModelConfig.granite_4_h_micro()
    assert (big.head_dim, big.page_head_dim) == (64, 128)
    assert big.page_arrays == ((8, 128),) * 2
    assert not supports_flash_prefill(32, 8, big.head_dim)
    assert supports_flash_prefill(32, 8, big.page_head_dim)
    # from the head size and the block's kind alone, whatever the
    # source calls the block: a tiny "full_attention" head of 16 is
    # padded too, a head of whole lanes is kept, and no other kind of
    # block (gated attention, the dense family's) is touched
    assert ModelConfig.tiny_exaone().page_head_dim == 128
    wide = ModelConfig.k_exaone_236b()
    assert wide.page_head_dim == wide.head_dim == 128
    narrow = ModelConfig.k_exaone_236b(head_dim=64)
    assert narrow.page_arrays == ((8, 128),) * 2
    for other in (ModelConfig.qwen3_next_80b(), ModelConfig.tiny_next(),
                  ModelConfig.tiny()):
        assert other.page_head_dim == other.head_dim


# -- (d) the pool, the refusals, the counters --------------------------------


def test_pages_for_the_attention_blocks_and_a_state_beside_them(eng, cfg):
    pool = Scheduler(eng, **GEO).pool
    assert pool.k.shape == pool.v.shape == (
        3, 1 + GEO["slots"] * 8, GEO["page"], 2, 128)
    assert pool.rec.shape == (6, GEO["slots"], 8, 16, 16)
    assert pool.rec.dtype == jnp.float32
    assert pool.conv.shape == (6, GEO["slots"], 3, 128 + 2 * 16)
    assert pool.win == () and len(pool.state) == 4
    assert pool.state_bytes_per_slot == 6 * (8 * 16 * 16 + 3 * 160) * 4
    assert pool.kv_bytes_per_token == 3 * 2 * 2 * 128 * 4
    assert hybrid.state_shapes(cfg, 3) == (pool.rec.shape, pool.conv.shape)
    pool.state = tuple(pool.state)
    assert pool.rec.shape[0] == 6 and pool.v is not None


@pytest.mark.parametrize("kw, names", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(role="prefill", migrate_to=object()), "xslice"),
    (dict(spec="k2"), "spec"),
])
def test_scheduler_refuses_what_cannot_carry_the_state(eng, kw, names):
    if kw.get("spec") == "k2":
        from triton_dist_tpu.spec import SpecConfig

        kw = dict(spec=SpecConfig(k=2))
    with pytest.raises(NotImplementedError,
                       match=r"state-space \(Mamba-2\) state") as e:
        Scheduler(eng, **GEO, **kw)
    assert names in str(e.value) and "gated-delta-net" not in str(e.value)
    assert "window block's tail (" not in str(e.value)


def test_pool_engine_and_megakernel_refuse_too(eng, cfg, mesh1):
    pool = Scheduler(eng, **GEO).pool
    pool.admit(0, 8)
    for call in (lambda: pool.export_pages(0),
                 lambda: pool.install(1, None, None, 8),
                 lambda: pool.share(1, [1], 8),
                 lambda: pool.cow(0, 0),
                 pool.as_mega_cache,
                 lambda: eng.prefill(np.zeros((1, 4), np.int32)),
                 lambda: eng.decode_step(np.zeros((1,), np.int32), None),
                 lambda: eng.generate(np.zeros((1,), np.int32), None, 2),
                 lambda: eng.make_serve_step(2, 4, 8, 8, per_pos=True)):
        with pytest.raises(NotImplementedError, match="state-space"):
            call()
    from triton_dist_tpu.mega.qwen3 import MegaQwen3, build_qwen3_graph

    with pytest.raises(NotImplementedError, match="state-space"):
        build_qwen3_graph(cfg, 1, 1, MAX_LEN)
    with pytest.raises(NotImplementedError, match="state-space"):
        MegaQwen3(cfg, mesh1, 1)
    with pytest.raises(NotImplementedError, match="tensor-parallel form"):
        Engine(cfg, make_mesh(mesh_shape=(2,), axis_names=("tp",)),
               max_len=MAX_LEN)
    assert RequestState.PREFILL  # the serve plane's own path stays


def test_eviction_and_reprefill_keep_the_tokens(eng, prompts):
    """Preemption is eviction with re-prefill: the slot starts again at
    length 0, from zero state."""
    _, want = _serve(eng, prompts, 9)
    sch, got = _serve(eng, prompts, 9, total_pages=7)
    counters = sch.obs.snapshot()["counters"]
    assert sum(v for k, v in counters.items()
               if k.startswith("serve_evicted")) >= 1
    assert counters["serve_state_resets"] > len(prompts)
    assert got == want
    sch.pool.check()


def test_a_slot_reused_reads_nothing_of_the_state_it_finds(eng, prompts):
    _, together = _serve(eng, prompts, 6)
    _, in_turn = _serve(eng, prompts, 6, slots=1)
    assert together == in_turn


def test_batch_and_chunk_alignment_keep_the_tokens(eng, prompts):
    _, together = _serve(eng, prompts, 6)
    alone = [_serve(eng, [p], 6)[1][0] for p in prompts]
    _, wider = _serve(eng, prompts, 6, chunk=8)
    assert together == alone == wider


def test_counters_say_what_the_state_and_the_pages_are(eng, cfg, prompts):
    sch, _ = _serve(eng, prompts, 5)
    c = sch.obs.snapshot()["counters"]
    # the state: every slot's moved a step, the live ones' counted,
    # under the delta nets' names
    per = sch.pool.state_bytes_per_slot
    assert c["serve_state_bytes_moved"] == per * GEO["slots"] \
        * c["serve_steps"]
    assert 0 < c["serve_state_bytes_live"] <= c["serve_state_bytes_moved"]
    assert c["serve_state_bytes_live"] % per == 0
    assert c["serve_state_resets"] == len(prompts)
    assert not any(k.startswith("serve_window_bytes") for k in c)
    # no expert layer: the step returns no counter and the names read 0
    assert sch.worker.last_stats == {}
    assert c["moe_pairs{held=here}"] == c["moe_pairs{held=absent}"] == 0
    assert c["moe_gmm_tile_rows"] == c["moe_expert_steps"] == 0
    # the page counters are the three attention blocks' alone, a head
    # as wide as the page keeps it
    assert sch.pool.kv_bytes_per_token == \
        cfg.num_kv_layers * cfg.kv_bytes_per_token
    assert c["serve_kv_bytes_live"] == \
        sch.pool.kv_bytes_per_token * c["serve_kv_tokens_live"]
    assert sch.worker.widths == (GEO["chunk"],)


def test_the_lowered_step_counts_no_expert_pair(eng):
    """No `moe.*` part and no counter output: the step spends no device
    operation on an expert layer it does not have."""
    sch = Scheduler(eng, **GEO)
    pool = sch.pool
    fn = eng.make_serve_step(pool.slots, GEO["chunk"], pool.page,
                             pool.max_pages)
    shapes = jax.eval_shape(
        fn, eng.params, jnp.zeros((pool.slots, GEO["chunk"]), jnp.int32),
        pool.state, jnp.asarray(pool.table), jnp.asarray(pool.lengths),
        jnp.zeros((pool.slots,), jnp.int32),
        jnp.zeros((pool.slots,), jnp.float32),
        jnp.zeros((pool.slots, 2), jnp.uint32))
    assert shapes[3] == {} and shapes[1].shape == (pool.slots, 256)


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_the_head_reads_the_row_a_slot_emits_from(eng, sampled):
    from _head_rows import check_hybrid_step

    check_hybrid_step(eng, sampled)


def test_the_lowered_step_holds_one_row_of_logits_a_slot(cfg, mesh1):
    from _head_rows import check_hybrid_lowering

    check_hybrid_lowering(cfg, mesh1)


def test_the_chunk_is_priced_from_the_state_space_sizes():
    from triton_dist_tpu import perf_model

    big = ModelConfig.granite_4_h_micro()
    v5e = perf_model.CHIPS["TPU v5 lite"]

    def step_ms(rows):
        return perf_model.estimate_hybrid_step_ms(big, rows, 8192, v5e)

    # the weights' stream (6.4 GB) binds every candidate: the widest
    assert perf_model._largest_chunk_within(step_ms, 8) == 128
    assert 7.5 < step_ms(8) == step_ms(135) < step_ms(1024)
    # no expert layer to divide by
    assert perf_model.choose_chunk_for(big, 1, 8, 8192) >= 1


def test_the_published_preset_is_the_rows_sizes():
    big = ModelConfig.granite_4_h_micro()
    assert (big.num_layers, big.hidden_size, big.intermediate_size,
            big.vocab_size) == (40, 2048, 8192, 100_352)
    assert (big.num_q_heads, big.num_kv_heads, big.head_dim) == (32, 8, 64)
    assert hybrid.mamba_spec(big) == Mamba2Spec(64, 64, 128, 4)
    assert hybrid.mamba_spec(big).channels == 4352
    assert big.mixer_kinds.count("mamba2") == 36 and big.num_kv_layers == 4
    assert [i for i, k in enumerate(big.mixer_kinds)
            if k == "global_attn"] == [5, 15, 25, 35]
    assert hybrid.state_shapes(big, 8) == ((36, 8, 64, 64, 128),
                                           (36, 8, 3, 4352))
    assert hybrid.slot_state(big) == "state-space (Mamba-2) state"
    assert "recurrent" in hybrid.slot_state(ModelConfig.tiny_next())
    # 3.19 B parameters, the embedding counted once
    n = sum(int(np.prod(s)) for _, s, _ in hybrid.leaves(big))
    assert 3.18e9 < n < 3.20e9
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "granite-4.0-h-micro")["config"]
        assert tuple(row["layer_types"]) == big.layer_types
        assert (row["mamba_n_heads"], row["mamba_d_head"],
                row["mamba_d_state"], row["mamba_d_conv"]) == (64, 64, 128, 4)
        assert (row["embedding_multiplier"], row["residual_multiplier"],
                row["attention_multiplier"], row["logits_scaling"]) == (
            big.embedding_multiplier, big.residual_multiplier,
            big.attention_multiplier, big.logits_scaling)


# -- the chip's own checks, rehearsed ----------------------------------------


@pytest.mark.parametrize("phase", ["ssd_phase", "nope_phase"])
def test_a_chip_smoke_phase_rehearses_on_the_cpu(phase, monkeypatch):
    """`chip_smoke.ssd_phase` (the mixer at the published widths, the
    carry broken three ways) and `chip_smoke.nope_phase` (heads of 64
    kept 128 wide over a paged view, the scale and the pages broken)
    hold on the XLA route here as they have to on the chip's: each
    raises unless its own reading lies under its tolerance and every
    break over it."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(root)
    import chip_smoke

    if phase == "ssd_phase":
        chip_smoke.ssd_phase(3)
        return
    monkeypatch.setattr(chip_smoke, "NOPE_LENS",
                        (512, 300, 257, 129, 65, 33, 100, 128))
    monkeypatch.setattr(chip_smoke, "NOPE_VALID",
                        (128, 128, 1, 128, 1, 32, 72, 128))
    chip_smoke.nope_phase(3, max_len=512)

