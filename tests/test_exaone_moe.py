"""The hybrid family's third member (models/hybrid.py with block kinds
from the source's `layer_types`) at a small size: a leading dense
block and two whole periods `L L L G`, L grouped-query attention with
rotary over the last 8 positions, G the same heads over every position
WITHOUT rotary; no delta-net block anywhere; 8 experts under a sigmoid
router of which this chip holds some, float32, on the CPU.

Against the benchmark's plain reference (perfbench/reference/
exaone_moe.py: one full causal pass, the window a mask): prefill in
chunks then decode through the Scheduler, the window blocks' per-slot
tails and the global blocks' pages; the window's two edges; four
mutations that each have to FAIL; the eight shares of the expert layer;
what the tail refuses.
"""

import dataclasses
import functools
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from triton_dist_tpu.kernels.flash_prefill import (
    flash_prefill_local,
    flash_prefill_ref,
)
from triton_dist_tpu.layers import gqa_attn
from triton_dist_tpu.layers.attention import gqa_attention
from triton_dist_tpu.layers.held_moe import (
    HeldMoEParams,
    RouterForm,
    held_moe_fwd,
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
HELD, OFFSET = 4, 2
WINDOW = 8
ATOL = 2e-4  # float32, logits of order 1
TYPES = ["sliding_attention"] * 3 + ["full_attention"]

# the configuration as a benchmark file would state it
FILE = {
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 8, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16,
    "layer_types": TYPES * 2, "sliding_window": WINDOW,
    "sliding_windows": [WINDOW, WINDOW, WINDOW, 0] * 2,
    "mlp_layer_types": ["dense"] + ["sparse"] * 7,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "num_experts": HELD, "num_experts_per_tok": 2, "num_shared_experts": 1,
    "moe_intermediate_size": 32, "routed_scaling_factor": 2.5,
    "first_k_dense_replace": 1, "rms_norm_eps": 1e-5,
    "scoring_func": "sigmoid", "norm_topk_prob": True, "n_group": 1,
    "topk_group": 1, "torch_dtype": "float32",
    "expert_parallel": {"router_width": 8, "expert_offset": OFFSET},
    "serve": {"max_len": MAX_LEN},
}


@pytest.fixture(scope="module")
def mesh1():
    return make_mesh(mesh_shape=(1,), axis_names=("tp",))


@pytest.fixture(scope="module")
def cfg():
    return ModelConfig.tiny_exaone(experts_held=HELD, expert_offset=OFFSET,
                                   max_positions=MAX_LEN)


@pytest.fixture(scope="module")
def eng(mesh1, cfg):
    return Engine(cfg, mesh1, max_len=MAX_LEN, seed=SEED, fast_init=True,
                  donate_cache=False)


@pytest.fixture(scope="module")
def ref():
    return harness.load_reference(REPO, "exaone_moe")


@pytest.fixture(scope="module")
def sizes(ref):
    return ref.Sizes.from_config(FILE)


@pytest.fixture(scope="module")
def weights(ref, sizes):
    return ref.draw_weights(sizes, 1, SEED, jax.devices()[:1])


@pytest.fixture(scope="module")
def prompts():
    # longer than sliding_window + chunk: positions leave the window
    # while the prompt is still being prefilled
    rng = np.random.default_rng(1)
    return [list(map(int, rng.integers(0, 256, n))) for n in (29, 14, 21)]


def _serve(eng, prompts, gen, **kw):
    sch = Scheduler(eng, **{**GEO, **kw})
    reqs = [sch.submit(p, max_new_tokens=gen) for p in prompts]
    sch.run()
    return sch, [list(r.out_tokens) for r in reqs]


# -- (a) the served path against the reference ------------------------------


def _served_against_reference(eng, ref, sizes, weights, prompts, gen=6,
                              **kw):
    """[(the `last` rows the step returned for a request's emitted
    tokens, the reference's logits at those positions, the request)]:
    chunked prefill across several steps, then decode, through the
    tails and the pages, against ONE causal pass."""
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


@pytest.mark.parametrize("chunk", [4, 16], ids=["chunk<window",
                                               "chunk>window"])
def test_prefill_in_chunks_then_decode_agrees_with_the_reference(
        eng, ref, sizes, weights, prompts, chunk):
    """Logits, not tokens, across chunk and window boundaries: every
    prompt is longer than the window and the chunk together."""
    assert min(map(len, prompts)) > WINDOW + 4
    for got, want, r in _served_against_reference(
            eng, ref, sizes, weights, prompts, chunk=chunk):
        np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
        assert list(np.argmax(want, -1)) == list(r.out_tokens)


def _no_rope(x, cos, sin, positions):
    return x


def _mutations(cfg):
    """name -> (configuration, {attribute of models.hybrid: its broken
    stand-in}): each computes something other than the model."""
    window, glob = hybrid.window_attn_fwd, hybrid.global_attn_fwd

    def rotary_on_global(x, p, spec, positions, *rest):
        import triton_dist_tpu.layers.rope as rope

        cos, sin = rope.rope_table(spec.head_dim, MAX_LEN, cfg.rope_theta)
        real = gqa_attn._qkv

        def turned(x_, p_, spec_, eps_):
            q, k, v = real(x_, p_, spec_, eps_)
            return (rope.apply_rope(q, cos, sin, positions),
                    rope.apply_rope(k, cos, sin, positions), v)

        gqa_attn._qkv = turned
        try:
            return glob(x, p, spec, positions, *rest)
        finally:
            gqa_attn._qkv = real

    def no_rotary_in_window(*a):
        real, gqa_attn.apply_rope = gqa_attn.apply_rope, _no_rope
        try:
            return window(*a)
        finally:
            gqa_attn.apply_rope = real

    def tail_not_carried(x, p, spec, cos, sin, positions, tail, *rest):
        y, _ = window(x, p, spec, cos, sin, positions, tail, *rest)
        return y, tail

    return {
        "rotary on a global block": (cfg, {"global_attn_fwd":
                                           rotary_on_global}),
        "no rotary on a window block": (cfg, {"window_attn_fwd":
                                              no_rotary_in_window}),
        "the window one key too wide": (
            dataclasses.replace(cfg, sliding_window=WINDOW + 1), {}),
        "the window one key too narrow": (
            dataclasses.replace(cfg, sliding_window=WINDOW - 1), {}),
        "the tail not carried": (cfg, {"window_attn_fwd":
                                       tail_not_carried}),
    }


@pytest.mark.parametrize("name", [
    "rotary on a global block", "no rotary on a window block",
    "the window one key too wide", "the window one key too narrow",
    "the tail not carried"])
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
    for name, leaf in eng.params.items():
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.asarray(weights[name]),
                                      err_msg=name)


def test_the_pattern_is_the_sources_list_cut_into_periods(cfg):
    w, g = ("window_attn", "moe"), ("global_attn", "moe")
    assert cfg.mixer_kinds == ("window_attn",) * 3 + ("global_attn",) \
        + ("window_attn",) * 3 + ("global_attn",)
    assert hybrid.segments(cfg) == [
        ((("window_attn", "dense"), w, w, g), 1), ((w, w, w, g), 1)]
    big = ModelConfig.k_exaone_236b()
    assert [n for _, n in hybrid.segments(big)] == [1, 11]
    assert (big.num_window_layers, big.num_kv_layers) == (36, 12)
    cut = ModelConfig.k_exaone_236b(num_layers=5)
    assert cut.mixer_kinds[-1] == "window_attn"  # a last period with no G
    assert [len(p) for p, _ in hybrid.segments(cut)] == [4, 1]
    # the two kinds share ONE set of leaves, stacked in the blocks' order
    shapes = {n: s for n, s, _ in hybrid.leaves(cfg)}
    assert shapes["attn_w_q"] == (8, 64, 4 * 16)
    assert shapes["attn_w_kv"] == (8, 64, 2 * 2 * 16)
    assert not any(n.startswith(("w_qkvz", "kda_", "mla_")) for n in shapes)
    assert cfg.is_hybrid and hybrid.state_shapes(cfg, 3) == ()


# -- (b) the window's two edges ---------------------------------------------


def _window_inputs(seed=0, b=2, t=48, c=8, hq=4, hkv=2, d=16):
    rng = np.random.default_rng(seed)

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape) * 0.5, jnp.float32)

    return rand(b, c, hq, d), rand(b, t, hkv, d), rand(b, t, hkv, d)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_key_i_minus_window_plus_one_is_seen_and_the_one_before_is_not(
        impl):
    """Row i attends keys i - window + 1 .. i: a change to the value
    at i - window + 1 moves row i, a change at i - window does not;
    the kernel under the interpreter and the dense chain alike."""
    q, k, v = _window_inputs()
    b, c = q.shape[:2]
    start = 30
    qpos = jnp.broadcast_to(start + jnp.arange(c), (b, c))

    def run(v):
        return np.asarray(gqa_attention(
            q, k, v, causal=True, q_positions=qpos,
            kv_len=jnp.full((b,), start + c), prefill_impl=impl,
            prefill_block=16, window=WINDOW))

    base = run(v)
    i = start + 3  # row 3
    inside = run(v.at[:, i - WINDOW + 1].add(5.0))
    outside = run(v.at[:, i - WINDOW].add(5.0))
    assert np.abs(inside[:, 3] - base[:, 3]).max() > 1e-2
    np.testing.assert_array_equal(outside[:, 3], base[:, 3])
    # and nothing after itself
    np.testing.assert_array_equal(run(v.at[:, i + 1].add(5.0))[:, 3],
                                  base[:, 3])


def test_windowed_kernel_is_the_replay_under_the_same_bound(mesh1):
    """`flash_prefill_local(window=)` under the interpreter against
    `flash_prefill_ref` with the same bound in `_block_live`, and both
    against the dense chain; `kv_from` hides the keys before it."""
    from jax.sharding import PartitionSpec as P

    rng = np.random.default_rng(4)
    b, s, hq, hkv, d = 2, 32, 4, 2, 16

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape) * 0.5, jnp.float32)

    q, k, v = rand(b, s, hq, d), rand(b, s, hkv, d), rand(b, s, hkv, d)
    kv_from = jnp.asarray([0, 5], jnp.int32)
    got = jax.jit(functools.partial(
        flash_prefill_local, block=8, window=WINDOW, kv_from=kv_from))(
        q, k, v)
    replay = jax.jit(jax.shard_map(
        functools.partial(flash_prefill_ref, axis="tp", block=8,
                          window=WINDOW, kv_from=kv_from),
        mesh=mesh1, in_specs=(P(), P(), P()), out_specs=P(),
        check_vma=False))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(replay),
                               rtol=1e-6, atol=1e-6)
    dense = gqa_attention(
        q, k, v, causal=True, prefill_impl="xla", window=WINDOW,
        q_positions=jnp.broadcast_to(jnp.arange(s), (b, s)),
        kv_from=kv_from)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_a_window_block_over_tail_and_chunk_is_the_masked_full_pass(
        ref, sizes, weights, impl):
    """One window block's layer, step by step through its tail (chunks
    of 4, then of 3 valid columns of 4), against the reference's
    attention over the whole sequence under the window's mask; and one
    global block over a carried view against the same function with
    the mask off."""
    w = {n: weights[n][1] for n in ref.ATTN}
    p = gqa_attn.GQAttnParams(*(w[n] for n in ref.ATTN))
    spec = gqa_attn.GQAttnSpec(sizes.q_heads, sizes.kv_heads, sizes.head_dim)
    from triton_dist_tpu.layers.rope import rope_table

    cos, sin = rope_table(sizes.head_dim, MAX_LEN, sizes.rope_theta)
    rng = np.random.default_rng(2)
    t = 27
    h = jnp.asarray(rng.standard_normal((1, t, sizes.hidden)), jnp.float32)
    want = np.asarray(ref.attention(
        sizes, jnp.pad(h[0], ((0, 32 - t), (0, 0))), w, True, None))[:t]
    tail = (jnp.zeros((1, WINDOW, sizes.kv_heads, sizes.head_dim)),) * 2
    got, at = [], 0
    for n in (4, 4, 3, 4, 1, 4, 4, 3):
        x = jnp.pad(h[:, at:at + n], ((0, 0), (0, 4 - n), (0, 0)))
        y, tail = gqa_attn.window_attn_fwd(
            x, p, spec, cos, sin, at + jnp.arange(4)[None, :], tail,
            jnp.asarray([at]), jnp.asarray([n]), WINDOW, impl,
            sizes.rms_eps)
        got.append(np.asarray(y[0, :n]))
        at += n
    assert at == t
    np.testing.assert_allclose(np.concatenate(got), want, atol=2e-5, rtol=0)
    # the global block: the first 20 positions cached, 7 new columns
    want = np.asarray(ref.attention(
        sizes, jnp.pad(h[0], ((0, 32 - t), (0, 0))), w, False, None))[:t]
    pos = jnp.arange(t)[None, :]
    empty = (jnp.zeros((1, 32, sizes.kv_heads, sizes.head_dim)),) * 2
    _, (k_rows, v_rows) = gqa_attn.global_attn_fwd(
        h, p, spec, pos, empty, jnp.asarray([t]), "xla", sizes.rms_eps)
    view = tuple(jnp.pad(r[:, :20], ((0, 0), (0, 12), (0, 0), (0, 0)),
                         constant_values=1e4) for r in (k_rows, v_rows))
    y, _ = gqa_attn.global_attn_fwd(
        jnp.pad(h[:, 20:], ((0, 0), (0, 1), (0, 0))), p, spec,
        20 + jnp.arange(8)[None, :], view, jnp.asarray([28]), impl,
        sizes.rms_eps)
    np.testing.assert_allclose(np.asarray(y[0, :7]), want[20:], atol=2e-5,
                               rtol=0)


# -- (c) the share ----------------------------------------------------------


def test_eight_shares_and_the_shared_expert_add_up(ref, sizes):
    """Each chip of a group of eight computes its two experts' part of
    one layer and the ungated shared expert; the parts, with the shared
    expert counted once, are the uncut reference's expert layer."""
    rng = np.random.default_rng(3)
    h, e, i = 64, 16, 32
    x = jnp.asarray(rng.standard_normal((12, h)), jnp.float32)

    def w(*shape):
        return jnp.asarray(rng.standard_normal(shape) * 0.2, jnp.float32)

    full = dict(w_router=w(h, e), router_bias=w(e), w_gate_up=w(e, h, 2 * i),
                w_down=w(e, i, h), ws_gate_up=w(h, 2 * i), ws_down=w(i, h))
    base = dataclasses.replace(sizes, routed=e)
    want = ref.experts(dataclasses.replace(base, held=e, offset=0), x, full,
                       None)
    shared = ref.experts(
        dataclasses.replace(base, held=0, offset=0), x,
        dict(full, w_gate_up=full["w_gate_up"][:0],
             w_down=full["w_down"][:0]), None)
    valid = jnp.ones((12,), bool)
    form = RouterForm("sigmoid", 2.5)
    total, pairs = 0.0, 0
    for off in range(0, e, 2):
        p = HeldMoEParams(full["w_router"], full["w_gate_up"][off:off + 2],
                          full["w_down"][off:off + 2], full["ws_gate_up"],
                          full["ws_down"], None, full["router_bias"])
        y, here, absent = held_moe_fwd(x, valid, p, 2, off, router=form)
        assert int(here) + int(absent) == 12 * 2
        total, pairs = total + y, pairs + int(here)
    assert pairs == 12 * 2  # every pair computed on exactly one chip
    np.testing.assert_allclose(np.asarray(total - 7 * shared),
                               np.asarray(want), atol=2e-5, rtol=0)


# -- (d) the pool, the tail and what it refuses -----------------------------


def test_pages_for_the_global_blocks_alone_and_a_fixed_tail_beside_them(
        eng, cfg):
    pool = Scheduler(eng, **GEO).pool
    # two global blocks keep pages (a head of 16 in a whole 128-value
    # lane, `ModelConfig.page_head_dim`: the published head of 128 as
    # it is); six window blocks keep a tail of `sliding_window`
    # positions a slot, whatever the context, at the head's own width
    assert (cfg.page_head_dim,
            ModelConfig.k_exaone_236b().page_head_dim) == (128, 128)
    assert pool.k.shape == pool.v.shape == (
        2, 1 + GEO["slots"] * 8, GEO["page"], 2, 128)
    assert [w.shape for w in pool.win] == [
        (6, GEO["slots"], WINDOW, 2, 16)] * 2
    assert pool.rec is None and pool.conv is None
    assert len(pool.state) == 4 and pool.state_bytes_per_slot == 0
    assert pool.kv_bytes_per_token == 2 * 2 * 2 * 128 * 4
    assert pool.window_bytes_per_slot == 6 * 2 * WINDOW * 2 * 16 * 4
    before = sum(x.nbytes for x in pool.state)
    pool.admit(0, 60)
    pool.ensure(0, 64)
    pool.check()
    assert sum(x.nbytes for x in pool.state) == before  # nothing grows
    # the state round-trips through the setter in the same order
    pool.state = tuple(pool.state)
    assert len(pool.win) == 2 and pool.v is not None


@pytest.mark.parametrize("kw, names", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(role="prefill", migrate_to=object()), "xslice"),
    (dict(spec="k2"), "spec"),
])
def test_scheduler_refuses_what_cannot_carry_the_tail(eng, kw, names):
    if kw.get("spec") == "k2":
        from triton_dist_tpu.spec import SpecConfig

        kw = dict(spec=SpecConfig(k=2))
    with pytest.raises(NotImplementedError, match="window block's tail") as e:
        Scheduler(eng, **GEO, **kw)
    assert names in str(e.value) and "recurrent" not in str(e.value)


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
        with pytest.raises(NotImplementedError, match="window block's tail"):
            call()
    from triton_dist_tpu.mega.qwen3 import MegaQwen3, build_qwen3_graph

    with pytest.raises(NotImplementedError, match="window block's tail"):
        build_qwen3_graph(cfg, 1, 1, MAX_LEN)
    with pytest.raises(NotImplementedError, match="window block's tail"):
        MegaQwen3(cfg, mesh1, 1)
    with pytest.raises(NotImplementedError, match="expert-parallel"):
        Engine(cfg, make_mesh(mesh_shape=(2,), axis_names=("tp",)),
               max_len=MAX_LEN)
    assert RequestState.PREFILL  # the serve plane's own path stays


def test_eviction_and_reprefill_keep_the_tokens(eng, prompts):
    """Preemption is eviction with re-prefill: the slot starts again at
    length 0, where none of its old tail is read."""
    _, want = _serve(eng, prompts, 9)
    sch, got = _serve(eng, prompts, 9, total_pages=7)
    counters = sch.obs.snapshot()["counters"]
    assert sum(v for k, v in counters.items()
               if k.startswith("serve_evicted")) >= 1
    assert counters["serve_state_resets"] > len(prompts)
    assert got == want
    sch.pool.check()


def test_a_slot_reused_reads_nothing_of_the_tail_it_finds(eng, prompts):
    """One slot, three requests in turn: each finds the last one's tail
    and has to read none of it."""
    _, together = _serve(eng, prompts, 6)
    _, in_turn = _serve(eng, prompts, 6, slots=1)
    assert together == in_turn


def test_batch_and_chunk_alignment_keep_the_tokens(eng, prompts):
    _, together = _serve(eng, prompts, 6)
    alone = [_serve(eng, [p], 6)[1][0] for p in prompts]
    _, wider = _serve(eng, prompts, 6, chunk=8)
    assert together == alone == wider


def test_counters_say_what_the_tail_and_the_pages_are(eng, cfg, prompts):
    sch, _ = _serve(eng, prompts, 5)
    c = sch.obs.snapshot()["counters"]
    rows = c["serve_rows{state=prefill}"] + c["serve_rows{state=decode}"]
    pairs = c["moe_pairs{held=here}"] + c["moe_pairs{held=absent}"]
    assert cfg.num_moe_layers == 7
    assert pairs == rows * 7 * cfg.num_experts_per_tok
    assert 0 < c["moe_pairs{held=here}"] < pairs
    assert c["moe_expert_steps"] == c["serve_steps"] * 7 * HELD
    # the tails: every slot's moved a step, the live ones' counted
    per = sch.pool.window_bytes_per_slot
    assert c["serve_window_bytes_moved"] == per * GEO["slots"] \
        * c["serve_steps"]
    assert 0 < c["serve_window_bytes_live"] <= c["serve_window_bytes_moved"]
    assert c["serve_window_bytes_live"] % per == 0
    # no delta-net block: its counters are not there at all
    assert not any(k.startswith("serve_state_bytes") for k in c)
    assert c["serve_state_resets"] == len(prompts)
    # the page counters are the two global blocks' alone
    assert sch.pool.kv_bytes_per_token == \
        cfg.num_kv_layers * cfg.kv_bytes_per_token
    assert cfg.num_kv_layers == 2
    assert c["serve_kv_bytes_live"] == \
        sch.pool.kv_bytes_per_token * c["serve_kv_tokens_live"]
    assert c["serve_kv_bytes_gathered"] == \
        sch.pool.kv_bytes_per_token * c["serve_kv_tokens_gathered"]
    assert sch.worker.widths == (GEO["chunk"],)


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_the_head_reads_the_row_a_slot_emits_from(eng, sampled):
    from _head_rows import check_hybrid_step

    check_hybrid_step(eng, sampled)


def test_the_lowered_step_holds_one_row_of_logits_a_slot(cfg, mesh1):
    from _head_rows import check_hybrid_lowering

    check_hybrid_lowering(cfg, mesh1)


def test_the_published_preset_is_the_rows_sizes():
    big = ModelConfig.k_exaone_236b()
    assert (big.num_layers, big.hidden_size, big.intermediate_size) == (
        48, 6144, 18_432)
    assert (big.num_q_heads, big.num_kv_heads, big.head_dim) == (64, 8, 128)
    assert (big.num_experts, big.num_experts_per_tok,
            big.moe_intermediate_size) == (128, 8, 2048)
    assert big.sliding_window == 128 and big.vocab_size == 153_600
    assert big.kv_bytes_per_token == 2 * 8 * 128 * 2
    assert hybrid.window_shapes(big, 8) == ((36, 8, 128, 8, 128),) * 2
    assert hybrid.slot_state(big).startswith("a window block's tail")
    assert "recurrent" in hybrid.slot_state(ModelConfig.tiny_next())
