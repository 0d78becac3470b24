"""The hybrid family (models/hybrid.py) at a small size: one
period of three gated-delta-net blocks and one gated-attention block,
8 experts of which this chip holds some, float32, on the CPU.

Against the benchmark's plain reference (perfbench/reference/
qwen3_next.py: the delta rule as a scan over tokens, one full pass):
prefill then decode through the Scheduler and the pool agree on
logits; the four shares of the expert layer add up to the uncut one.
Of the serve plane: a request's tokens do not depend on the slot, the
batch, the chunk alignment or an eviction with re-prefill; a padding
column changes no state; what cannot carry the recurrent state refuses
the configuration, naming what is missing.
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from triton_dist_tpu.layers.held_moe import (
    HeldMoEParams,
    held_moe_fwd,
)
from triton_dist_tpu.models import Engine, ModelConfig
from triton_dist_tpu.runtime import make_mesh
from triton_dist_tpu.serve import RequestState, Scheduler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from perfbench import harness  # noqa: E402

GEO = dict(slots=3, chunk=4, page=8)
MAX_LEN = 64
SEED = 5
HELD, OFFSET = 4, 2

# the configuration as a benchmark file would state it, for the
# reference's Sizes
FILE = {
    "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 4,
    "full_attention_interval": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "partial_rotary_factor": 0.25,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "num_experts": HELD,
    "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "rope_theta": 10000000,
    "rms_norm_eps": 1e-06, "torch_dtype": "float32",
    "expert_parallel": {"router_width": 8, "expert_offset": OFFSET},
    "serve": {"max_len": MAX_LEN},
}


@pytest.fixture(scope="module")
def mesh1():
    return make_mesh(mesh_shape=(1,), axis_names=("tp",))


@pytest.fixture(scope="module")
def cfg():
    return ModelConfig.tiny_next(experts_held=HELD, expert_offset=OFFSET,
                                 max_positions=MAX_LEN)


@pytest.fixture(scope="module")
def eng(mesh1, cfg):
    return Engine(cfg, mesh1, max_len=MAX_LEN, seed=SEED, fast_init=True,
                  donate_cache=False)


@pytest.fixture(scope="module")
def ref():
    return harness.load_reference(REPO, "qwen3_next")


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(1)
    return [list(map(int, rng.integers(0, 256, n))) for n in (13, 10, 9)]


def _serve(eng, prompts, gen, **kw):
    sch = Scheduler(eng, **{**GEO, **kw})
    reqs = [sch.submit(p, max_new_tokens=gen) for p in prompts]
    sch.run()
    return sch, [list(r.out_tokens) for r in reqs]


def test_seed_names_the_same_weights_in_program_and_reference(eng, ref):
    sizes = ref.Sizes.from_config(FILE)
    drawn = ref.draw_weights(sizes, 1, SEED, jax.devices()[:1])
    assert set(drawn) == set(eng.params)
    for name, leaf in eng.params.items():
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.asarray(drawn[name]), err_msg=name)


def test_prefill_then_decode_agrees_with_the_reference_on_logits(
        eng, ref, prompts):
    """Every `last` row the step returned for a token it emitted,
    against the reference's one full pass over [prompt + served]."""
    sch = Scheduler(eng, **GEO)
    fn, seen = sch.worker._fn, []

    def recording(*a):
        out = fn(*a)
        seen.append((np.asarray(a[5]), np.asarray(out[1])))
        return out

    sch.worker._fn = recording
    gen = 6
    reqs = [sch.submit(p, max_new_tokens=gen) for p in prompts]
    # slot of each request, and the steps at which it emitted
    emitted = {r.request_id: [] for r in reqs}
    while sch.step():
        entry = sch.history[-1]
        for slot, (rid, state, n) in entry["slots"].items():
            emitted[rid].append((len(seen) - 1, slot))
    sizes = ref.Sizes.from_config(FILE)
    weights = ref.draw_weights(sizes, 1, SEED, jax.devices()[:1])
    score = ref.make_scorer(sizes, MAX_LEN, gen)
    for r in reqs:
        seq = np.zeros((MAX_LEN,), np.int32)
        full = list(r.prompt) + list(r.out_tokens)
        seq[:len(full)] = full
        want = np.asarray(score(weights, jnp.asarray(seq),
                                len(r.prompt) - 1))
        steps = emitted[r.request_id][-gen:]  # the emitting steps
        got = np.stack([seen[i][1][slot] for i, slot in steps])
        np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)
        assert list(np.argmax(want, -1)) == list(r.out_tokens)


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_the_head_reads_the_row_a_slot_emits_from(eng, sampled):
    """The step takes each slot's hidden row at column `n_valid - 1`
    before the final norm and the head (ISSUE 36): bit for bit the
    all-rows form with the column taken afterwards, `n_valid` in
    {0, 1, 3, chunk}."""
    from _head_rows import check_hybrid_step

    check_hybrid_step(eng, sampled)


def test_the_lowered_step_holds_one_row_of_logits_a_slot(cfg, mesh1):
    from _head_rows import check_hybrid_lowering

    check_hybrid_lowering(cfg, mesh1)


def test_four_shares_and_the_shared_expert_add_up(ref):
    """Each chip of a group of four computes its two experts' part and
    the shared expert; the parts, with the shared expert counted once,
    are the uncut reference's expert layer."""
    rng = np.random.default_rng(3)
    h, e, i = 64, 8, 32
    x = jnp.asarray(rng.standard_normal((12, h)), jnp.float32)

    def w(*shape):
        return jnp.asarray(rng.standard_normal(shape) * 0.2, jnp.float32)

    # half of the rows take the first share's two experts, whatever else
    x = x.at[:6].add(1.0)
    full = dict(w_router=w(h, e).at[:, :2].add(0.1),
                w_gate_up=w(e, h, 2 * i),
                w_down=w(e, i, h), ws_gate_up=w(h, 2 * i),
                ws_down=w(i, h), w_sgate=w(h))
    base = ref.Sizes.from_config(FILE)
    uncut = dataclasses.replace(base, held=e, offset=0)
    want = ref.experts(uncut, x, full, None)
    shared = ref.experts(dataclasses.replace(base, held=0, offset=0), x,
                         dict(full, w_gate_up=full["w_gate_up"][:0],
                              w_down=full["w_down"][:0]), None)
    valid = jnp.ones((12,), bool)
    total, pairs, loads = 0.0, 0, []
    for off in range(0, e, 2):
        p = HeldMoEParams(full["w_router"], full["w_gate_up"][off:off + 2],
                          full["w_down"][off:off + 2], full["ws_gate_up"],
                          full["ws_down"], full["w_sgate"])
        y, here, absent = held_moe_fwd(x, valid, p, 2, off)
        assert int(here) + int(absent) == 12 * 2
        total, pairs = total + y, pairs + int(here)
        loads.append(int(here))
    assert pairs == 12 * 2  # every pair computed on exactly one chip
    assert min(loads) < max(loads)  # the shares' loads differ
    np.testing.assert_allclose(np.asarray(total - 3 * shared),
                               np.asarray(want), atol=1e-5, rtol=0)
    # padding rows are not routed
    y, here, absent = held_moe_fwd(x, valid.at[5:].set(False), p, 2, 6)
    assert int(here) + int(absent) == 5 * 2


def test_batched_tokens_are_the_sequential_ones(eng, prompts):
    """Whatever the slot and the batch: three requests together, each
    alone, and in another order."""
    _, together = _serve(eng, prompts, 7)
    alone = [_serve(eng, [p], 7)[1][0] for p in prompts]
    _, reversed_ = _serve(eng, prompts[::-1], 7)
    assert together == alone == reversed_[::-1]


def test_eviction_and_reprefill_keep_the_tokens(eng, prompts):
    """A pool too small for three requests evicts one, which requeues
    and prefills its prompt AND its tokens so far again, at another
    chunk alignment and from zero state."""
    _, want = _serve(eng, prompts, 9)
    sch, got = _serve(eng, prompts, 9, total_pages=6)
    counters = sch.obs.snapshot()["counters"]
    assert sum(v for k, v in counters.items()
               if k.startswith("serve_evicted")) >= 1
    assert counters["serve_state_resets"] > len(prompts)
    assert got == want


def test_chunk_alignment_keeps_the_tokens(eng, prompts):
    """Another chunk is another alignment of every prompt (and a
    chunk of 8 is two passes of the delta rule's chunked form where a
    chunk of 4 is one... of another width)."""
    _, want = _serve(eng, prompts, 6)
    _, got = _serve(eng, prompts, 6, chunk=8)
    assert got == want


def test_a_padding_column_changes_no_state(eng):
    """A slot with no valid column keeps its recurrent and convolution
    state bit for bit, whatever sits in its columns; a slot's state
    does not depend on what sits in its padding columns."""
    sch = Scheduler(eng, **GEO)
    sch.submit(list(range(20, 31)), max_new_tokens=4)
    for _ in range(2):
        sch.step()
    pool, w = sch.pool, sch.worker
    assert float(jnp.abs(pool.rec[:, 0]).max()) > 0
    k, c = pool.slots, sch.chunk
    args = (jnp.asarray(pool.table), jnp.asarray(pool.lengths))
    rest = (jnp.zeros((k,), jnp.float32), jnp.zeros((k, 2), jnp.uint32))
    junk = jnp.full((k, c), 7, jnp.int32)
    _, _, after, _ = w._fn(eng.params, junk, pool.state, *args,
                           jnp.zeros((k,), jnp.int32), *rest)
    for old, new in zip(pool.state[2:], after[2:]):
        np.testing.assert_array_equal(np.asarray(old)[:, 0],
                                      np.asarray(new)[:, 0])
    n_valid = jnp.zeros((k,), jnp.int32).at[0].set(2)
    outs = []
    for pad in (0, 9):
        tokens = jnp.full((k, c), pad, jnp.int32).at[0, :2].set(
            jnp.asarray([3, 4]))
        outs.append(w._fn(eng.params, tokens, pool.state, *args, n_valid,
                          *rest))
    for a, b in zip(outs[0][2][2:], outs[1][2][2:]):
        np.testing.assert_array_equal(np.asarray(a)[:, 0],
                                      np.asarray(b)[:, 0])
    np.testing.assert_array_equal(np.asarray(outs[0][1][0]),
                                  np.asarray(outs[1][1][0]))


def test_counters_of_the_expert_layer_and_the_state(eng, cfg, prompts):
    sch, _ = _serve(eng, prompts, 5)
    c = sch.obs.snapshot()["counters"]
    rows = c["serve_rows{state=prefill}"] + c["serve_rows{state=decode}"]
    pairs = c["moe_pairs{held=here}"] + c["moe_pairs{held=absent}"]
    assert pairs == rows * cfg.num_layers * cfg.num_experts_per_tok
    assert 0 < c["moe_pairs{held=here}"] < pairs
    assert c["moe_expert_steps"] == c["serve_steps"] * cfg.num_layers * HELD
    assert 0 < c["serve_state_bytes_live"] <= c["serve_state_bytes_moved"]
    assert c["serve_state_resets"] == len(prompts)
    assert c["serve_kv_tokens_gathered"] > 0  # the attention block's pages


def test_the_pool_holds_pages_for_the_attention_blocks_only(eng, cfg):
    pool = Scheduler(eng, **GEO).pool
    assert pool.k.shape[0] == 1 and cfg.num_layers == 4
    assert pool.rec.shape == (3, GEO["slots"], 4, 16, 16)
    assert pool.rec.dtype == jnp.float32
    assert pool.conv.shape == (3, GEO["slots"], 3, 2 * 2 * 16 + 4 * 16)


def test_the_family_keeps_the_chunk_s_width(eng, prompts):
    """The dense family's worker holds a decode-only step beside the
    wide one; this family's set is the chunk's width alone (its
    attention route has no form for one query row on the chip:
    Engine.serve_widths), so every step, decode rows alone too, is the
    one `(slots, chunk)` program and counts as wide."""
    assert eng.serve_widths(GEO["chunk"]) == (GEO["chunk"],)
    sch = Scheduler(eng, **GEO)
    assert sch.worker.widths == (GEO["chunk"],) and not sch.worker._narrow
    sch.submit(prompts[0], max_new_tokens=3)
    sch.run()
    assert {h["width"] for h in sch.history} == {GEO["chunk"]}
    c = sch.obs.snapshot()["counters"]
    assert c["serve_steps{shape=wide}"] == sch.worker.n_steps
    assert "serve_steps{shape=narrow}" not in c


def test_chunk_is_priced_from_the_family_s_sizes(mesh1, monkeypatch):
    """The default chunk comes from `estimate_hybrid_step_ms` over the
    configuration itself, not from the dense formula at
    `intermediate_size`."""
    from triton_dist_tpu import perf_model

    asked = []
    real = perf_model.estimate_hybrid_step_ms

    def spy(cfg, n_tokens, kv_tokens=0, chip=None):
        asked.append(n_tokens)
        return real(cfg, n_tokens, kv_tokens, chip)

    monkeypatch.setattr(perf_model, "estimate_hybrid_step_ms", spy)
    monkeypatch.setattr(perf_model, "estimate_serve_step_ms",
                        lambda *a, **k: pytest.fail("the dense formula"))
    cfg = ModelConfig.tiny_next(max_positions=MAX_LEN)
    eng = Engine(cfg, mesh1, max_len=MAX_LEN, fast_init=True)
    sch = Scheduler(eng, slots=2, page=8)
    assert asked and 1 <= sch.chunk <= MAX_LEN
    big = ModelConfig.qwen3_next_80b(num_layers=12, experts_held=128,
                                     vocab_size=37_984)
    chip = perf_model.CHIPS["TPU v5 lite"]
    few, many = (real(big, n, 8 * 8192, chip) for n in (8, 135))
    # the held experts' weights are streamed whatever rides the step,
    # at the fifth of the HBM peak the grouped matmul was measured at
    assert 55.0 < few < 65.0 and many <= 2.0 * few


@pytest.mark.parametrize("kw, names", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(role="prefill", migrate_to=object()), "xslice"),
    (dict(spec="k2"), "spec"),
])
def test_scheduler_refuses_what_cannot_carry_the_state(eng, kw, names):
    if kw.get("spec") == "k2":
        from triton_dist_tpu.spec import SpecConfig

        kw = dict(spec=SpecConfig(k=2))
    with pytest.raises(NotImplementedError, match="recurrent") as e:
        Scheduler(eng, **GEO, **kw)
    assert names in str(e.value)


def test_pool_engine_and_megakernel_refuse_too(eng, cfg, mesh1):
    pool = Scheduler(eng, **GEO).pool
    pool.admit(0, 8)
    for call in (lambda: pool.export_pages(0),
                 lambda: pool.install(1, None, None, 8),
                 lambda: pool.share(1, [1], 8),
                 lambda: pool.cow(0, 0),
                 pool.as_mega_cache):
        with pytest.raises(NotImplementedError, match="recurrent"):
            call()
    for call in (lambda: eng.prefill(np.zeros((1, 4), np.int32)),
                 lambda: eng.decode_step(np.zeros((1,), np.int32), None),
                 lambda: eng.generate(np.zeros((1,), np.int32), None, 2),
                 lambda: eng.make_serve_step(2, 4, 8, 8, per_pos=True)):
        with pytest.raises(NotImplementedError, match="recurrent"):
            call()
    from triton_dist_tpu.mega.qwen3 import MegaQwen3, build_qwen3_graph

    with pytest.raises(NotImplementedError, match="recurrent"):
        build_qwen3_graph(cfg, 1, 1, MAX_LEN)
    with pytest.raises(NotImplementedError, match="recurrent"):
        MegaQwen3(cfg, mesh1, 1)
    with pytest.raises(NotImplementedError, match="expert-parallel"):
        Engine(cfg, make_mesh(mesh_shape=(2,), axis_names=("tp",)),
               max_len=MAX_LEN)
    assert RequestState.PREFILL  # the serve plane's own path stays
