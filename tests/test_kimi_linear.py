"""The hybrid family's second member (models/hybrid.py with block kinds
from two lists) at a small size: a leading dense block, one whole
period `K K K M` and a short last one `K M`, K a channel-gated delta
net and M latent attention without rotary, 8 experts under a sigmoid
router of which this chip holds some, float32, on the CPU.

Against the benchmark's plain reference (perfbench/reference/
kimi_linear.py: the recurrence as a scan over tokens, the latent
attention expanded, one full pass): the chunked channel-gated rule, the
absorbed latent attention, the router's form, the four shares of the
expert layer, and prefill in chunks then decode through the Scheduler
and the ONE latent pool.
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from triton_dist_tpu.kernels.moe_utils import topk_routing
from triton_dist_tpu.layers import gated_delta_net as gdn
from triton_dist_tpu.layers.held_moe import (
    HeldMoEParams,
    RouterForm,
    held_moe_fwd,
)
from triton_dist_tpu.layers.latent_attn import (
    LatentAttnParams,
    LatentAttnSpec,
    latent_attn_fwd,
)
from triton_dist_tpu.models import Engine, ModelConfig, hybrid
from triton_dist_tpu.runtime import make_mesh
from triton_dist_tpu.serve import Scheduler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from perfbench import harness  # noqa: E402

GEO = dict(slots=3, chunk=4, page=8)
MAX_LEN = 64
SEED = 5
HELD, OFFSET = 4, 2
_HI = jax.lax.Precision.HIGHEST

# the configuration as a benchmark file would state it
FILE = {
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 6, "num_attention_heads": 4, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "num_experts": HELD, "num_experts_per_token": 2,
    "num_shared_experts": 1, "moe_intermediate_size": 32,
    "routed_scaling_factor": 2.446, "first_k_dense_replace": 1,
    "rms_norm_eps": 1e-5, "moe_router_activation_func": "sigmoid",
    "moe_renormalize": True, "mla_use_nope": True,
    "linear_attn_config": {
        "full_attn_layers": [4, 6], "kda_layers": [1, 2, 3, 5],
        "head_dim": 16, "num_heads": 4, "short_conv_kernel_size": 4},
    "torch_dtype": "float32",
    "expert_parallel": {"router_width": 8, "expert_offset": OFFSET},
    "serve": {"max_len": MAX_LEN},
}


@pytest.fixture(scope="module")
def mesh1():
    return make_mesh(mesh_shape=(1,), axis_names=("tp",))


@pytest.fixture(scope="module")
def cfg():
    # the gates' low rank is the delta net's head size, as the
    # benchmark's family file has it
    return ModelConfig.tiny_kimi(experts_held=HELD, expert_offset=OFFSET,
                                 linear_gate_rank=16, max_positions=MAX_LEN)


@pytest.fixture(scope="module")
def eng(mesh1, cfg):
    return Engine(cfg, mesh1, max_len=MAX_LEN, seed=SEED, fast_init=True,
                  donate_cache=False)


@pytest.fixture(scope="module")
def ref():
    return harness.load_reference(REPO, "kimi_linear")


@pytest.fixture(scope="module")
def weights(ref):
    return ref.draw_weights(ref.Sizes.from_config(FILE), 1, SEED,
                            jax.devices()[:1])


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(1)
    return [list(map(int, rng.integers(0, 256, n))) for n in (13, 10, 9)]


def _serve(eng, prompts, gen, **kw):
    sch = Scheduler(eng, **{**GEO, **kw})
    reqs = [sch.submit(p, max_new_tokens=gen) for p in prompts]
    sch.run()
    return sch, [list(r.out_tokens) for r in reqs]


# -- (a) the chunked rule under a gate per key channel ----------------------


def _by_token(q, k, v, g, beta, state):
    """The recurrence, a token at a time, in float64."""
    q, k, v, g, beta, state = (np.asarray(x, np.float64)
                               for x in (q, k, v, g, beta, state))
    if g.ndim == 3:
        g = g[..., None]
    out = []
    for t in range(q.shape[2]):
        state = state * np.exp(g[:, :, t])[..., None]
        rest = v[:, :, t] - np.einsum("bhk,bhkv->bhv", k[:, :, t], state)
        state = state + np.einsum("bhk,bhv->bhkv", k[:, :, t],
                                  beta[:, :, t][..., None] * rest)
        out.append(np.einsum("bhk,bhkv->bhv", q[:, :, t], state))
    return np.stack(out, 2), state


def _parent_rule(q, k, v, g, beta, state, c):
    """`chunk_gated_delta_rule` as the parent commit had it: one gate a
    head, the decay one (c, c) matrix."""
    b, h, length, dk = k.shape
    n = length // c

    def cut(x):
        return x.reshape(b, h, n, c, *x.shape[3:])

    q, k, v, g, beta = map(cut, (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-1)
    k_beta, v_beta = k * beta[..., None], v * beta[..., None]
    rows = jnp.arange(c)
    decay = jnp.exp(jnp.where(rows[:, None] >= rows[None, :],
                              gc[..., :, None] - gc[..., None, :], -jnp.inf))
    kk = jnp.einsum("bhnid,bhnjd->bhnij", k_beta, k, precision=_HI)
    solve = gdn._unit_lower_inverse(
        jnp.where(rows[:, None] > rows[None, :], kk * decay, 0.0))
    value = jnp.matmul(solve, v_beta, precision=_HI)
    k_cum = jnp.matmul(solve, k_beta * jnp.exp(gc)[..., None], precision=_HI)
    qk = jnp.einsum("bhnid,bhnjd->bhnij", q, k, precision=_HI) * decay
    outs = []
    for i in range(n):
        v_new = value[:, :, i] - jnp.matmul(k_cum[:, :, i], state,
                                            precision=_HI)
        outs.append(
            jnp.matmul(q[:, :, i] * jnp.exp(gc[:, :, i])[..., None], state,
                       precision=_HI)
            + jnp.matmul(qk[:, :, i], v_new, precision=_HI))
        last = gc[:, :, i, -1]
        k_out = k[:, :, i] * jnp.exp(last[..., None] - gc[:, :, i])[..., None]
        state = state * jnp.exp(last)[..., None, None] + jnp.einsum(
            "bhck,bhcv->bhkv", k_out, v_new, precision=_HI)
    return jnp.concatenate(outs, axis=2), state


@pytest.fixture(scope="module")
def rule_inputs():
    rng = np.random.default_rng(0)
    b, h, length, dk, dv = 2, 3, 128, 16, 8

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    return dict(
        q=unit(rng.standard_normal((b, h, length, dk))),
        k=unit(rng.standard_normal((b, h, length, dk))),
        v=rng.standard_normal((b, h, length, dv)),
        beta=rng.uniform(0, 1, (b, h, length)),
        # from -5 to -0.001 a column and channel: exp(5 * 128) is far
        # outside float32 and so is its inverse
        g=-np.exp(rng.uniform(np.log(0.001), np.log(5.0),
                              (b, h, length, dk))),
        state=rng.standard_normal((b, h, dk, dv)))


@pytest.mark.parametrize("carried", [False, True], ids=["fresh", "carried"])
@pytest.mark.parametrize("c", [64, 16, 128])
def test_channel_gated_rule_is_the_recurrence(rule_inputs, c, carried):
    x = dict(rule_inputs)
    if not carried:
        x["state"] = np.zeros_like(x["state"])
    got_o, got_s = gdn.chunk_gated_delta_rule(
        *(jnp.asarray(x[n], jnp.float32)
          for n in ("q", "k", "v", "g", "beta", "state")), c)
    want_o, want_s = _by_token(*(x[n] for n in ("q", "k", "v", "g", "beta",
                                               "state")))
    assert np.isfinite(np.asarray(got_o)).all()
    np.testing.assert_allclose(got_o, want_o, atol=2e-5, rtol=0)
    np.testing.assert_allclose(got_s, want_s, atol=2e-5, rtol=0)


def test_scalar_gate_through_the_same_code_is_the_parent_s(rule_inputs):
    x = {n: jnp.asarray(v, jnp.float32) for n, v in rule_inputs.items()}
    g = x["g"][..., 0]
    args = (x["q"], x["k"], x["v"], g, x["beta"], x["state"])
    got = gdn.chunk_gated_delta_rule(*args, 64)
    want = _parent_rule(*args, 64)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the channel form, handed the same gate on every channel,
    # agrees with it to rounding
    wide = gdn.chunk_gated_delta_rule(
        x["q"], x["k"], x["v"], jnp.broadcast_to(g[..., None], x["k"].shape),
        x["beta"], x["state"], 64)
    np.testing.assert_allclose(wide[0], got[0], atol=2e-5, rtol=0)


def test_kda_padding_columns_leave_both_states_bit_for_bit(eng, cfg):
    """A row with no valid column keeps the recurrent and convolution
    state as they were; what sits in a row's padding columns reaches
    neither state nor a valid column's output."""
    spec = hybrid.gdn_spec(cfg)
    p = gdn.KDAParams(*(eng.params[n][1]
                        for n in hybrid._MIXER_LEAVES["kda"]))
    rng = np.random.default_rng(2)
    b, c = 3, 8
    rec = jnp.asarray(rng.standard_normal(
        (b, spec.num_v_heads, spec.k_dim, spec.v_dim)), jnp.float32)
    conv = jnp.asarray(rng.standard_normal((b, spec.conv - 1,
                                            spec.channels)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((b, c, cfg.hidden_size)),
                    jnp.float32)
    fresh = jnp.zeros((b,), bool)
    n_valid = jnp.asarray([0, 3, c], jnp.int32)
    y, rec1, conv1 = gdn.kda_fwd(x, p, spec, rec, conv, n_valid, fresh,
                                 cfg.rms_eps)
    np.testing.assert_array_equal(np.asarray(rec1[0]), np.asarray(rec[0]))
    np.testing.assert_array_equal(np.asarray(conv1[0]), np.asarray(conv[0]))
    assert float(jnp.abs(rec1[1] - rec[1]).max()) > 0
    junk = x.at[1, 3:].set(7.0)
    y2, rec2, conv2 = gdn.kda_fwd(junk, p, spec, rec, conv, n_valid, fresh,
                                  cfg.rms_eps)
    np.testing.assert_array_equal(np.asarray(rec2), np.asarray(rec1))
    np.testing.assert_array_equal(np.asarray(conv2), np.asarray(conv1))
    np.testing.assert_array_equal(np.asarray(y2[1, :3]),
                                  np.asarray(y[1, :3]))
    # a fresh row starts from zero state whatever the pool held
    _, rec3, _ = gdn.kda_fwd(x, p, spec, rec * 9.0, conv * 9.0, n_valid,
                             jnp.ones((b,), bool), cfg.rms_eps)
    assert float(jnp.abs(rec3[0]).max()) == 0.0


# -- (b) latent attention: absorbed against expanded ------------------------


def test_absorbed_latent_attention_is_the_expanded_one(ref, weights):
    """One layer over one sequence with an empty cache: the program's
    absorbed form over the (padded) latent rows against the
    reference's expanded keys and values a head."""
    sizes = ref.Sizes.from_config(FILE)
    w = {n: weights[n][1] for n in ref.MLA}
    rng = np.random.default_rng(4)
    s = 24
    h = jnp.asarray(rng.standard_normal((s, sizes.hidden)), jnp.float32)
    want = ref.latent_attention(sizes, h, w, None)
    spec = LatentAttnSpec(sizes.heads, sizes.rank, sizes.nope, sizes.rope,
                          sizes.v_dim)
    p = LatentAttnParams(*(w[n] for n in ref.MLA))
    view = jnp.zeros((1, 32, 1, 128), jnp.float32)  # 40 padded to lanes
    y, (row,) = latent_attn_fwd(
        h[None], p, spec, jnp.arange(s)[None], view,
        jnp.asarray([s], jnp.int32), jnp.asarray([s - 4], jnp.int32), "xla",
        sizes.rms_eps)
    # the last four columns are padding: they attend nothing, and the
    # valid ones are what they are with every column valid
    np.testing.assert_allclose(y[0, :s - 4], want[:s - 4], atol=2e-6, rtol=0)
    assert row.shape == (1, s, 1, 128)
    assert float(jnp.abs(row[..., spec.row:]).max()) == 0.0  # the padding


@pytest.fixture(scope="module")
def carried_latent(ref, weights):
    """Two slots whose latent rows are already in their views (1,100
    and 700 positions of a 2,048-position view: four pages of the
    kernel's 512, the last ones dead), the step's chunk of 16 columns at
    the end of each (all valid / five valid), and the reference's
    expanded attention at those columns."""
    sizes = ref.Sizes.from_config(FILE)
    w = {n: weights[n][1] for n in ref.MLA}
    spec = LatentAttnSpec(sizes.heads, sizes.rank, sizes.nope, sizes.rope,
                          sizes.v_dim)
    p = LatentAttnParams(*(w[n] for n in ref.MLA))
    rng = np.random.default_rng(9)
    t, c, lens, n_valid = 2048, 16, (1100, 700), (16, 5)
    h = jnp.asarray(rng.standard_normal((2, t, sizes.hidden)), jnp.float32)
    want = jnp.stack([ref.latent_attention(sizes, h[i], w, None)
                      for i in range(2)])
    # every position's row, from one pass of the layer itself
    _, (rows,) = latent_attn_fwd(
        h, p, spec, jnp.broadcast_to(jnp.arange(t), (2, t)),
        jnp.zeros((2, t, 1, 128), jnp.float32),
        jnp.full((2,), t, jnp.int32), jnp.full((2,), t, jnp.int32), "xla",
        sizes.rms_eps)
    start = jnp.asarray([n - v for n, v in zip(lens, n_valid)])
    pos = start[:, None] + jnp.arange(c)[None, :]
    keep = jnp.arange(t)[None, :] < start[:, None]
    # what lies past a slot's cached rows is another request's: never read
    view = jnp.where(keep[..., None, None], rows, 1e4)
    x = jnp.take_along_axis(h, pos[..., None], axis=1)

    def run(view, impl):
        y, _ = latent_attn_fwd(
            x, p, spec, pos, view, jnp.asarray(lens, jnp.int32),
            jnp.asarray(n_valid, jnp.int32), impl, sizes.rms_eps)
        return [np.asarray(y[i, :v]) for i, v in enumerate(n_valid)]

    got = [np.asarray(jnp.take_along_axis(want, pos[..., None], axis=1)
                      [i, :v]) for i, v in enumerate(n_valid)]
    return run, view, got, spec


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_latent_attention_over_a_carried_view_is_the_expanded_one(
        carried_latent, impl):
    """(b) the route the chip takes, under the interpreter: the kernel
    that reads each latent page once and takes the values out of its
    leading columns (`flash_prefill_local(v_prefix=)`), over contexts
    that cross its pages, against the reference's expanded form; and
    the XLA scan the tests' engine runs, over the same view. Rows past
    a slot's length are never read, and a cached row broken on purpose
    (its value columns zeroed, in the first page alone) shows."""
    run, view, want, spec = carried_latent
    atol = 5e-6  # of outputs near 1e-3
    for got, ref_rows in zip(run(view, impl), want):
        np.testing.assert_allclose(got, ref_rows, atol=atol, rtol=0)
    broken = view.at[:, :512, :, :spec.rank].set(0.0)
    for got, ref_rows in zip(run(broken, impl), want):
        assert np.abs(got - ref_rows).max() > 50 * atol


def test_prefill_in_chunks_then_decode_agrees_with_the_reference(
        eng, ref, weights, prompts):
    """(b) and (e): every `last` row the step returned for a token it
    emitted, through the irregular pattern (a leading dense block, a
    short last period) and the latent pool, against the reference's
    one full pass over [prompt + served]."""
    sch = Scheduler(eng, **GEO)
    fn, seen = sch.worker._fn, []

    def recording(*a):
        out = fn(*a)
        seen.append(np.asarray(out[1]))
        return out

    sch.worker._fn = recording
    gen = 6
    reqs = [sch.submit(p, max_new_tokens=gen) for p in prompts]
    emitted = {r.request_id: [] for r in reqs}
    while sch.step():
        for slot, (rid, _state, _n) in sch.history[-1]["slots"].items():
            emitted[rid].append((len(seen) - 1, slot))
    sizes = ref.Sizes.from_config(FILE)
    score = ref.make_scorer(sizes, MAX_LEN, gen)
    for r in reqs:
        seq = np.zeros((MAX_LEN,), np.int32)
        full = list(r.prompt) + list(r.out_tokens)
        seq[:len(full)] = full
        want = np.asarray(score(weights, jnp.asarray(seq),
                                len(r.prompt) - 1))
        got = np.stack([seen[i][slot]
                        for i, slot in emitted[r.request_id][-gen:]])
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


def test_seed_names_the_same_weights_in_program_and_reference(eng, weights):
    assert set(weights) == set(eng.params)
    for name, leaf in eng.params.items():
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.asarray(weights[name]),
                                      err_msg=name)


def test_the_pattern_is_cut_into_runs_of_equal_periods(cfg):
    k, m = ("kda", "moe"), ("mla", "moe")
    assert hybrid.segments(cfg) == [
        ((("kda", "dense"), k, k, m), 1), ((k, m), 1)]
    big = hybrid.segments(ModelConfig.kimi_linear_48b())
    assert [n for _, n in big] == [1, 5, 1]
    assert big[1][0] == (k, k, k, m) and big[2][0] == (k, k, m)
    g, a = ("gdn", "moe"), ("gated_attn", "moe")
    assert hybrid.segments(ModelConfig.qwen3_next_80b(num_layers=12)) == [
        ((g, g, g, a), 3)]


# -- (c) the router's form --------------------------------------------------


def test_sigmoid_router_bias_moves_the_choice_and_not_the_weight():
    logits = jnp.asarray([[2.0, 1.0, 0.5, -1.0, 0.0, -3.0]])
    score = np.asarray(jax.nn.sigmoid(logits))[0]
    w, ids = topk_routing(logits, 2, score="sigmoid", scale=2.446)
    assert list(np.asarray(ids[0])) == [0, 1]
    np.testing.assert_allclose(
        w[0], score[[0, 1]] / score[[0, 1]].sum() * 2.446, rtol=1e-6)
    assert float(w.sum()) == pytest.approx(2.446, rel=1e-6)  # renormalised
    # a bias lifts expert 3 over expert 1: the choice moves, and the
    # weights are the chosen experts' own scores, the bias not in them
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.9, 0.0, 0.0])
    wb, idb = topk_routing(logits, 2, score="sigmoid", bias=bias,
                           scale=2.446)
    assert list(np.asarray(idb[0])) == [3, 0]  # in the biased order
    np.testing.assert_allclose(
        wb[0], score[[3, 0]] / score[[0, 3]].sum() * 2.446, rtol=1e-6)
    # the softmax form is the one it was
    ws, _ = topk_routing(logits, 2)
    soft = np.asarray(jax.nn.softmax(logits))[0]
    np.testing.assert_allclose(ws[0], soft[[0, 1]] / soft[[0, 1]].sum(),
                               rtol=1e-6)


# -- (d) the share ----------------------------------------------------------


def test_four_shares_and_the_shared_expert_add_up(ref):
    """Each chip of a group of four computes its two experts' part and
    the ungated shared expert; the parts, with the shared expert
    counted once, are the uncut reference's expert layer."""
    rng = np.random.default_rng(3)
    h, e, i = 64, 8, 32
    x = jnp.asarray(rng.standard_normal((12, h)), jnp.float32)

    def w(*shape):
        return jnp.asarray(rng.standard_normal(shape) * 0.2, jnp.float32)

    full = dict(w_router=w(h, e), router_bias=w(e), w_gate_up=w(e, h, 2 * i),
                w_down=w(e, i, h), ws_gate_up=w(h, 2 * i), ws_down=w(i, h))
    base = ref.Sizes.from_config(FILE)
    want = ref.experts(dataclasses.replace(base, held=e, offset=0), x, full,
                       None)
    shared = ref.experts(
        dataclasses.replace(base, held=0, offset=0), x,
        dict(full, w_gate_up=full["w_gate_up"][:0],
             w_down=full["w_down"][:0]), None)
    valid = jnp.ones((12,), bool)
    form = RouterForm("sigmoid", 2.446)
    total, pairs = 0.0, 0
    for off in range(0, e, 2):
        p = HeldMoEParams(full["w_router"], full["w_gate_up"][off:off + 2],
                          full["w_down"][off:off + 2], full["ws_gate_up"],
                          full["ws_down"], None, full["router_bias"])
        y, here, absent = held_moe_fwd(x, valid, p, 2, off, router=form)
        assert int(here) + int(absent) == 12 * 2
        total, pairs = total + y, pairs + int(here)
    assert pairs == 12 * 2  # every pair computed on exactly one chip
    np.testing.assert_allclose(np.asarray(total - 3 * shared),
                               np.asarray(want), atol=2e-5, rtol=0)


# -- (f) the pool -----------------------------------------------------------


def test_the_pool_is_one_latent_array_and_the_state_beside_it(eng, cfg):
    pool = Scheduler(eng, **GEO).pool
    # two latent blocks; a row of 32 + 8 values padded to whole lanes;
    # ONE array: nothing stands where the values' pool would
    assert pool.k.shape == (2, 1 + GEO["slots"] * 8, GEO["page"], 1, 128)
    assert pool.v is None and len(pool.state) == 3
    assert pool.kv_bytes_per_token == 2 * 128 * 4
    assert pool.rec.shape == (4, GEO["slots"], 4, 16, 16)
    assert pool.rec.dtype == jnp.float32
    assert pool.conv.shape == (4, GEO["slots"], 3, 3 * 4 * 16)
    pool.admit(0, 20)
    pool.check()
    dense = pool.to_dense()
    assert dense.v is None and dense.k.shape == (2, GEO["slots"], 64, 1, 128)
    for call in (lambda: pool.export_pages(0), lambda: pool.cow(0, 0),
                 lambda: pool.share(1, [1], 8), pool.as_mega_cache):
        with pytest.raises(NotImplementedError, match="recurrent"):
            call()


def test_eviction_and_reprefill_keep_the_tokens(eng, prompts):
    _, want = _serve(eng, prompts, 9)
    sch, got = _serve(eng, prompts, 9, total_pages=6)
    counters = sch.obs.snapshot()["counters"]
    assert sum(v for k, v in counters.items()
               if k.startswith("serve_evicted")) >= 1
    assert counters["serve_state_resets"] > len(prompts)
    assert got == want
    sch.pool.check()


def test_batch_and_chunk_alignment_keep_the_tokens(eng, prompts):
    _, together = _serve(eng, prompts, 6)
    alone = [_serve(eng, [p], 6)[1][0] for p in prompts]
    _, wider = _serve(eng, prompts, 6, chunk=8)
    assert together == alone == wider


def test_counters_mean_what_they_mean_for_the_other_member(
        eng, cfg, prompts):
    sch, _ = _serve(eng, prompts, 5)
    c = sch.obs.snapshot()["counters"]
    rows = c["serve_rows{state=prefill}"] + c["serve_rows{state=decode}"]
    pairs = c["moe_pairs{held=here}"] + c["moe_pairs{held=absent}"]
    # 5 expert blocks of 6: the leading block's MLP routes nothing
    assert cfg.num_moe_layers == 5
    assert pairs == rows * 5 * cfg.num_experts_per_tok
    assert 0 < c["moe_pairs{held=here}"] < pairs
    assert c["moe_expert_steps"] == c["serve_steps"] * 5 * HELD
    assert 0 < c["serve_state_bytes_live"] <= c["serve_state_bytes_moved"]
    assert c["serve_state_resets"] == len(prompts)
    # the byte counters are the token counters times the family's row
    per = sch.pool.kv_bytes_per_token
    assert c["serve_kv_bytes_live"] == per * c["serve_kv_tokens_live"]
    assert c["serve_kv_bytes_gathered"] == \
        per * c["serve_kv_tokens_gathered"]
    assert sch.worker.widths == (GEO["chunk"],)


@pytest.mark.parametrize("kw, names", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(role="prefill", migrate_to=object()), "xslice"),
])
def test_scheduler_refuses_what_cannot_carry_the_state(eng, kw, names):
    with pytest.raises(NotImplementedError, match="recurrent") as e:
        Scheduler(eng, **GEO, **kw)
    assert names in str(e.value)


def test_more_than_one_device_is_refused_and_says_why(cfg):
    with pytest.raises(NotImplementedError, match="expert-parallel"):
        Engine(cfg, make_mesh(mesh_shape=(2,), axis_names=("tp",)),
               max_len=MAX_LEN)
