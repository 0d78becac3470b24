"""The Granite 4.0-H family with experts, in the benchmark: the
repository's BENCHMARK.json keeps the contract with the configuration,
its cell and `moe.tile_rows_real_pct` added, and with the accepted
entries that read something in the cell listing it; the configuration
against its record (four keys reduced, every width as published); the
work counts and the memory against hand counts; the new reader on
planted counters; and a tiny copy of the family (`M A` twice, `M M`, 8
experts of which 4 held, float32) through `run_cell` on the CPU:
`correct` as served, not `correct` with the state-space state or the
held experts' output dropped. Each rehearsal runs until its scored
requests all finished, however slow the CPU."""

import math
import os

import pytest

import by_name as entries
import perfbench_tiny as tiny
from perfbench import contract, harness, work

REPO = tiny.REPO
CELL = "g4hs-1chip.longdoc-closed"
CONFIG = "granite-4.0-h-small.1chip"
# the accepted entries that read something here beside the fifteen
# every cell lists: the state-space state under the delta nets'
# counters, the expert layer's counters, the attention blocks' pages,
# `_fp_local_kernel`'s events against work `flash_prefill_full_layers`
EXTENDED = ("state.live_pct", "moe.local_pairs_pct",
            "moe.pairs_per_expert_step", "kv.bytes_per_live_token",
            "kernel.flash_prefill_full.roofline_pct")
NEW = "moe.tile_rows_real_pct"
EXPERT_CELLS = ("q3n-1chip.longdoc-closed", "kl48b-1chip.longdoc-closed",
                "kx236b-1chip.longdoc-closed", CELL)
REDUCED = ["num_hidden_layers", "layer_types", "num_local_experts",
           "vocab_size"]
M, A = "mamba", "attention"
CHIP_BYTES = 16.91e9

TINY_GRANITE_MOE = {
    "model_type": "granitemoehybrid", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 32, "shared_intermediate_size": 48,
    "num_hidden_layers": 6, "num_attention_heads": 4,
    "num_key_value_heads": 2, "layer_types": [M, A] * 2 + [M, M],
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "attention_bias": False,
    "num_local_experts": 4, "num_experts_per_tok": 2,
    "position_embedding_type": "nope", "normalization_function": "rmsnorm",
    "hidden_act": "silu", "tie_word_embeddings": True,
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "attention_multiplier": 0.0625, "logits_scaling": 16,
    "rms_norm_eps": 1e-05, "torch_dtype": "float32",
    "expert_parallel": {"chips_sharing_a_layer": 2, "router_width": 8,
                        "expert_offset": 4, "vocab_offset": 0},
    "published": "tiny-test",
    "stands_for": "a test-scale Granite 4.0-H pattern with experts on the "
                  "CPU",
    "serve": {"chips": 1, "tp": 1, "slots": 4, "max_len": 64},
    "family": "granite_moe_hybrid", "reference": "granite_moe_hybrid",
    "whole_step": "granite_moe_hybrid_step",
    # float32 served against the float32 reference: the program's
    # first choice is the reference's at every served position (a gap
    # of 0); a token that is not reads its logit's distance under the
    # best, 1e-6 and more where the state or the experts are dropped
    "check": {"control": "bf16", "gap_limit": 1e-06},
}
# prompts of 3-5 chunks of 8, outputs of 12-20 tokens: every request
# crosses fifteen steps or more. A window runs until all 40 planned
# requests finished, and every one is scored: the same tokens whatever
# the CPU's speed
MIX = dict(tiny.TINY_MIX, max_requests=40,
           prompt={"dist": "lognormal", "median": 28, "sigma": 0.3,
                   "min": 20, "max": 40},
           output={"dist": "uniform", "min": 12, "max": 20},
           check_requests=1000)


@pytest.fixture(scope="module")
def bench():
    return entries.load()


@pytest.fixture(scope="module")
def cfg(bench):
    return harness.load_json(os.path.join(
        REPO, entries.config(bench, CONFIG)["file"]))


def test_the_repository_s_benchmark_keeps_the_contract(bench):
    assert contract.violations(bench, REPO) == []
    assert entries.cell(bench, CELL)["config"] == entries.config(
        bench, CONFIG)["name"]


def test_the_configuration_against_its_record(bench, cfg):
    entry = entries.config(bench, CONFIG)
    assert entry["reduced"] == REDUCED
    record = harness.load_published(REPO, cfg["published"])
    assert entry["source"] == record["source_url"]
    pub = record["config"]
    assert {k for k, v in pub.items() if cfg[k] != v} == set(REDUCED)
    assert (cfg["num_hidden_layers"], cfg["num_local_experts"],
            cfg["vocab_size"]) == (10, 36, 50176)
    assert cfg["layer_types"] == pub["layer_types"][:10]
    assert cfg["layer_types"] == [M] * 5 + [A] + [M] * 4
    assert cfg["published_counts"] == {
        k: pub[k] for k in ("num_hidden_layers", "num_local_experts",
                            "vocab_size")}
    widths = harness.load_family(REPO, cfg["family"]).WIDTHS
    assert all(cfg[k] == pub[k] for k in widths)
    assert {"intermediate_size", "num_experts_per_tok",
            "shared_intermediate_size"} <= set(widths)
    # the deployment: two chips a block, the router as published
    assert cfg["expert_parallel"] == {
        "chips_sharing_a_layer": 2, "router_width": 72, "expert_offset": 0,
        "vocab_offset": 0}
    assert cfg["vocab_size"] * 2 == pub["vocab_size"]
    assert "stage 1" in cfg["stands_for"] and "two" in cfg["stands_for"]
    assert cfg["serve"] == {"chips": 1, "tp": 1, "slots": 8,
                            "max_len": 8192}
    assert {"dtype", "expert_width", "head_dim", "weights", "mixer",
            "gated_norm", "chunk", "attention", "router", "experts",
            "shared", "multipliers", "layouts"} <= set(cfg["assumed"])
    check = cfg["check"]
    assert set(check) == {"control", "gap_limit", "gap_quantile"}
    assert check["control"] == "fp8" and 0.5 <= check["gap_quantile"] <= 1
    assert isinstance(check["gap_limit"], float) and check["gap_limit"] > 0


def test_the_family_file_builds_the_program_s_configuration(cfg):
    from triton_dist_tpu.models import ModelConfig

    mc = harness.load_family(REPO, cfg["family"]).model_config(cfg)
    assert mc == ModelConfig.granite_4_h_small(
        num_layers=10, experts_held=36, vocab_size=50176,
        max_positions=8192)
    assert mc.mixer_kinds.count("mamba2") == 9
    assert set(mc.ffn_kinds) == {"moe"} and mc.num_moe_layers == 10
    assert (mc.num_experts, mc.num_experts_held, mc.expert_offset,
            mc.num_experts_per_tok) == (72, 36, 0, 10)
    assert (mc.router_score, mc.router_bias, mc.shared_expert_gate) == (
        "softmax", False, False)
    assert (mc.head_dim, mc.page_arrays) == (128, ((8, 128),) * 2)
    assert mc.tie_word_embeddings and not mc.use_qk_norm


def test_the_cell_and_the_entries_that_list_it(bench):
    cell = entries.cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longdoc-closed", 1)
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert {"itl_p95_ms", "setup_s"} <= e2e
    assert "ttft_p95_ms" not in e2e  # as in g4hm: ROADMAP C9
    lists = entries.listing(bench, CELL)
    assert set(entries.GENERIC + EXTENDED + (NEW,)) <= lists
    new = entries.metric(bench, NEW)
    assert set(EXPERT_CELLS) <= set(new["workloads"])
    assert (new["unit"], new["better"], new["source"], new["layer"],
            new["moves"]) == ("%", "higher", "program_counter",
                              "expert layer", "itl_p95_ms")
    for name in EXTENDED + (NEW,):
        assert callable(harness.load_reader(REPO, name).read)


SIZES = dict(L=10, Ls=9, Lf=1, H=4096, V=50176, hq=32, hkv=8, d=128,
             Hm=128, P=64, N=128, Di=8192, K=4, E=72, Eh=36, k=10, Im=768,
             Is=1536, tp=1, b=2)


def test_size_vars_are_the_configuration_s(cfg):
    assert harness.load_family(REPO, cfg["family"]).size_vars(cfg) == SIZES


def test_whole_step_work_against_a_hand_count():
    spec = work.load(REPO, "granite_moe_hybrid_step")
    assert spec["whole_step"] is True
    n, ctx = 128, 4096
    need = work.step_needs(spec, SIZES, [(n, ctx, True)])
    ffn = 2 * 4096 * 72 + 10 * 36 / 72 * 6 * 4096 * 768 + 6 * 4096 * 1536
    mamba = (2 * 4096 * 16768 + 2 * 8192 * 4096 + 2 * 4 * 8448
             + 4 * 128 * 64 * 128)
    attn = 2 * 4096 * (32 + 16) * 128 + 2 * 4096 * 4096
    core = 4 * 32 * 128 * (n * ctx + n * (n + 1) / 2)
    head = 2 * 4096 * 50176
    want = n * (10 * ffn + 9 * mamba + attn) + core + head
    assert need["flops"] == pytest.approx(want, rel=1e-12)
    assert need["hbm_bytes"] == 0 and need["ici_bytes"] == 0
    # 2 x the parameters a row touches outside the head, and a little:
    # 0.96 B of the mixers, 10 x (0.29 + 18.87 + 5 x 9.44) M = 0.66 B
    # of the expert layers; the recurrence, the convolution
    assert 3.25e9 < (want - core - head) / n < 3.35e9
    assert work.step_needs(spec, SIZES, [])["flops"] == 0


def test_attention_work_reads_this_family_s_sizes():
    spec = work.load(REPO, "flash_prefill_full_layers")
    n, ctx = 128, 4096
    need = work.step_needs(spec, SIZES, [(n, ctx, False)])
    assert need["flops"] == 4 * 32 * 128 * (n * ctx + n * (n + 1) / 2)
    assert need["hbm_bytes"] == 2 * (2 * n * 32 * 128
                                     + 2 * (ctx + n) * 8 * 128)


def test_the_memory_the_configuration_holds(cfg):
    """The arithmetic of the configuration's file: 4.76 B parameters
    (the held experts 3.40 B of them) in bfloat16, the state-space
    state beside one attention block's pages, together over a quarter
    of the chip."""
    from triton_dist_tpu.models import hybrid

    mc = harness.load_family(REPO, cfg["family"]).model_config(cfg)
    params = {n: math.prod(s) for n, s, _ in hybrid.leaves(mc)}
    total = sum(params.values())
    experts = params["w_gate_up"] + params["w_down"]
    assert 4.75e9 < total < 4.77e9 and 3.39e9 < experts < 3.41e9
    assert params["embed"] == 50176 * 4096
    slots, max_len = cfg["serve"]["slots"], cfg["serve"]["max_len"]
    rec, conv = hybrid.state_shapes(mc, slots)
    assert rec == (9, slots, 128, 64, 128) and conv == (9, slots, 3, 8448)
    state = 4 * math.prod(rec) + 2 * math.prod(conv)
    assert 0.30e9 < state < 0.31e9
    pages = mc.num_kv_layers * mc.kv_bytes_per_token * slots * max_len
    assert mc.num_kv_layers * mc.kv_bytes_per_token == 4096
    held = 2 * total + state + pages
    assert 0.25 * CHIP_BYTES < 9.5e9 < held < 10.2e9 < CHIP_BYTES


def _view(**kw):
    base = dict(root=REPO, counters={}, trace=None, trace_steps=[],
                say=lambda m: None)
    return harness.RunView(**{**base, **kw})


def test_tile_rows_real_pct_on_planted_counters():
    read = harness.load_reader(REPO, NEW).read
    assert read(_view()) is None
    # the ragged_dot route visits no tile: nothing to read
    assert read(_view(counters={"moe_pairs{held=here}": 480,
                                "moe_gmm_tile_rows": 0})) is None
    # 48 pairs in one 128-row tile, both products: 37.5%
    assert read(_view(counters={"moe_pairs{held=here}": 48,
                                "moe_gmm_tile_rows": 256})) == 37.5
    assert read(_view(counters={"moe_pairs{held=here}": 256,
                                "moe_gmm_tile_rows": 512})) == 100.0


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    # tiny.add_cell lists the cell under every entry that lists cells
    return tiny.make_root(tmp_path_factory.mktemp("g4hs"), mix=MIX,
                          config=TINY_GRANITE_MOE)


def _rehearsed(root, bench, cell, seed, trace=False):
    """`run_cell` over windows of 4, 8, 16 ... s until the window
    finished every planned request: (result, lines, the fewest steps
    such a request crossed)."""
    for seconds in (4.0, 8.0, 16.0, 32.0, 64.0):
        seen, sent = [], []

        def tamper(sch):
            submit = sch.submit

            def recorded(*a, **kw):
                sent.append(submit(*a, **kw))
                return sent[-1]

            sch.submit = recorded
            seen.append(sch)

        result, lines = tiny.rehearse(root, bench, cell, seed=seed,
                                      seconds=seconds, trace=trace,
                                      tamper=tamper)
        steps = {}
        for h in seen[0].history:
            for rid, _state, _n in h["slots"].values():
                steps[rid] = steps.get(rid, 0) + 1
        # the window's requests, not the warm-up's
        done = [steps[r.request_id] for r in sent
                if r.max_new_tokens > harness.WARM_NEW_TOKENS and r.done
                and len(r.out_tokens) == r.max_new_tokens]
        if len(done) == MIX["max_requests"]:
            return result, lines, min(done)
    raise AssertionError("the planned requests did not finish in 64 s")


def test_a_tiny_cell_of_the_family_reads_correct_and_its_metrics(
        tiny_root):
    root, bench, cell = tiny_root
    assert contract.violations(bench, root) == []
    result, lines, crossed = _rehearsed(root, bench, cell, seed=2**31 + 11,
                                        trace=True)
    assert crossed >= 15
    assert result["correct"] is True and result["failed"] == 0
    assert result["checks"]["stream_mismatches"]["value"] == 0
    assert result["checks"]["served_logit_gap_max"]["value"] == 0.0
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert 0.0 < got["step.mfu_pct"] < 100.0
    assert 0.0 < got["state.live_pct"] <= 100.0
    # 4 of 8 experts held: about half the pairs fall here
    assert 20.0 < got["moe.local_pairs_pct"] < 80.0
    assert got["moe.pairs_per_expert_step"] > 0.0
    # the interpreter's ragged_dot visits no tile, and no kernel runs
    for silent in (NEW, "kernel.flash_prefill_full.roofline_pct"):
        assert silent not in got
    assert any("reduced: nothing" in line for line in lines)


@pytest.mark.parametrize("lost", ["state", "experts"])
def test_the_state_or_the_experts_dropped_reads_not_correct(
        tiny_root, monkeypatch, lost):
    """The comparison that decides `correct`, with the state-space
    state thrown away after every step, or the held experts' part of
    every expert layer left out, on requests that crossed fifteen steps
    or more."""
    from triton_dist_tpu.models import hybrid

    if lost == "state":
        real = hybrid.mamba2_fwd

        def forgetful(hid, p, spec, rec, conv, *rest):
            y, _rec, conv2 = real(hid, p, spec, rec, conv, *rest)
            return y, rec, conv2

        monkeypatch.setattr(hybrid, "mamba2_fwd", forgetful)
    else:
        import jax.numpy as jnp

        real = hybrid.held_moe_counted

        def no_experts(x, valid, p, *a, **kw):
            return real(x, valid, p._replace(w_down=jnp.zeros_like(
                p.w_down)), *a, **kw)

        monkeypatch.setattr(hybrid, "held_moe_counted", no_experts)
    root, bench, cell = tiny_root
    # at this seed each drop moves 6-8 of the 640 tokens served off the
    # reference's first choice (at the tiny width a held expert adds a
    # few percent to the residual stream); the program moves none
    result, _, crossed = _rehearsed(root, bench, cell, seed=2**31 + 11)
    assert crossed >= 15
    assert result["correct"] is False
    gap = result["checks"]["served_logit_gap_max"]
    assert gap["value"] > gap["limit"]
