"""The Granite 4.0-H family's benchmark files: the repository's
BENCHMARK.json keeps the contract with the configuration and the cell
added, and with the two accepted entries that read something in the
cell listing it; the configuration against its record (nothing
reduced, every width as published); the work counts against hand
counts; those two readers, on planted counters and on the fixture's
trace under this family's sizes; and a tiny copy of the family (`M A`,
`M M A` twice, `M`, a state of 16, float32) through `run_cell` on the
CPU: `correct` as served, not `correct` with one token altered, with
the state or the convolution tail not carried."""

import json
import os

import pytest

import by_name as entries
import perfbench_tiny as tiny
from perfbench import contract, harness, work
from perfbench.sources import device_trace

REPO = tiny.REPO
CELL = "g4hm-1chip.longdoc-closed"
CONFIG = "granite-4.0-h-micro.1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the accepted entries that read something here: the state-space state
# under the delta nets' counters, `_fp_local_kernel`'s events against
# work `flash_prefill_full_layers`, whose formulae fit this family's
# sizes (d the useful 64); the expert layer's two find nothing here
EXTENDED = ("state.live_pct", "kernel.flash_prefill_full.roofline_pct")
MOE = ("moe.local_pairs_pct", "moe.pairs_per_expert_step")
M, A = "mamba", "attention"

TINY_GRANITE = {
    "model_type": "granitemoehybrid", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 96, "shared_intermediate_size": 96,
    "num_hidden_layers": 9, "num_attention_heads": 4,
    "num_key_value_heads": 2, "layer_types": [M, A] + [M, M, A] * 2 + [M],
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "attention_bias": False,
    "num_local_experts": 0, "num_experts_per_tok": 0,
    "position_embedding_type": "nope", "normalization_function": "rmsnorm",
    "hidden_act": "silu", "tie_word_embeddings": True,
    # the source's multipliers; the tied table is drawn at 0.02 / 12
    # (the configuration's `assumed.weights`), so the head's best token
    # is not the input's own and a served token tells of the mixers
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "attention_multiplier": 0.0625, "logits_scaling": 8,
    "rms_norm_eps": 1e-05, "torch_dtype": "float32",
    "published": "tiny-test",
    "stands_for": "a test-scale Granite 4.0-H pattern on the CPU",
    "serve": {"chips": 1, "tp": 1, "slots": 4, "max_len": 64},
    "family": "granite_hybrid", "reference": "granite_hybrid",
    "whole_step": "granite_hybrid_step",
    # float32 served against the float32 reference: a gap is a token
    # that is not the reference's first choice, and there is none; a
    # state not carried flips a few of 400 and reads 6e-4 and more
    "check": {"control": "bf16", "gap_limit": 0.00001},
}
# every request the window finishes is scored
MIX = dict(tiny.TINY_MIX, check_requests=1000)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg(bench):
    entry = harness.find(bench["configs"], CONFIG, "configuration")
    return harness.load_json(os.path.join(REPO, entry["file"]))


def test_the_repository_s_benchmark_keeps_the_contract(bench):
    assert contract.violations(bench, REPO) == []
    # the configuration and its cell, found by their names
    assert entries.cell(bench, CELL)["config"] == entries.config(
        bench, CONFIG)["name"]


def test_the_configuration_is_the_record_whole(bench, cfg):
    entry = harness.find(bench["configs"], CONFIG, "configuration")
    assert entry["reduced"] == []
    record = harness.load_published(REPO, cfg["published"])
    assert entry["source"] == record["source_url"]
    pub = record["config"]
    assert all(cfg[k] == v for k, v in pub.items())
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (40, 100352)
    assert cfg["layer_types"].count("mamba") == 36
    assert [i for i, t in enumerate(cfg["layer_types"])
            if t == "attention"] == [5, 15, 25, 35]
    widths = harness.load_family(REPO, cfg["family"]).WIDTHS
    assert all(cfg[k] == pub[k] for k in widths)
    assert set(widths) == {
        "hidden_size", "intermediate_size", "shared_intermediate_size",
        "num_attention_heads", "num_key_value_heads", "mamba_d_state",
        "mamba_d_head", "mamba_n_heads", "mamba_expand", "mamba_d_conv",
        "mamba_n_groups", "embedding_multiplier", "residual_multiplier",
        "attention_multiplier", "logits_scaling"}
    assert cfg["serve"] == {"chips": 1, "tp": 1, "slots": 8,
                            "max_len": 8192}
    assert {"dtype", "weights", "head_dim", "mixer", "gated_norm", "chunk",
            "attention", "multipliers", "page", "layouts"} <= set(
        cfg["assumed"])
    assert "no group" in cfg["stands_for"]
    check = cfg["check"]
    assert set(check) == {"control", "gap_limit", "gap_quantile"}
    assert check["control"] == "fp8" and 0.5 <= check["gap_quantile"] <= 1
    assert isinstance(check["gap_limit"], float) and check["gap_limit"] > 0
    if os.path.exists(CATALOG):  # the record is the catalog's row
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == record["name"])
        assert row["source_url"] == record["source_url"]
        assert row["config"] == pub


def test_the_family_file_builds_the_program_s_configuration(cfg):
    mc = harness.load_family(REPO, cfg["family"]).model_config(cfg)
    from triton_dist_tpu.models import ModelConfig

    assert mc == ModelConfig.granite_4_h_micro(max_positions=8192)
    assert mc.mixer_kinds.count("mamba2") == 36
    assert set(mc.ffn_kinds) == {"dense"} and mc.num_moe_layers == 0
    assert (mc.num_kv_layers, mc.num_window_layers) == (4, 0)
    assert (mc.head_dim, mc.page_arrays) == (64, ((8, 128),) * 2)
    # pages of the four attention blocks, a head kept 128 wide
    assert mc.num_kv_layers * mc.kv_bytes_per_token == 16384
    assert mc.tie_word_embeddings and not mc.use_qk_norm
    assert not mc.norm_zero_centred and mc.max_positions == 8192


def test_the_cell_and_the_entries_that_list_it(bench):
    cell = harness.find(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longdoc-closed", 1)
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert {"itl_p95_ms", "setup_s"} <= e2e <= {"itl_p95_ms", "setup_s",
                                               "ttft_p95_ms"}
    lists = entries.listing(bench, CELL)
    assert set(entries.GENERIC + EXTENDED) <= lists
    assert not lists & set(MOE)


def test_the_extended_lists_keep_the_contract_and_have_their_files(bench):
    assert contract.violations(bench, REPO) == []
    for name in EXTENDED:
        cells = entries.metric(bench, name)["workloads"]
        assert {"q3n-1chip.longdoc-closed", CELL} <= set(cells)
        assert callable(harness.load_reader(REPO, name).read)


SIZES = dict(L=40, Ls=36, Lf=4, H=2048, V=100352, I=8192, hq=32, hkv=8,
             d=64, Hm=64, P=64, N=128, Di=4096, K=4, tp=1, b=2)


def test_size_vars_are_the_configuration_s(cfg):
    assert harness.load_family(REPO, cfg["family"]).size_vars(cfg) == SIZES


def test_whole_step_work_against_a_hand_count():
    spec = work.load(REPO, "granite_hybrid_step")
    assert spec["whole_step"] is True
    n, ctx = 128, 4096
    need = work.step_needs(spec, SIZES, [(n, ctx, True)])
    mlp = 6 * 2048 * 8192
    mamba = (2 * 2048 * 8512 + 2 * 4096 * 2048 + 2 * 4 * 4352
             + 4 * 64 * 64 * 128)
    attn = 2 * 2048 * (32 + 16) * 64 + 2 * 2048 * 2048
    core = 4 * 4 * 32 * 64 * (n * ctx + n * (n + 1) / 2)
    want = n * (40 * mlp + 36 * mamba + 4 * attn) + core + 2 * 2048 * 100352
    assert need["flops"] == pytest.approx(want, rel=1e-12)
    assert need["hbm_bytes"] == 0 and need["ici_bytes"] == 0
    # 2 x the 2.99 B parameters outside the embedding a row, and a
    # little: the recurrence, the convolution
    assert 5.9e9 < (want - core - 2 * 2048 * 100352) / n < 6.2e9
    # a padding-only step needs nothing
    assert work.step_needs(spec, SIZES, [])["flops"] == 0


@pytest.mark.parametrize("n, ctx", [(128, 4096), (1, 900), (72, 0)])
def test_scan_work_against_a_hand_count(n, ctx):
    spec = work.load(REPO, "ssd_scan")
    need = work.step_needs(spec, SIZES, [(n, ctx, False)])
    assert need["flops"] == 36 * n * 4 * 64 * 64 * 128
    # the float32 state in and out once a block and slot (4 MiB), the
    # rows' x, B, C, z in and y out at 2 bytes, dt at 4
    state = 2 * 4 * 64 * 64 * 128
    rows = n * (2 * (3 * 4096 + 2 * 128) + 4 * 64)
    assert need["hbm_bytes"] == 36 * (state + rows)
    # XLA's fusions under the part's name: no event carries it yet (C8)
    assert work.patterns(spec) == ["*tdt.mixer.rule*"]
    peaks = work.peaks_for(REPO, "TPU v5 lite")
    assert work.least_seconds(need, peaks)[1] == "hbm_bytes"


def test_attention_work_counts_the_useful_head():
    spec = work.load(REPO, "flash_prefill_full_layers")
    n, ctx = 128, 4096
    need = work.step_needs(spec, SIZES, [(n, ctx, False)])
    assert need["flops"] == 4 * 4 * 32 * 64 * (n * ctx + n * (n + 1) / 2)
    assert need["hbm_bytes"] == 4 * 2 * (2 * n * 32 * 64
                                         + 2 * (ctx + n) * 8 * 64)


def _view(**kw):
    base = dict(root=REPO, counters={}, trace=None, trace_steps=[],
                say=lambda m: None)
    return harness.RunView(**{**base, **kw})


def test_state_live_pct_on_planted_counters():
    read = harness.load_reader(REPO, "state.live_pct").read
    assert read(_view(counters={"serve_state_bytes_live": 300,
                                "serve_state_bytes_moved": 400})) == 75.0
    assert read(_view()) is None


def test_attention_roofline_on_the_fixture_under_this_family_s_sizes():
    from perfbench.sources import program_span

    read = harness.load_reader(
        REPO, "kernel.flash_prefill_full.roofline_pct").read
    assert read(_view()) is None
    with open(os.path.join(REPO, "perfbench", "fixtures",
                           "trace_chat_closed_1chip.json")) as f:
        trace = device_trace.Trace.from_json(f.read())
    steps = [program_span.Step(0.0, 0.1, [
        program_span.Row(1, "prefill", 128, 2048, False),
        program_span.Row(2, "decode", 1, 900, True)])] * 3
    peaks = work.peaks_for(REPO, "TPU v5 lite")
    got = read(_view(trace=trace, trace_steps=steps, sizes=SIZES,
                     peaks=peaks))
    assert got is not None and 0.0 < got < 100.0


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    # tiny.add_cell lists the cell under every entry that lists cells
    return tiny.make_root(tmp_path_factory.mktemp("g4h"), mix=MIX,
                          config=TINY_GRANITE)


def test_a_tiny_cell_of_the_family_reads_correct_and_its_metrics(
        tiny_root):
    root, bench, cell = tiny_root
    assert contract.violations(bench, root) == []
    result, lines = tiny.rehearse(root, bench, cell, seconds=2.0,
                                  trace=True)
    assert result["correct"] is True and result["failed"] == 0
    assert result["checks"]["stream_mismatches"]["value"] == 0
    assert result["checks"]["served_logit_gap_max"]["value"] < 1e-6
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert 0.0 < got["step.mfu_pct"] < 100.0
    assert 0.0 < got["kv.gather_live_pct"] <= 100.0
    # the state-space state under the delta nets' counters
    assert 0.0 < got["state.live_pct"] <= 100.0
    # no expert layer, no window block, no kernel on the CPU's route:
    # those readers say nothing and do not raise
    for silent in ("moe.local_pairs_pct", "moe.pairs_per_expert_step",
                   "kernel.flash_prefill_full.roofline_pct"):
        assert silent not in got
    assert any("reduced: nothing" in line for line in lines)


def test_one_altered_token_reads_not_correct(tiny_root):
    root, bench, cell = tiny_root

    def tamper(sch):
        inner, emitted = sch._emit, []

        def emit(req, tok):
            emitted.append(tok)
            inner(req, (tok + 1) % 256 if len(emitted) == 25 else tok)

        sch._emit = emit

    result, _ = tiny.rehearse(root, bench, cell, seconds=1.5, tamper=tamper)
    assert result["correct"] is False
    gap = result["checks"]["served_logit_gap_max"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("lost", ["state", "tail"])
def test_a_state_not_carried_reads_not_correct(tiny_root, monkeypatch,
                                               lost):
    """The comparison that decides `correct`, with the state-space
    state, or the convolution's last inputs, thrown away after every
    step."""
    from triton_dist_tpu.models import hybrid

    real = hybrid.mamba2_fwd

    def forgetful(hid, p, spec, rec, conv, *rest):
        y, rec2, conv2 = real(hid, p, spec, rec, conv, *rest)
        return (y, rec, conv2) if lost == "state" else (y, rec2, conv)

    monkeypatch.setattr(hybrid, "mamba2_fwd", forgetful)
    root, bench, cell = tiny_root
    result, _ = tiny.rehearse(root, bench, cell, seconds=1.5, seed=77)
    assert result["correct"] is False
