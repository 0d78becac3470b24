"""The Kimi-Linear family's benchmark files: the repository's
BENCHMARK.json keeps the contract with the configuration and the cell
added; the work counts against hand counts; the two readers this
family brings, on planted counters and on the fixture's trace; and a
tiny copy of the family (a leading dense block, a whole period and a
short one, 8 experts of which 4 held, float32) through `run_cell` on
the CPU: `correct` as served, not `correct` with one token altered."""

import json
import os

import pytest

import perfbench_tiny as tiny
from perfbench import contract, harness, work
from perfbench.sources import device_trace

REPO = tiny.REPO
CELL = "kl48b-1chip.longdoc-closed"
CONFIG = "kimi-linear-48b.1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

TINY_KIMI = {
    "model_type": "kimi_linear", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 96, "num_hidden_layers": 6,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "mla_use_nope": True,
    "linear_attn_config": {
        "full_attn_layers": [4, 6], "kda_layers": [1, 2, 3, 5],
        "head_dim": 16, "num_heads": 4, "short_conv_kernel_size": 4},
    "num_experts": 4, "num_experts_per_token": 2, "num_shared_experts": 1,
    "moe_intermediate_size": 32, "routed_scaling_factor": 2.446,
    "first_k_dense_replace": 1, "num_expert_group": 1,
    "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
    "rope_theta": 10000, "rms_norm_eps": 1e-05,
    "tie_word_embeddings": False, "torch_dtype": "float32",
    "expert_parallel": {"chips_sharing_a_layer": 2, "router_width": 8,
                        "expert_offset": 4},
    "published": "tiny-test",
    "stands_for": "a test-scale Kimi-Linear pattern on the CPU",
    "serve": {"chips": 1, "tp": 1, "slots": 4, "max_len": 64},
    "family": "kimi_linear", "reference": "kimi_linear",
    "whole_step": "kimi_linear_step",
    "check": {"control": "bf16", "gap_limit": 0.001},
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


def test_the_configuration_cuts_the_experts_held_and_nothing_else(
        bench, cfg):
    entry = harness.find(bench["configs"], CONFIG, "configuration")
    assert entry["reduced"] == ["num_experts"]
    record = harness.load_published(REPO, cfg["published"])
    assert entry["source"] == record["source_url"]
    pub = record["config"]
    assert {k for k, v in pub.items() if cfg.get(k) != v} == {"num_experts"}
    assert (cfg["num_experts"], cfg["num_hidden_layers"],
            cfg["vocab_size"]) == (16, 27, 163840)
    assert cfg["published_counts"] == {"num_experts": pub["num_experts"]}
    assert cfg["expert_parallel"] == {
        "chips_sharing_a_layer": 16, "router_width": 256,
        "expert_offset": 0}
    widths = harness.load_family(REPO, cfg["family"]).WIDTHS
    assert all(cfg[k] == pub[k] for k in widths)
    assert {"hidden_size", "intermediate_size", "moe_intermediate_size",
            "num_attention_heads", "head_dim", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "num_experts_per_token", "num_shared_experts",
            "routed_scaling_factor", "first_k_dense_replace",
            "linear_attn_config"} <= set(widths)
    assert cfg["serve"] == {"chips": 1, "tp": 1, "slots": 8,
                            "max_len": 8192}
    check = cfg["check"]
    assert set(check) == {"control", "gap_limit", "gap_quantile"}
    assert check["control"] == "fp8" and 0.5 <= check["gap_quantile"] < 1
    assert isinstance(check["gap_limit"], float) and check["gap_limit"] > 0
    if os.path.exists(CATALOG):  # the record is the catalog's row
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == record["name"])
        assert row["source_url"] == record["source_url"]
        assert row["config"] == pub


def test_the_family_file_builds_the_program_s_configuration(cfg):
    mc = harness.load_family(REPO, cfg["family"]).model_config(cfg)
    kinds = mc.mixer_kinds
    assert len(kinds) == 27 and kinds.count("kda") == 20
    assert [i + 1 for i, k in enumerate(kinds) if k == "mla"] == [
        4, 8, 12, 16, 20, 24, 27]
    assert mc.ffn_kinds == ("dense",) + ("moe",) * 26
    assert (mc.num_experts, mc.num_experts_held, mc.router_score,
            mc.router_bias, mc.shared_expert_gate) == (
        256, 16, "sigmoid", True, False)
    assert mc.page_arrays == ((1, 640),)  # 576 padded to whole lanes
    assert mc.kv_bytes_per_token == 1280


def test_the_cell_and_the_entries_that_list_it(bench):
    cell = harness.find(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longdoc-closed", 1)
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    # not `tokens_per_s`, as for the other cell of this mix (PR 30);
    # not `ttft_p95_ms`: of some 33 requests due a window the 95th
    # percentile is the second largest wait; of four sets of six on
    # the final step two spread it 3.2% and 3.6%, over half its bound
    # (PERF.md section 6, PR 35)
    assert e2e == {"itl_p95_ms", "setup_s"}
    lists = {m["name"] for m in bench["per_layer"]
             if CELL in m["workloads"]}
    assert {"step.mfu_pct", "step.wall_p50_ms", "kv.gather_live_pct",
            "sched.prefill_rows_pct", "device.idle_pct"} <= lists
    # its work counts keys and values a head
    assert "kernel.flash_prefill_full.roofline_pct" not in lists


def _with_the_waiting_entries(bench, also=()):
    """`bench` with the two per-layer entries this family's readers
    wait for, APPENDED: where a `benchmark` PR puts them once
    test_perfbench_narrow_steps.py finds its entry by name and not at
    `per_layer[-1]` (PERF.md, section 7)."""
    with open(os.path.join(REPO, "perfbench", "fixtures",
                           "per_layer.kimi_linear.json")) as f:
        waiting = json.load(f)
    for m in waiting:
        m["workloads"] += list(also)
    return dict(bench, per_layer=bench["per_layer"] + waiting), waiting


def test_the_waiting_entries_keep_the_contract_and_have_their_files(bench):
    full, waiting = _with_the_waiting_entries(bench)
    assert contract.violations(full, REPO) == []
    assert [(m["name"], m["unit"], m["better"], m["source"], m["moves"])
            for m in waiting] == [
        ("kernel.mla_attn.roofline_pct", "%", "higher", "device_trace",
         "itl_p95_ms"),
        ("kv.bytes_per_live_token", "B", "lower", "program_counter",
         "itl_p95_ms")]
    assert waiting[0]["workloads"] == [CELL]
    assert waiting[1]["workloads"] == ["q3n-1chip.longdoc-closed", CELL]
    for m in waiting:
        assert callable(harness.load_reader(REPO, m["name"]).read)
        assert m["name"] not in {x["name"] for x in bench["per_layer"]}


SIZES = dict(L=27, Lk=20, Lf=7, Ld=1, Lm=26, H=2304, V=163840, I=9216,
             hq=32, dn=128, dr=64, dvh=128, c=512, W=640, E=256, Eh=16,
             k=8, Im=1024, Is=1024, Hl=32, dk=128, dv=128, K=4, r=128,
             tp=1, b=2)


def test_size_vars_are_the_configuration_s(cfg):
    assert harness.load_family(REPO, cfg["family"]).size_vars(cfg) == SIZES


def test_whole_step_work_against_a_hand_count():
    spec = work.load(REPO, "kimi_linear_step")
    assert spec["whole_step"] is True
    n, ctx = 128, 4096
    need = work.step_needs(spec, SIZES, [(n, ctx, True)])
    kda = (2 * 2304 * (3 * 4096 + 256 + 32) + 2 * 128 * 8192
           + 2 * 4096 * 2304 + 2 * 4 * 12288 + 6 * 32 * 128 * 128)
    mla = (2 * 2304 * 32 * 192 + 2 * 2304 * 576 + 2 * 512 * 32 * 256
           + 2 * 4096 * 2304)
    moe = (2 * 2304 * 256 + 8 * 16 / 256 * 6 * 2304 * 1024
           + 6 * 2304 * 1024)
    dense = 6 * 2304 * 9216
    core = 7 * 32 * 2 * 320 * (n * ctx + n * (n + 1) / 2)
    want = n * (20 * kda + 7 * mla + 26 * moe + dense) + core \
        + 2 * 2304 * 163840
    assert need["flops"] == pytest.approx(want, rel=1e-12)
    assert need["hbm_bytes"] == 0 and need["ici_bytes"] == 0
    # a padding-only step needs nothing
    assert work.step_needs(spec, SIZES, [])["flops"] == 0


def test_latent_attention_work_against_a_hand_count():
    spec = work.load(REPO, "mla_attn")
    n, ctx = 128, 4096
    need = work.step_needs(spec, SIZES, [(n, ctx, False), (1, 100, True)])
    pairs = (n * ctx + n * (n + 1) / 2) + (100 + 1)
    assert need["flops"] == pytest.approx(7 * 32 * 2 * 320 * pairs)
    rows = (ctx + n) + 101
    assert need["hbm_bytes"] == pytest.approx(
        7 * 2 * (rows * 640 + (n + 1) * 32 * (640 + 512)))
    # the absorbed form's own count is over the bound's, so its share
    # of this roofline cannot pass 100%
    assert 2 * (640 + 512) > 2 * 320
    assert work.patterns(spec) == ["*_fp_local_kernel*"]


def _view(**kw):
    base = dict(root=REPO, counters={}, trace=None, trace_steps=[],
                say=lambda m: None)
    return harness.RunView(**{**base, **kw})


def test_bytes_per_live_token_on_planted_counters():
    read = harness.load_reader(REPO, "kv.bytes_per_live_token").read
    assert read(_view(counters={"serve_kv_bytes_gathered": 8960 * 1000,
                                "serve_kv_tokens_live": 2000})) == 4480.0
    # the parent has no byte counters; no live position, no quotient
    assert read(_view(counters={"serve_kv_tokens_live": 2000})) is None
    assert read(_view(counters={"serve_kv_bytes_gathered": 5,
                                "serve_kv_tokens_live": 0})) is None


def test_mla_roofline_on_the_fixture_and_on_nothing():
    """The fixture's trace is a dense cell's: its `_fp_local_kernel`
    events stand in for the latent ones, the share comes out between 0
    and 100 under this family's sizes; without a trace, or with one
    that holds no such event, the reader says nothing."""
    from perfbench.sources import program_span

    read = harness.load_reader(REPO, "kernel.mla_attn.roofline_pct").read
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
    assert read(_view(trace=trace, trace_steps=[], sizes=SIZES,
                      peaks=peaks)) is None


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root, bench, cell = tiny.make_root(tmp_path_factory.mktemp("kl"),
                                       mix=MIX, config=TINY_KIMI)
    bench, _ = _with_the_waiting_entries(bench, also=[cell["name"]])
    tiny.write_json(root, "BENCHMARK.json", bench)
    return root, bench, cell


def test_a_tiny_cell_of_the_family_reads_correct_and_its_metrics(
        tiny_root):
    root, bench, cell = tiny_root
    assert contract.violations(bench, root) == []
    result, lines = tiny.rehearse(root, bench, cell, seconds=2.0,
                                  trace=True)
    assert result["correct"] is True and result["failed"] == 0
    assert result["checks"]["stream_mismatches"]["value"] == 0
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert 0.0 < got["step.mfu_pct"] < 100.0
    assert 0.0 < got["kv.gather_live_pct"] <= 100.0
    # tiny.add_cell lists the cell under every metric that lists cells:
    # the expert layer's and the state's counters mean here what they
    # mean for the other hybrid family
    assert 35.0 < got["moe.local_pairs_pct"] < 65.0  # 4 of 8 held
    assert 0.0 < got["state.live_pct"] <= 100.0
    # the waiting entries, through the harness: a gathered position is
    # the view's whole width of ONE latent row a page layer (2 layers x
    # 128 float32 values), a live one costs more than that; the CPU's
    # route runs no kernel, so the roofline's reader says nothing
    assert got["kv.bytes_per_live_token"] > 2 * 128 * 4
    assert "kernel.mla_attn.roofline_pct" not in got


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
