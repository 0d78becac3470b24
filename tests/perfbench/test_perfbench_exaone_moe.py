"""The K-EXAONE family's benchmark files: the repository's
BENCHMARK.json keeps the contract with the configuration and the cell
added, and with the family's two per-layer entries appended; the
configuration against its record; the work counts against hand counts;
the two readers this family brings, on planted counters and on the
fixture's trace; and a tiny copy of the family (a leading dense block
and two periods `L L L G`, a window of 8, 8 experts of which 4 held,
float32) through `run_cell` on the CPU: `correct` as served, not
`correct` with one token altered."""

import json
import os

import pytest

import by_name as entries
import perfbench_tiny as tiny
from perfbench import contract, harness, work
from perfbench.sources import device_trace

REPO = tiny.REPO
CELL = "kx236b-1chip.longdoc-closed"
CONFIG = "k-exaone-236b.1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the entries the `q3n` cell brought: the expert layer's two read this
# cell's counters as they are; the state's and the full-attention
# kernel's find nothing here (no delta-net block; this family's kernel
# share is `kernel.window_attn.roofline_pct`)
MOE = ("moe.local_pairs_pct", "moe.pairs_per_expert_step")
NOT_HERE = ("state.live_pct", "kernel.flash_prefill_full.roofline_pct")
APPENDED = ("kernel.window_attn.roofline_pct", "kv.window_live_pct")
CUT = ("num_hidden_layers", "num_experts", "vocab_size", "layer_types",
       "mlp_layer_types", "sliding_windows")

TINY_EXAONE = {
    "model_type": "exaone_moe", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 96, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 2,
    "sliding_window": 8, "sliding_windows": [8, 8, 8, 0] * 2,
    "sliding_window_pattern": "LLLG",
    "mlp_layer_types": ["dense"] + ["sparse"] * 7,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "num_experts": 4, "num_experts_per_tok": 2, "num_shared_experts": 1,
    "moe_intermediate_size": 32, "routed_scaling_factor": 2.5,
    "first_k_dense_replace": 1, "n_group": 1, "topk_group": 1,
    "scoring_func": "sigmoid", "norm_topk_prob": True,
    "rms_norm_eps": 1e-05, "tie_word_embeddings": False,
    "torch_dtype": "float32",
    "expert_parallel": {"chips_sharing_a_layer": 2, "router_width": 8,
                        "expert_offset": 4},
    "published": "tiny-test",
    "stands_for": "a test-scale K-EXAONE pattern on the CPU",
    "serve": {"chips": 1, "tp": 1, "slots": 4, "max_len": 64},
    "family": "exaone_moe", "reference": "exaone_moe",
    "whole_step": "exaone_moe_step",
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
    # the four entries the `q3n` cell brought list it
    for name in MOE + NOT_HERE:
        assert "q3n-1chip.longdoc-closed" in entries.metric(
            bench, name)["workloads"]


def test_the_configuration_cuts_depth_experts_and_vocabulary_alone(
        bench, cfg):
    entry = harness.find(bench["configs"], CONFIG, "configuration")
    assert tuple(entry["reduced"]) == CUT
    record = harness.load_published(REPO, cfg["published"])
    assert entry["source"] == record["source_url"]
    pub = record["config"]
    assert {k for k, v in pub.items() if cfg.get(k) != v} == set(CUT)
    depth = cfg["num_hidden_layers"]
    assert (depth, cfg["num_experts"], cfg["vocab_size"]) == (8, 16, 19200)
    # the three per-layer lists are the published ones' first entries:
    # the leading dense block and two whole periods, 3 to 1 as published
    for k in ("layer_types", "mlp_layer_types", "sliding_windows"):
        assert cfg[k] == pub[k][:depth]
    assert cfg["layer_types"] == (["sliding_attention"] * 3
                                  + ["full_attention"]) * 2
    assert cfg["published_counts"] == {
        k: pub[k] for k in ("num_hidden_layers", "num_experts",
                            "vocab_size")}
    ep = cfg["expert_parallel"]
    assert (ep["chips_sharing_a_layer"], ep["router_width"],
            ep["expert_offset"]) == (8, 128, 0)
    assert ep["chips_sharing_a_layer"] * cfg["num_experts"] == 128
    assert ep["chips_sharing_a_layer"] * cfg["vocab_size"] == 153600
    widths = harness.load_family(REPO, cfg["family"]).WIDTHS
    assert all(cfg[k] == pub[k] for k in widths)
    assert set(widths) == {
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "num_experts_per_tok", "num_shared_experts",
        "routed_scaling_factor", "first_k_dense_replace",
        "sliding_window", "sliding_window_pattern"}
    assert not set(widths) & set(CUT)
    assert cfg["serve"] == {"chips": 1, "tp": 1, "slots": 8,
                            "max_len": 8192}
    assert {"rotary", "norms", "router", "dtype", "weights",
            "not_served"} <= set(cfg["assumed"])
    assert "multi-token-prediction" in cfg["assumed"]["not_served"]
    assert "stands_for" in cfg
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
    assert mc.mixer_kinds == (("window_attn",) * 3 + ("global_attn",)) * 2
    assert mc.ffn_kinds == ("dense",) + ("moe",) * 7
    assert (mc.num_experts, mc.num_experts_held, mc.router_score,
            mc.router_bias, mc.shared_expert_gate) == (
        128, 16, "sigmoid", True, False)
    assert (mc.num_kv_layers, mc.num_window_layers, mc.sliding_window) == (
        2, 6, 128)
    assert mc.page_arrays == ((8, 128),) * 2
    # pages of the two global blocks alone: 8,192 B a position
    assert mc.num_kv_layers * mc.kv_bytes_per_token == 8192
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
    assert set(entries.GENERIC + MOE + APPENDED) <= lists
    assert not lists & set(NOT_HERE)


def test_the_appended_entries_keep_the_contract_and_have_their_files(
        bench):
    assert contract.violations(bench, REPO) == []
    appended = [entries.metric(bench, name) for name in APPENDED]
    assert [(m["name"], m["unit"], m["better"], m["source"], m["layer"],
             m["moves"]) for m in appended] == [
        ("kernel.window_attn.roofline_pct", "%", "higher", "device_trace",
         "attention kernels", "itl_p95_ms"),
        ("kv.window_live_pct", "%", "higher", "program_counter", "KV pool",
         "itl_p95_ms")]
    names = [m["name"] for m in bench["per_layer"]]
    accepted = names.index("sched.narrow_steps_pct")
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in APPENDED}
    for m in appended:
        assert names.index(m["name"]) > accepted  # appended, not inserted
        assert CELL in m["workloads"] and m["layer"] in layers
        assert callable(harness.load_reader(REPO, m["name"]).read)


SIZES = dict(L=8, Lw=6, Lf=2, Ld=1, Lm=7, H=6144, V=19200, I=18432, hq=64,
             hkv=8, d=128, w=128, E=128, Eh=16, k=8, Im=2048, Is=2048,
             tp=1, b=2)


def test_size_vars_are_the_configuration_s(cfg):
    assert harness.load_family(REPO, cfg["family"]).size_vars(cfg) == SIZES


def _window_pairs(n, ctx, w=128):
    """Keys the window lets n new rows after ctx cached ones see, row
    by row."""
    return sum(min(ctx + c + 1, w) for c in range(n))


def test_whole_step_work_against_a_hand_count():
    spec = work.load(REPO, "exaone_moe_step")
    assert spec["whole_step"] is True
    n, ctx = 128, 4096
    need = work.step_needs(spec, SIZES, [(n, ctx, True)])
    attn = 2 * 6144 * (64 + 16) * 128 + 2 * 8192 * 6144
    moe = (2 * 6144 * 128 + 8 * 16 / 128 * 6 * 6144 * 2048
           + 6 * 6144 * 2048)
    dense = 6 * 6144 * 18432
    core = 4 * 64 * 128 * (2 * (n * ctx + n * (n + 1) / 2)
                           + 6 * n * 128)
    want = n * (8 * attn + 7 * moe + dense) + core + 2 * 6144 * 19200
    assert need["flops"] == pytest.approx(want, rel=1e-12)
    assert need["hbm_bytes"] == 0 and need["ici_bytes"] == 0
    # a padding-only step needs nothing
    assert work.step_needs(spec, SIZES, [])["flops"] == 0


@pytest.mark.parametrize("n, ctx", [(128, 4096), (128, 0), (5, 125),
                                    (1, 127), (1, 900), (100, 60)])
def test_window_attention_work_against_a_count_row_by_row(n, ctx):
    spec = work.load(REPO, "window_gqa_attn")
    need = work.step_needs(spec, SIZES, [(n, ctx, False)])
    pairs = 2 * (n * ctx + n * (n + 1) / 2) + 6 * _window_pairs(n, ctx)
    assert need["flops"] == pytest.approx(4 * 64 * 128 * pairs)
    # rows in and out once a block; each cached position a block reads
    # once: the whole context in a global block, the tail's min(ctx, w)
    # and the chunk in a window block (256 at most)
    assert min(ctx, 128) + n <= 256
    assert need["hbm_bytes"] == pytest.approx(2 * (
        8 * 2 * n * 64 * 128 + 2 * 2 * (ctx + n) * 8 * 128
        + 6 * 2 * (min(ctx, 128) + n) * 8 * 128))
    assert work.patterns(spec) == ["*_fp_local_kernel*"]


def _view(**kw):
    base = dict(root=REPO, counters={}, trace=None, trace_steps=[],
                say=lambda m: None)
    return harness.RunView(**{**base, **kw})


def test_window_live_pct_on_planted_counters():
    read = harness.load_reader(REPO, "kv.window_live_pct").read
    assert read(_view(counters={"serve_window_bytes_live": 300,
                                "serve_window_bytes_moved": 400})) == 75.0
    # the parent, and a configuration with no window block, count
    # neither: the reader says nothing and does not raise
    assert read(_view()) is None
    assert read(_view(counters={"serve_state_bytes_live": 5})) is None
    assert read(_view(counters={"serve_window_bytes_live": 0,
                                "serve_window_bytes_moved": 0})) is None


def test_window_roofline_on_the_fixture_and_on_nothing():
    """The fixture's trace is a dense cell's: its `_fp_local_kernel`
    events stand in for this family's, the share comes out between 0
    and 100 under this family's sizes; without a trace, or with one
    that holds no such event, the reader says nothing."""
    from perfbench.sources import program_span

    read = harness.load_reader(REPO, "kernel.window_attn.roofline_pct").read
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
    # tiny.add_cell lists the cell under every entry that lists cells
    return tiny.make_root(tmp_path_factory.mktemp("kx"), mix=MIX,
                          config=TINY_EXAONE)


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
    # the expert layer's counters mean here what they mean for the
    # other hybrid members; there is no delta-net state to count
    assert 35.0 < got["moe.local_pairs_pct"] < 65.0  # 4 of 8 held
    assert "state.live_pct" not in got
    # the appended entries, through the harness: the tails of the slots
    # with a valid row over every slot's; the CPU's route runs no
    # kernel, so the roofline's reader says nothing
    assert 0.0 < got["kv.window_live_pct"] <= 100.0
    assert "kernel.window_attn.roofline_pct" not in got


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


def test_a_tail_not_carried_reads_not_correct(tiny_root, monkeypatch):
    """The comparison that decides `correct`, with the window blocks'
    tails thrown away after every step."""
    from triton_dist_tpu.models import hybrid

    real = hybrid.window_attn_fwd

    def forgetful(x, p, spec, cos, sin, positions, tail, *rest):
        y, _ = real(x, p, spec, cos, sin, positions, tail, *rest)
        return y, tail

    monkeypatch.setattr(hybrid, "window_attn_fwd", forgetful)
    root, bench, cell = tiny_root
    result, _ = tiny.rehearse(root, bench, cell, seconds=1.5, seed=77)
    assert result["correct"] is False
