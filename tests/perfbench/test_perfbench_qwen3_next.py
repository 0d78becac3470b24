"""The Qwen3-Next family's benchmark files: the repository's
BENCHMARK.json keeps the contract with the configuration and the cell
added, and a tiny copy of the family (one period, 8 experts of which 4
held, float32) goes through `run_cell` on the CPU: `correct` as served,
not `correct` with one token altered, the new per-layer metrics read."""

import json
import os

import pytest

import perfbench_tiny as tiny
from perfbench import contract, harness

REPO = tiny.REPO
CELL = "q3n-1chip.longdoc-closed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

TINY_NEXT = {
    "model_type": "qwen3_next", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 4,
    "full_attention_interval": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "partial_rotary_factor": 0.25,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "num_experts": 4,
    "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "rope_theta": 10000000,
    "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "torch_dtype": "float32",
    "expert_parallel": {"chips_sharing_a_layer": 2, "router_width": 8,
                        "expert_offset": 4},
    "published": "tiny-test", "stands_for": "a test-scale hybrid on the CPU",
    "serve": {"chips": 1, "tp": 1, "slots": 4, "max_len": 64},
    "family": "qwen3_next", "reference": "qwen3_next",
    "whole_step": "qwen3_next_step",
    "check": {"control": "bf16", "gap_limit": 0.001},
}
# every request the window finishes is scored
MIX = dict(tiny.TINY_MIX, check_requests=1000)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_repository_s_benchmark_keeps_the_contract(bench):
    assert contract.violations(bench, REPO) == []


def test_the_configuration_cuts_three_counts_and_no_width(bench):
    entry = harness.find(bench["configs"], "qwen3-next-80b.1chip",
                         "configuration")
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    cfg = harness.load_json(os.path.join(REPO, entry["file"]))
    record = harness.load_published(REPO, cfg["published"])
    assert entry["source"] == record["source_url"]
    pub = record["config"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (12, 128, 37984)
    assert cfg["published_counts"] == {
        k: pub[k] for k in ("num_experts", "vocab_size",
                            "num_hidden_layers")}
    assert cfg["expert_parallel"]["router_width"] == pub["num_experts"]
    assert cfg["num_hidden_layers"] % cfg["full_attention_interval"] == 0
    widths = harness.load_family(REPO, cfg["family"]).WIDTHS
    assert all(cfg[k] == pub[k] for k in widths) and len(widths) == 15
    assert cfg["serve"] == {"chips": 1, "tp": 1, "slots": 8,
                            "max_len": 8192}
    # the limit is on the gap that three quarters of each scored
    # request's tokens lie within (the reference's module doc; PERF.md,
    # PR 30): under the fp8 control's smallest reading, over the
    # program's largest
    check = cfg["check"]
    assert set(check) == {"control", "gap_limit", "gap_quantile"}
    assert check["control"] == "fp8" and check["gap_quantile"] == 0.75
    assert 0.0 < check["gap_limit"] < 1.0
    if os.path.exists(CATALOG):  # the record is the catalog's row
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == record["name"])
        assert row["source_url"] == record["source_url"]
        assert row["config"] == pub


def test_the_cell_and_the_entries_that_list_it(bench):
    cell = harness.find(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "qwen3-next-80b.1chip", "longdoc-closed", 1)
    mix = harness.load_json(os.path.join(
        REPO, "perfbench", "traffic", "longdoc-closed.json"))
    assert {k: mix[k] for k in ("loop", "clients", "pool", "prompt",
                                "output", "check_requests")} == {
        "loop": "closed", "clients": 8, "pool": 32,
        "prompt": {"dist": "lognormal", "median": 4096, "sigma": 0.45,
                   "min": 1536, "max": 7680},
        "output": {"dist": "lognormal", "median": 64, "sigma": 0.5,
                   "min": 16, "max": 192},
        "check_requests": 3}
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    # not `tokens_per_s`: over six seeds it spreads by 3.0-3.4%, over
    # half its bound, with which of the 32 prompts fall into a window
    # of some 55 requests; only a window of 100 s or more would bring
    # it under (PERF.md, PR 30)
    assert e2e == {"itl_p95_ms", "setup_s", "ttft_p95_ms"}
    lists = {m["name"] for m in bench["per_layer"]
             if CELL in m["workloads"]}
    assert {"step.mfu_pct", "kernel.flash_prefill_full.roofline_pct",
            "moe.local_pairs_pct", "moe.pairs_per_expert_step",
            "state.live_pct", "kv.gather_live_pct",
            "sched.prefill_rows_pct", "device.idle_pct"} <= lists
    assert not lists & {"kernel.flash_prefill.roofline_pct",
                        "kernel.gemm_rs.roofline_pct",
                        "coll.unfused_ms_per_step"}
    new = [m for m in bench["per_layer"] if m["workloads"] == [CELL]]
    assert len(new) == 4 and all(
        m["better"] == "higher" and m["moves"] == "itl_p95_ms" for m in new)


def test_a_gap_quantile_cuts_each_request_s_widest_gaps():
    """Through the harness's own `score_sample`: under `gap_quantile`
    q a request's gaps come back cut down to the smallest of them that
    at least q of them lie within, whatever the request's length and
    wherever the harness lays its rows (a prompt near the horizon
    pushes the first served row off row 0); without the key they come
    back as they are."""
    import math

    import jax
    import numpy as np

    ref = harness.load_reference(REPO, "qwen3_next")
    raw_sizes = ref.Sizes.from_config(TINY_NEXT)
    assert raw_sizes.gap_quantile == 1.0
    cut_sizes = ref.Sizes.from_config(
        dict(TINY_NEXT, check=dict(TINY_NEXT["check"], gap_quantile=0.5)))
    devices = jax.devices()[:1]
    weights = ref.draw_weights(raw_sizes, 1, 11, devices)
    rng = np.random.default_rng(5)
    rows, width = 16, raw_sizes.max_len
    # (prompt length, served tokens): off row 0 only in the last one;
    # random "served" tokens, so every gap is wide and they all differ;
    # the second ends on a served token 0
    sample = [(rng.integers(1, 256, p).tolist(),
               rng.integers(1, 256, n).tolist())
              for p, n in ((20, 16), (9, 5), (width - 12, 11))]
    sample[1][1][-1] = 0
    raw = harness.score_sample(ref, raw_sizes, weights, sample, rows,
                               devices, 1)
    cut = harness.score_sample(ref, cut_sizes, weights, sample, rows,
                               devices, 1)
    for (prompt, served), r, c in zip(sample, raw, cut):
        assert len(r) == len(c) == len(served)
        n = len(served) - (served[-1] == 0)  # module doc: a last 0
        kept = np.sort(r[:n])[math.ceil(0.5 * n) - 1]
        np.testing.assert_allclose(c, np.minimum(r, kept), rtol=0,
                                   atol=1e-6)
        assert c.max() < r.max()
    # the comparison the harness makes of them
    assert harness.gap_check(cut, 1e9)["value"] == max(c.max() for c in cut)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("q3n"), mix=MIX,
                          config=TINY_NEXT)


def test_a_tiny_cell_of_the_family_reads_correct_and_its_metrics(
        tiny_root):
    root, bench, cell = tiny_root
    assert contract.violations(bench, root) == []
    result, lines = tiny.rehearse(root, bench, cell, seconds=2.0,
                                  trace=True)
    assert result["correct"] is True and result["failed"] == 0
    assert result["checks"]["stream_mismatches"]["value"] == 0
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert 35.0 < got["moe.local_pairs_pct"] < 65.0  # 4 of 8 held
    assert 0.0 < got["moe.pairs_per_expert_step"]
    assert 0.0 < got["state.live_pct"] <= 100.0
    assert 0.0 < got["step.mfu_pct"] < 100.0
    assert 0.0 < got["kv.gather_live_pct"] <= 100.0
    # the CPU's step holds no Pallas kernel: the roofline says nothing
    assert "kernel.flash_prefill_full.roofline_pct" not in got


def test_one_altered_token_reads_not_correct(tiny_root):
    root, bench, cell = tiny_root

    def tamper(sch):
        inner, emitted = sch._emit, []

        def emit(req, tok):
            emitted.append(tok)
            # the 25th token any request is given, and no other (the
            # warm-up's two requests take the first six)
            inner(req, (tok + 1) % 256 if len(emitted) == 25 else tok)

        sch._emit = emit

    result, _ = tiny.rehearse(root, bench, cell, seconds=1.5, tamper=tamper)
    assert result["correct"] is False
    gap = result["checks"]["served_logit_gap_max"]
    assert gap["value"] > gap["limit"]
