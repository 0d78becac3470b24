"""World=1 latency-ledger tests (ISSUE 5): byte-budgeted megakernel
tiling, tile-major weights, byte-accurate floor model, bench schema
tail-stat enforcement, perf-claims lint, and the 32B-shape prefetch
hit-rate regression pin."""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu import perf_model as pm
from triton_dist_tpu.mega.core import (
    fit_mm_tile,
    mm_tile_cap,
    plan_mm_tiles,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------- byte-budgeted tile planning ----------


def _mm_key(w, k, n):
    return ("matmul", w, k, n, None, 0.0)


def test_mm_tile_cap_budget_and_floor(monkeypatch):
    # 16 MiB default at the 32B contract dim -> 1536-column cap
    monkeypatch.delenv("TDT_MEGA_TILE_BYTES", raising=False)
    assert mm_tile_cap(5120) == 1536
    # never below the legacy 512 cap, however large K gets
    assert mm_tile_cap(1 << 20) == 512
    # env override is binding (8 MiB at K=5120 -> 768-column cap)...
    monkeypatch.setenv("TDT_MEGA_TILE_BYTES", str(8 << 20))
    assert mm_tile_cap(5120) == 768
    # ...but still clamped at the legacy-floor 512
    monkeypatch.setenv("TDT_MEGA_TILE_BYTES", str(1 << 20))
    assert mm_tile_cap(5120) == 512


def test_plan_mm_tiles_32b_geometry(monkeypatch):
    """The 32B per-rank shard tiles at 1280 columns under the default
    budget (2.5 KiB bursts vs the legacy 512-byte ones) — the concrete
    number the byte-accurate floor model prices."""
    monkeypatch.delenv("TDT_MEGA_TILE_BYTES", raising=False)
    keys = [_mm_key("w_qkv", 5120, 1280), _mm_key("w_o", 1024, 5120),
            _mm_key("w_gate_up", 5120, 6400),
            _mm_key("w_down", 3200, 5120)]
    plan = plan_mm_tiles(keys)
    assert all(tn == 1280 for tn in plan.values())
    # the cap is GLOBAL (shared (kmax, tnmax) VMEM rectangles): w_o's
    # own K=1024 would allow far wider tiles, but kmax=5120 rules
    assert plan[_mm_key("w_o", 1024, 5120)] == 1280
    # small graphs keep the historical tiling (cap floor 512)
    small = plan_mm_tiles([_mm_key("w", 128, 512)])
    assert small[_mm_key("w", 128, 512)] == fit_mm_tile(512, 512)


def test_auto_pf_depth_bytes(monkeypatch):
    from triton_dist_tpu.mega.scheduler import auto_pf_depth

    monkeypatch.delenv("TDT_MEGA_PF_DEPTH", raising=False)
    monkeypatch.delenv("TDT_MEGA_PF_ARENA_BYTES", raising=False)
    # 32B-class 13.1 MiB tiles: the 32 MiB arena buys 2 slots
    assert auto_pf_depth([("w", 5120, 1280)]) == 2
    # tiny test tiles: byte budget buys the depth ceiling
    assert auto_pf_depth([("w", 128, 128)]) == 4
    # huge tiles never drop below the streaming floor of 2
    assert auto_pf_depth([("w", 8192, 4096)]) == 2
    # env pin wins (incl. the legacy depth-1 lookahead)
    monkeypatch.setenv("TDT_MEGA_PF_DEPTH", "1")
    assert auto_pf_depth([("w", 128, 128)]) == 1


def test_tile_weight_major_roundtrip():
    from triton_dist_tpu.mega.kernel import tile_weight_major

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((3, 8, 12)), jnp.float32)
    t = tile_weight_major(w, 4)  # (3, 3, 8, 4)
    assert t.shape == (3, 3, 8, 4)
    for layer in range(3):
        for j in range(3):
            np.testing.assert_array_equal(
                np.asarray(t[layer, j]),
                np.asarray(w[layer, :, j * 4:(j + 1) * 4]))


# ---------- byte-accurate floor model ----------


def test_hbm_stream_efficiency_shape():
    assert pm.hbm_stream_efficiency(None) == 1.0
    e512 = pm.hbm_stream_efficiency(512)
    e2560 = pm.hbm_stream_efficiency(2560)
    assert 0 < e512 < e2560 < 1.0
    # the calibration point: 512-byte bursts well below peak
    assert e512 == pytest.approx(512 / (512 + pm.HBM_BURST_GAP_BYTES))


def test_mega_floor_explains_round5_and_orders(monkeypatch):
    """The model's two load-bearing properties: (a) under the LEGACY
    tiling it prices the round-5 32B step at ~11.4-11.5 ms (the
    measured 11.50 the old weights-only 9.76 ms floor could not
    explain); (b) the round-6 layout (byte-budgeted tiles + tile-major
    gate_up) strictly lowers the floor, and every floor stays above
    the raw-byte lower bound."""
    chip = pm.CHIPS["TPU v5 lite"]
    dims = dict(num_layers=64, hidden=5120, inter_loc=3200, hq_loc=8,
                hkv_loc=1, head_dim=128, vocab_loc=151936 // 8,
                s_max=512)

    new_floor = pm.mega_decode_floor_ms(chip=chip, **dims)
    monkeypatch.setenv("TDT_MEGA_TILE_BYTES", str(1 << 20))  # legacy cap
    legacy_floor = pm.mega_decode_floor_ms(chip=chip, tiled_weights=(),
                                           **dims)
    monkeypatch.delenv("TDT_MEGA_TILE_BYTES")
    assert 11.2 <= legacy_floor <= 11.6  # explains the measured 11.50
    assert new_floor < legacy_floor

    raw_bytes = sum(t.nbytes for t in pm.mega_decode_traffic_terms(**dims))
    raw_floor = raw_bytes / (chip.hbm_gbps * 1e9) * 1e3
    assert new_floor > raw_floor  # burst efficiency never free
    # weights still dominate the ledger (sanity on the term builder)
    w_bytes = sum(t.nbytes for t in pm.mega_decode_traffic_terms(**dims)
                  if t.name.startswith("w_") or t.name == "lm_head")
    assert w_bytes / raw_bytes > 0.95


def test_kernel_vmem_ceiling():
    v5e = pm.CHIPS["TPU v5 lite"]
    assert pm.kernel_vmem_ceiling(v5e) == 64 << 20
    small = pm.ChipSpec("s", 1.0, 1.0, 1.0, 2, 64)
    assert pm.kernel_vmem_ceiling(small) == 32 << 20


# ---------- bench schema: tail stats are mandatory ----------


def _ok_result():
    raw = {"diffs_ms": [1.0, 1.1], "k": (1, 41), "p25_ms": 1.0,
           "min_ms": 1.0}
    return {
        "metric": "mega_decode_qwen3_8b_ms", "value": 1.0, "unit": "ms",
        "vs_baseline": 0.5, "raw": dict(raw),
        "mega_decode_qwen3_32b_ms": 10.0, "mega_32b_raw": dict(raw),
        "a2a_dispatch_world1_us": 128.0,
    }


def test_check_result_requires_tail_stats():
    import bench

    assert bench.check_result(_ok_result()) == []
    # a diffs_ms-bearing field without its lower-tail stats is malformed
    # — for the 32B field AND the headline raw alike
    for field in ("raw", "mega_32b_raw"):
        bad = _ok_result()
        del bad[field]["p25_ms"]
        probs = bench.check_result(bad)
        assert any(field in p and "p25_ms" in p for p in probs), probs
        bad = _ok_result()
        del bad[field]["min_ms"]
        assert any("min_ms" in p for p in bench.check_result(bad))


def test_check_result_a2a_world1_key():
    import bench

    # only the canonical renamed key is schema-legal: the pre-rename
    # alias rode round 6 deprecated and is now schema DRIFT, like any
    # fabricated spelling
    assert "a2a_dispatch_world1_us" in bench._NUMERIC_KEYS
    bad = _ok_result()
    bad["a2a_dispatch_p50_us"] = 1.0
    assert any("unknown key" in p for p in bench.check_result(bad))
    gone = _ok_result()
    gone["a2a_dispatch_us"] = 128.0
    assert any("unknown key" in p for p in bench.check_result(gone))


def test_chain_timer_raw_carries_tail_stats():
    """chain_timer's raw payload (what every diffs_ms field embeds)
    always carries p25/min — the producer side of the schema rule."""
    from triton_dist_tpu.runtime.utils import chain_timer

    def build(k):  # work genuinely linear in k, ~ms scale
        return lambda: np.sin(np.arange(k * 100_000, dtype=np.float64)).sum()

    ms, raw = chain_timer(build, (), k_lo=1, k_hi=9, pairs=3, warmup=1)
    assert {"diffs_ms", "k", "p25_ms", "min_ms"} <= set(raw)


# ---------- perf-claims lint ----------


def _load_claims_cli():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "_claims_cli", os.path.join(REPO, "scripts",
                                    "check_perf_claims.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_check_perf_claims_repo_clean():
    """The shipped tree's claims agree with the artifact of record —
    the same invariant the dryrun plane asserts."""
    cli = _load_claims_cli()
    assert cli.check(REPO) == 0


def test_grace_ledger_retired():
    """ISSUE 12 emptied the grace ledger; ISSUE 14 re-armed it for
    exactly the spec/prefix families under a round-14 gate, and ISSUE
    17 for the fusion-planner family under a round-17 gate — and the
    committed artifact series already MEASURES those graced keys
    (r07 the spec/prefix pair, r08 the plan pair, r09 the xslice
    pair), so their grace is inert (what it protects against is a
    later round dropping the arms). ISSUE 20's tuning-loop pair
    shipped MEASURED in its own round (BENCH_r09.json carries the
    tuned_vs_default sweeps), so its round-20 grace is inert from
    birth. With r09 landed, EVERY graced key is measured — no grace
    is live, and every required claim is backed by an artifact."""
    cli = _load_claims_cli()
    assert cli.PENDING_FIRST_ARTIFACT == {
        "spec_vs_plain_tokens": 14, "prefix_hit_ttft": 14,
        "plan_vs_hand_prefill": 17, "plan_recover_misroute_ratio": 17,
        "xslice_disagg_vs_single_tokens": 19, "xslice_ag_vs_flat": 19,
        "gemm_rs_tuned_vs_default": 20,
        "flash_prefill_tuned_vs_default": 20}
    _label, measured = cli.latest_measured(REPO)
    live = set(cli.PENDING_FIRST_ARTIFACT) - set(measured)
    # r09 measures the xslice AND tuned families, so no graced key
    # is awaiting its first artifact — the whole ledger is inert
    assert live == set()
    # r09 predates every remaining gate round, so each grace still
    # covers a later round that would DROP its arms (dies at its gate)
    assert cli._artifact_round(_label) == 9


@pytest.mark.parametrize("record", ["BENCH_r06.json", "BENCH_r07.json",
                                    "BENCH_r08.json", "BENCH_r09.json"])
def test_check_result_still_reads_every_committed_record(record):
    """The committed cpu-world1 records are not edited when an arm is
    retired: the schema keeps the names they hold (the
    `serve_resident_*` family went with its loop in PR 32), so
    check_result — and with it the claims lint and the trend — still
    reads every one of them."""
    import json

    import bench

    with open(os.path.join(REPO, record)) as f:
        parsed = json.load(f)["parsed"]
    assert parsed["rig"] == "cpu-world1"
    assert bench.check_result(parsed) == []
    assert not hasattr(bench, "bench_serve_resident")


def test_check_perf_claims_catches_drift(tmp_path, monkeypatch):
    """A claim outside the measured band, an unknown schema key, and a
    deleted required claim must each exit nonzero."""
    cli = _load_claims_cli()
    (tmp_path / "docs").mkdir()
    (tmp_path / "bench.py").write_text(
        "_NUMERIC_KEYS = {'pallas_vs_xla'}\n")
    (tmp_path / "BENCH_r01.json").write_text(
        '{"parsed": {"pallas_vs_xla": 1.10}}')
    doc = tmp_path / "docs" / "performance.md"
    monkeypatch.setattr(
        cli, "REQUIRED_CLAIMS",
        (("pallas_vs_xla", "docs/performance.md"),))

    doc.write_text("tax [perf:pallas_vs_xla=0.95-1.13]\n")
    assert cli.check(str(tmp_path)) == 0
    # contradiction: claimed band excludes the measured 1.10
    doc.write_text("parity! [perf:pallas_vs_xla=0.98-1.00]\n")
    assert cli.check(str(tmp_path)) == 1
    # silently deleting the claim is as loud as contradicting it
    doc.write_text("we are fast\n")
    assert cli.check(str(tmp_path)) == 1
    # unknown schema key: the claim detached from the measurement
    doc.write_text("[perf:pallas_vs_xla=0.95-1.13] "
                   "[perf:not_a_key=1.0-2.0]\n")
    assert cli.check(str(tmp_path)) == 1
    # fail CLOSED: a required claim NO artifact backs (the newest round
    # dropped the key and no prior round carried it) is unbacked
    doc.write_text("tax [perf:pallas_vs_xla=0.95-1.13]\n")
    (tmp_path / "BENCH_r01.json").write_text(
        '{"parsed": {"pallas_ag_gemm_error": "boom"}}')
    assert cli.check(str(tmp_path)) == 1
    # ...but an OLDER artifact that measured it still backs the claim
    (tmp_path / "BENCH_r02.json").write_text(
        '{"parsed": {"pallas_ag_gemm_error": "boom"}}')
    (tmp_path / "BENCH_r01.json").write_text(
        '{"parsed": {"pallas_vs_xla": 1.10}}')
    assert cli.check(str(tmp_path)) == 0


# ---------- trace: per-branch ledger + 32B-shape prefetch pin ----------


def test_task_time_by_branch_buckets():
    from triton_dist_tpu import trace
    from triton_dist_tpu.trace import events as ev
    from triton_dist_tpu.trace.collect import Span, Timeline

    def span(payload, t0, t1):
        return Span("mega", 0, 0, ev.REGIONS["mega.task"], payload, 0,
                    t0, t1)

    tl = Timeline(events=[], spans=[
        span(0, 0.0, 2.0), span(1, 2.0, 3.0), span(0, 3.0, 7.0),
    ], drops={}, host_spans=[])
    keys = [("matmul", "w", 128, 128, None, 0.0), ("rms_norm", 128)]
    by = trace.task_time_by_branch(tl, keys)
    assert by[keys[0]] == {"time": 6.0, "count": 2}
    assert by[keys[1]] == {"time": 1.0, "count": 1}
    # without branch_keys the buckets key on raw ids
    assert trace.task_time_by_branch(tl)[0]["count"] == 2


def test_mega_tiled_multitile_decode_parity(monkeypatch):
    """Numeric parity of the tile-major weight read path at nt > 1:
    shrinking the tile byte budget forces the tiny model's gate_up into
    THREE tile-major blocks (and qkv into two strided tiles), so the
    kernel's [layer, j] contiguous-block reads are checked against the
    XLA engine token-for-token — the tiny default configs degenerate to
    nt == 1, which would leave the multi-tile indexing untested."""
    from triton_dist_tpu.mega.qwen3 import MegaKVCache, MegaQwen3
    from triton_dist_tpu.models import ModelConfig
    from triton_dist_tpu.models.engine import Engine
    from triton_dist_tpu.runtime import make_mesh

    monkeypatch.setenv("TDT_MEGA_TILE_BYTES", "800000")  # cap -> 512
    mesh = make_mesh((1,), ("tp",))
    cfg = ModelConfig.tiny(max_positions=32, intermediate_size=768)
    eng = Engine(cfg, mesh, prefill_mode="xla", decode_mode="xla",
                 donate_cache=False, max_len=32)
    mega = MegaQwen3(cfg, mesh, batch=2, s_max=32, params=eng.params,
                     donate_cache=False)
    gu_key = next(k for k in mega.cm.branch_keys
                  if k[0] == "matmul" and k[1] == "w_gate_up")
    assert gu_key[3] // mega.cm.mm_tiles[gu_key] == 3  # nt == 3, tiled
    assert mega._w_gate_up.shape[2] == 3

    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, (2, 5)).astype(np.int32)
    logits_ref, cache_ref = eng.prefill(prompt)
    mega_cache = MegaKVCache.from_dense(cache_ref, s_max=32)
    tok = jnp.argmax(logits_ref, -1).astype(jnp.int32)
    for step in range(3):
        logits_m, mega_cache = mega.decode_step(tok, mega_cache)
        logits_x, cache_ref = eng.decode_step(tok, cache_ref)
        np.testing.assert_allclose(
            np.asarray(logits_m), np.asarray(logits_x),
            rtol=2e-3, atol=2e-3, err_msg=f"decode step {step}")
        tok = jnp.argmax(logits_m, -1).astype(jnp.int32)


def test_mega_32b_shape_prefetch_hit_rate():
    """ISSUE 5 satellite: pin the 32B-shape weight-streaming pipeline's
    prefetch hit rate on the interpret clock — the per-rank Qwen3-32B
    geometry (hidden 5120, inter 3200, 8q/1kv heads) at 2 layers, with
    the tile-major gate_up layout the production model ships. Exactly
    one cold open is expected (the single queue's first matmul; the
    step boundary is uncovered by design, docs/performance.md), so the
    measured rate must equal the plan's fed fraction and clear 0.8."""
    from triton_dist_tpu import trace
    from triton_dist_tpu.mega.qwen3 import MegaQwen3
    from triton_dist_tpu.models import ModelConfig
    from triton_dist_tpu.runtime import make_mesh

    mesh = make_mesh((1,), ("tp",))
    cfg = ModelConfig(
        vocab_size=256, hidden_size=5120, intermediate_size=3200,
        num_layers=2, num_q_heads=8, num_kv_heads=1, head_dim=128,
        max_positions=64, dtype="float32",
    )
    with trace.tracing("mega", cap=4096) as (_build, sess):
        mega = MegaQwen3(cfg, mesh, batch=1, s_max=64, fast_init=True,
                         donate_cache=False, seed=0)
        # the production tile plan at these dims: 1280-column tiles,
        # tile-major gate_up (the byte-ledger geometry under test)
        assert mega.cm.tile_cols("w_gate_up") == 1280
        assert mega.cm.tiled_weights == ("w_gate_up",)
        assert mega._w_gate_up.shape[2:] == (5, 5120, 1280)
        _logits, _cache, tbuf = mega.decode_step(
            jnp.zeros((1,), jnp.int32), mega.new_cache())
        nc = mega.sched.num_cores
        tl = sess.assemble({"mega": np.asarray(tbuf).reshape(
            1, nc, -1, trace.RECORD_WORDS)})

    plan = mega.sched.prefetch
    cold = set(plan.cold)
    consumers = sum(1 for t in mega.graph.tasks if t.op == "matmul"
                    and (plan.consume[t.id] > 0 or t.id in cold))
    expected = 1.0 - len(cold) / consumers
    rate = trace.prefetch_hit_rate(tl)
    assert rate == pytest.approx(expected)
    assert rate >= 0.8, (rate, plan.cold)
    # the per-branch ledger covers every scheduled task
    by = trace.task_time_by_branch(tl, mega.cm.branch_keys)
    assert sum(d["count"] for d in by.values()) == len(mega.graph.tasks)
