"""Tests for the analytic perf models and the contextual autotuner
(ref test strategy: SURVEY §4 — unit tests per component; the reference
exercises its autotuner indirectly through kernel tests, docs/autotuner.md)."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from triton_dist_tpu import perf_model as pm
from triton_dist_tpu.autotuner import ContextualAutotuner, autotune, get_tuner


# -- perf models -------------------------------------------------------------


def test_detect_chip_returns_spec():
    spec = pm.detect_chip()
    assert spec.bf16_tflops > 0 and spec.hbm_gbps > 0 and spec.ici_links > 0


def test_gemm_model_monotone_in_flops():
    small = pm.estimate_gemm_ms(512, 512, 512)
    big = pm.estimate_gemm_ms(4096, 4096, 4096)
    assert 0 < small < big


def test_gemm_model_memory_bound_decode():
    # decode GEMM (m=1) must be memory-bound: time tracks weight bytes,
    # not flops.
    chip = pm.CHIPS["TPU v5 lite"]
    t = pm.estimate_gemm_ms(1, 4096, 4096, jnp.bfloat16, chip)
    weight_ms = 2 * 4096 * 4096 / (chip.hbm_gbps * 1e9) * 1e3
    assert t == pytest.approx(weight_ms, rel=0.5)
    assert pm.gemm_arith_intensity(1, 4096, 4096) < 2


def test_comm_models_scale_with_world():
    b = 1 << 20
    assert pm.estimate_ag_ms(b, 1) == 0.0
    assert pm.estimate_ag_ms(b, 8) > pm.estimate_ag_ms(b, 2)
    assert pm.estimate_rs_ms(8 * b, 8) == pytest.approx(
        pm.estimate_ag_ms(b, 8)
    )
    # two-shot AR == RS + AG of the shard
    chip = pm.CHIPS["TPU v5p"]
    ar = pm.estimate_ar_ms(8 * b, 8, chip)
    assert ar == pytest.approx(
        pm.estimate_rs_ms(8 * b, 8, chip) + pm.estimate_ag_ms(b, 8, chip)
    )


def test_ag_gemm_bound_covers_both_sides():
    chip = pm.CHIPS["TPU v5p"]
    fused = pm.estimate_ag_gemm_ms(2048, 5120, 800, 8, jnp.bfloat16, chip)
    gemm = pm.estimate_gemm_ms(2048, 800, 5120, jnp.bfloat16, chip)
    ag = pm.estimate_ag_ms(2048 // 8 * 5120 * 2, 8, chip)
    assert fused >= max(gemm, ag)


# -- blocked-GEMM tile model + roofline pruning (ISSUE 1 tentpole (c)) -------


def test_blocked_gemm_model_charges_tile_traffic_and_steps():
    """The tile-aware model must separate configs the coarse roofline
    cannot: pathologically tiny tiles pay grid-step overhead and A/B
    re-reads; a single full-size tile converges to the plain roofline."""
    chip = pm.CHIPS["TPU v5 lite"]
    m, n, k = 2048, 5120, 3200
    tiny = pm.estimate_blocked_gemm_ms(m, n, k, 128, 128, 128, chip=chip)
    good = pm.estimate_blocked_gemm_ms(m, n, k, 512, 1280, 640, chip=chip)
    assert tiny > 2 * good
    one = pm.estimate_blocked_gemm_ms(m, n, k, m, n, k, chip=chip)
    base = pm.estimate_gemm_ms(m, n, k, jnp.bfloat16, chip, 0.85)
    assert one == pytest.approx(base, rel=0.35)


def test_roofline_frontier_keeps_best_and_never_empties():
    cfgs = [1, 2, 3, 4]
    model = {1: 1.0, 2: 1.2, 3: 2.0, 4: 10.0}.get
    kept = pm.roofline_frontier(cfgs, model, slack=1.25)
    assert kept == [1, 2]
    assert pm.roofline_frontier([4], model) == [4]  # best always survives
    assert pm.roofline_frontier([], model) == []


def test_prune_ag_gemm_configs_fit_dedupe_topn():
    from triton_dist_tpu.autotuner import (
        ag_gemm_config_space,
        prune_ag_gemm_configs,
    )
    from triton_dist_tpu.lang.core import fit_tile

    chip = pm.CHIPS["TPU v5 lite"]
    m, k, n_loc = 2048, 5120, 6400
    pruned = prune_ag_gemm_configs(m, k, n_loc, chip=chip)
    assert 0 < len(pruned) < len(ag_gemm_config_space())
    fitted = [(fit_tile(c.tile_m, m), fit_tile(c.tile_n, n_loc),
               fit_tile(c.tile_k, k)) for c in pruned]
    assert len(set(fitted)) == len(fitted)  # deduped by fitted tiles
    top = prune_ag_gemm_configs(m, k, n_loc, chip=chip, top_n=3)
    assert len(top) <= 3 and set(map(repr, top)) <= set(map(repr, pruned))


def test_prune_fallback_when_nothing_fits_returns_single_smallest():
    """A budget no candidate fits must not hand back the whole rejected
    space (each overflow tiling burns a Mosaic compile failure on
    hardware): the helper returns exactly the least-VMEM candidate."""
    from triton_dist_tpu.autotuner import prune_ag_gemm_configs

    chip = pm.CHIPS["TPU v5 lite"]
    out = prune_ag_gemm_configs(2048, 5120, 6400, chip=chip,
                                vmem_budget=1)
    assert len(out) == 1


def test_prune_gemm_rs_local_configs_respects_vmem():
    """Default prune budget is the chip's forced-kernel VMEM ceiling
    (perf_model.kernel_vmem_ceiling — the kernels grant forced
    candidates the VMEM their tiling implies, so the conservative
    auto-fallback dataclass budget must not cut the measured frontier);
    an explicit vmem_budget still prunes exactly."""
    from triton_dist_tpu.autotuner import prune_gemm_rs_local_configs
    from triton_dist_tpu.kernels.gemm_reduce_scatter import GemmRsConfig
    from triton_dist_tpu.lang.core import fit_tile

    chip = pm.CHIPS["TPU v5 lite"]
    m, k_loc, n_full = 2048, 3200, 5120

    def need(c):
        tm = fit_tile(c.tile_m_local, m)
        tn = fit_tile(c.tile_n_local, n_full)
        tk = fit_tile(c.tile_k_local, k_loc)
        nk = -(-k_loc // tk)
        return (2 * (tm * tk + tk * tn) * 2 + 2 * tm * tn * 2
                + (tm * tn * 4 if nk > 1 else 0))

    ceiling = pm.kernel_vmem_ceiling(chip)
    default = prune_gemm_rs_local_configs(m, k_loc, n_full, chip=chip)
    for c in default:
        assert need(c) <= ceiling, (c, need(c))
    # the widened default frontier reaches past the old fallback budget
    # (that was the mis-pruning: the roofline winners need > 14 MiB)
    assert any(need(c) > GemmRsConfig().vmem_budget for c in default)
    # explicit budgets are still binding
    tight = GemmRsConfig().vmem_budget
    for c in prune_gemm_rs_local_configs(m, k_loc, n_full, chip=chip,
                                         vmem_budget=tight):
        assert need(c) <= tight, (c, need(c))


# -- chunk-pipelined EP MoE model (ISSUE 2 tentpole (c)) ---------------------


def test_ep_moe_model_pipeline_orderings():
    """The pipeline roofline must reproduce the chunk-count physics the
    measured pipeline exhibits: overlap beats sequential at n > 1;
    chunking pays off on comm-exposed shapes; at n == 1 (no wire time to
    hide) extra chunks can only lose (weight re-streaming + worse
    per-chunk MXU efficiency)."""
    chip = pm.CHIPS["TPU v5 lite"]
    # comm-heavy: big hidden, tiny expert compute
    kw = dict(m=128, hidden=7168, inter=256, e_loc=2, top_k=8, chip=chip)
    seq = pm.estimate_ep_moe_ms(n=8, n_chunks=1, overlap=False, **kw)
    one = pm.estimate_ep_moe_ms(n=8, n_chunks=1, overlap=True, **kw)
    four = pm.estimate_ep_moe_ms(n=8, n_chunks=4, overlap=True, **kw)
    assert one <= seq
    assert four < one  # chunking shrinks the exposed ramp
    # n == 1: nothing to hide — chunking must never look profitable
    local1 = pm.estimate_ep_moe_ms(n=1, n_chunks=1, overlap=True, **kw)
    local8 = pm.estimate_ep_moe_ms(n=1, n_chunks=8, overlap=True, **kw)
    assert local1 <= local8
    # sequential degenerate: overlap=False with q chunks >= overlap=True
    assert pm.estimate_ep_moe_ms(n=8, n_chunks=4, overlap=False, **kw) \
        >= four


def test_choose_ep_chunks_divides_capacity_and_degenerates_locally():
    chip = pm.CHIPS["TPU v5 lite"]
    cap = 128 * 8
    q = pm.choose_ep_chunks(128, 7168, 256, 2, 8, 8, capacity=cap,
                            chip=chip, overlap=True)
    assert q >= 1 and cap % q == 0
    # comm-exposed shape at n=8 must pipeline UNDER THE TRUE-OVERLAP
    # model (the in-kernel-consumer target)
    assert q > 1
    assert pm.choose_ep_chunks(128, 7168, 256, 2, 1, 8, capacity=cap,
                               chip=chip, overlap=True) == 1
    # the default models the EXECUTED composition (transport completes
    # before the FFNs start): extra chunks only add per-chunk GEMM and
    # weight-restream cost, so the pick must degenerate to 1 at ANY n —
    # a q>1 default here would be a model-driven slowdown
    for n in (1, 8):
        assert pm.choose_ep_chunks(128, 7168, 256, 2, n, 8,
                                   capacity=cap, chip=chip) == 1


def test_prune_ep_moe_configs_frontier_and_levels():
    """The pruner must keep the model-optimal chunk count (within slack)
    at EVERY capacity level — capacity_factor is a quality trade the
    time model cannot fold away — and respect top_n within levels."""
    from triton_dist_tpu.autotuner import (
        ep_moe_config_space,
        prune_ep_moe_configs,
    )
    from triton_dist_tpu.kernels.ep_a2a import EpMoeConfig

    chip = pm.CHIPS["TPU v5 lite"]
    kw = dict(m=128, hidden=7168, inter=256, e_loc=2, n=8, top_k=8,
              chip=chip)
    pruned = prune_ep_moe_configs(**kw)
    space = ep_moe_config_space()
    assert 0 < len(pruned) < len(space)
    levels = {c.capacity_factor for c in space}
    assert {c.capacity_factor for c in pruned} == levels
    # the model's own argmin at each level survives the frontier
    for cf in levels:
        best = min(
            (c for c in space if c.capacity_factor == cf),
            key=lambda c: pm.estimate_ep_moe_ms(
                n_chunks=c.n_chunks,
                capacity=c.fit_capacity(128, 8), **kw),
        )
        kept = [c for c in pruned if c.capacity_factor == cf]
        assert any(c.n_chunks == best.n_chunks for c in kept), (cf, kept)
    top = prune_ep_moe_configs(top_n=1, **kw)
    assert len(top) == len(levels)
    assert prune_ep_moe_configs(configs=[], **kw) == [EpMoeConfig()]


# -- bench result schema (ISSUE 1 satellite: CI catches metric drift) --------


def _load_bench():
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).resolve().parent.parent / "bench.py"
    spec = importlib.util.spec_from_file_location("tdt_bench", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def bench_mod():
    return _load_bench()


def test_bench_schema_accepts_wellformed(bench_mod):
    good = {"metric": "mega_decode_qwen3_8b_ms", "value": 2.8,
            "unit": "ms", "vs_baseline": 0.86, "raw": [1.0, 2.0],
            "mega_8b_hbm_floor_ms": 2.31, "mega_8b_gap_vs_floor": 1.2,
            "mega_32b_gap_vs_floor": 1.1, "pallas_vs_xla": 0.98,
            "gemm_rs_vs_xla": 1.0, "ag_gemm_tuned_cfg": "(256,3200,512)"}
    assert bench_mod.check_result(good) == []
    # measurement-failure line stays valid (tracked outcome)
    fail = {"metric": "mega_decode_qwen3_8b_ms", "value": -1.0,
            "unit": "ms", "vs_baseline": -1.0, "error": "measurement failed"}
    assert bench_mod.check_result(fail) == []


def test_bench_schema_accepts_ep_moe_keys(bench_mod):
    """ISSUE 2 satellite: the chunk-pipelined EP MoE metrics are schema
    keys, so a rename silently breaking the driver's trend tracking
    becomes a nonzero bench exit instead."""
    good = {"metric": "mega_decode_qwen3_8b_ms", "value": 2.8,
            "unit": "ms", "vs_baseline": 0.86,
            "ep_moe_fwd_us": 990.0, "ep_moe_seq_us": 1080.0,
            "ep_moe_xla_us": 910.0, "ep_moe_overlap_vs_seq": 0.92,
            "ep_moe_chunks": 1, "ep_moe_drop_frac": 0.0}
    assert bench_mod.check_result(good) == []
    for key in ("ep_moe_fwd_us", "ep_moe_seq_us", "ep_moe_xla_us",
                "ep_moe_overlap_vs_seq", "ep_moe_chunks",
                "ep_moe_drop_frac"):
        assert key in bench_mod._NUMERIC_KEYS
        assert any("must be numeric" in p for p in bench_mod.check_result(
            dict(good, **{key: "fast"})))
    # the typo'd variant is schema drift, not a new metric
    assert any("unknown key" in p for p in bench_mod.check_result(
        dict(good, ep_moe_fwd_uss=1.0)))
    assert any("malformed value" in p for p in bench_mod.check_result(
        dict(good, ep_moe_drop_frac=float("nan"))))


def test_bench_schema_sp_prefill_keys_travel_together(bench_mod):
    """ISSUE 7 satellite: the sp_prefill_* family is schema-checked AND
    travels together with its tail-stat raw dict — a ratio without its
    absolute arms (or without tails) is unfalsifiable."""
    base = {"metric": "m", "value": 1.0, "unit": "ms",
            "vs_baseline": 1.0}
    raw = {"diffs_ms": [1.0], "p25_ms": 1.0, "min_ms": 1.0}
    full = dict(base, sp_prefill_us=250.0, sp_prefill_ring_us=700.0,
                sp_prefill_xla_us=500.0, sp_prefill_vs_ring=0.36,
                sp_prefill_vs_xla=0.5, sp_prefill_cfg="block=512",
                sp_prefill_raw=raw)
    assert bench_mod.check_result(full) == []
    for key in bench_mod._SP_PREFILL_KEYS:
        assert key in bench_mod._NUMERIC_KEYS
        partial = dict(full)
        del partial[key]
        assert any("travel together" in p
                   for p in bench_mod.check_result(partial))
    # the raw tail-stat dict is part of the contract
    no_raw = dict(full)
    del no_raw["sp_prefill_raw"]
    assert any("sp_prefill_raw" in p
               for p in bench_mod.check_result(no_raw))
    # ...and raw dicts with diffs still need their tail stats
    bad_raw = dict(full, sp_prefill_raw={"diffs_ms": [1.0]})
    assert any("tail stats" in p
               for p in bench_mod.check_result(bad_raw))
    # serve-side movement arm keys are schema too
    assert "prefill_xla_us" in bench_mod._NUMERIC_KEYS
    assert "prefill_flash_vs_xla" in bench_mod._NUMERIC_KEYS


def test_bench_sp_prefill_arm_runs_end_to_end(bench_mod):
    """The whole sp_prefill bench arm executes at a tiny shape on the
    CPU interpreter and emits a schema-clean key family — an
    axis-binding or routing bug in the arm must fail HERE, not
    silently error-key every future artifact (the ring baseline needs
    its axis bound via the world=1 sub-mesh; a bare jit crashes)."""
    from triton_dist_tpu.runtime import make_mesh

    mesh = make_mesh(mesh_shape=(1,), axis_names=("tp",))
    # ks spread wide enough that the slope survives host-timer noise;
    # one retry mirrors bench main's transient-measurement policy (the
    # test exists to catch structural breakage, not to time anything)
    for attempt in (0, 1):
        try:
            out = bench_mod.bench_sp_prefill(
                mesh, shape=(1, 16, 2, 1, 16), ks=(1, 9, 17), k_hi=9,
                pairs=1)
            break
        except RuntimeError:
            if attempt:
                raise
    assert bench_mod._SP_PREFILL_KEYS <= set(out)
    assert "diffs_ms" in out["sp_prefill_raw"]
    assert out["sp_prefill_cfg"].startswith("block=")
    base = {"metric": "m", "value": 1.0, "unit": "ms",
            "vs_baseline": 1.0}
    assert bench_mod.check_result(dict(base, **out)) == []


def test_bench_allreduce_wire_arm_runs_end_to_end(bench_mod):
    """The quantized-wire AR bench arm (ISSUE 9) executes at a tiny
    shape on the CPU interpreter — the world=1 forced-ring path
    included (the n == 1 early returns are SKIPPED by force_kernel, so
    a Mosaic-facing structural bug in that regime fails here, not in
    the driver's artifact) — and emits the schema-clean travelling
    key family."""
    from triton_dist_tpu.runtime import make_mesh

    mesh = make_mesh(mesh_shape=(1,), axis_names=("tp",))
    for attempt in (0, 1):
        try:
            out = bench_mod.bench_allreduce_wire(
                mesh, shape=(16, 128), ks=(1, 9, 17), k_hi=9, pairs=1)
            break
        except RuntimeError:
            if attempt:
                raise
    assert bench_mod._AR_WIRE_KEYS <= set(out)
    assert "diffs_ms" in out["allreduce_wire_raw"]
    assert out["allreduce_wire_model_pick"] in ("native", "fp8", "int8")
    base = {"metric": "m", "value": 1.0, "unit": "ms",
            "vs_baseline": 1.0}
    assert bench_mod.check_result(dict(base, **out)) == []


def test_flash_prefill_perf_model():
    """The flash-vs-xla prefill pricing (ISSUE 7): the xla formulation
    carries the f32 logits-materialization traffic the kernel deletes,
    so at real shapes the model must (a) rank flash ahead, (b) price
    the SP pipeline monotonically in n, and (c) rank the SP flash
    pipeline ahead of the ppermute ring formulation."""
    from triton_dist_tpu.perf_model import (
        CHIPS,
        choose_prefill_impl,
        choose_sp_prefill_impl,
        estimate_flash_prefill_ms,
        estimate_sp_prefill_ms,
        estimate_xla_prefill_ms,
    )

    chip = CHIPS["TPU v5 lite"]
    shape = dict(hq=4, hkv=1, d=128, chip=chip)
    f = estimate_flash_prefill_ms(4096, 4096, **shape)
    x = estimate_xla_prefill_ms(4096, 4096, **shape)
    assert 0 < f < x  # the logits term is the separation
    assert choose_prefill_impl(4096, 4096, 4, 1, 128, chip=chip) \
        == "flash"
    # ...and the switch is a REAL decision, not a constant: a tiny
    # serve chunk's logits traffic is below the kernel-dispatch term,
    # so the fused dense path wins there
    assert choose_prefill_impl(2, 256, 4, 1, 128, chip=chip) == "xla"
    # the block knob is priced (burst efficiency): taller pages never
    # model slower
    assert estimate_flash_prefill_ms(4096, 4096, block=1024, **shape) \
        <= estimate_flash_prefill_ms(4096, 4096, block=128, **shape)

    prev = 0.0
    for n in (1, 2, 4, 8):
        cur = estimate_sp_prefill_ms(4096, n, 4, 1, 128, chip=chip)
        assert cur > prev  # more segments never get cheaper
        prev = cur
    ring = estimate_sp_prefill_ms(4096, 8, 4, 1, 128, chip=chip,
                                  impl="ring")
    flash = estimate_sp_prefill_ms(4096, 8, 4, 1, 128, chip=chip)
    assert flash < ring
    assert choose_sp_prefill_impl(4096, 8, 4, 1, 128, chip=chip) \
        == "flash"


def test_prune_flash_prefill_configs():
    """Frontier + dedupe + top_n discipline on the block space: fitted
    blocks are distinct divisor-fitted heights, top_n caps, and the
    VMEM rule never empties the set."""
    from triton_dist_tpu.autotuner import (
        flash_prefill_config_space,
        prune_flash_prefill_configs,
    )
    from triton_dist_tpu.perf_model import CHIPS

    chip = CHIPS["TPU v5 lite"]
    space = flash_prefill_config_space()
    out = prune_flash_prefill_configs(4096, 4096, 4, 1, 128, chip=chip)
    assert out and len(out) <= len(space)
    blocks = [c.block for c in out]
    assert len(set(blocks)) == len(blocks)  # fitted-dedupe
    top = prune_flash_prefill_configs(4096, 4096, 4, 1, 128, chip=chip,
                                      top_n=2)
    assert 1 <= len(top) <= 2
    # tiny T: every candidate degrades to the same fitted block
    tiny = prune_flash_prefill_configs(8, 8, 2, 1, 128, chip=chip)
    assert len(tiny) == 1


def test_serve_step_model_prices_attn_impl():
    """estimate_serve_step_ms attn_impl pricing: the xla logits term
    grows with chunk x kv_tokens, so the flash-priced chunk chooser
    picks at least as wide a chunk (ISSUE 7: what the device-side
    kernel buys the scheduler)."""
    from triton_dist_tpu.perf_model import (
        CHIPS,
        choose_prefill_chunk,
        estimate_serve_step_ms,
    )

    chip = CHIPS["TPU v5 lite"]
    dims = dict(num_layers=36, hidden=4096, inter_loc=1536, hq_loc=4,
                hkv_loc=1, head_dim=128, vocab_loc=18992, chip=chip)
    fl = estimate_serve_step_ms(n_tokens=128, kv_tokens=8192,
                                attn_impl="flash", **dims)
    xl = estimate_serve_step_ms(n_tokens=128, kv_tokens=8192,
                                attn_impl="xla", **dims)
    assert fl <= xl
    wide = choose_prefill_chunk(slots=4, kv_tokens=8192,
                                attn_impl="flash", **dims)
    narrow = choose_prefill_chunk(slots=4, kv_tokens=8192,
                                  attn_impl="xla", **dims)
    assert wide >= narrow


def test_bench_schema_flags_drift(bench_mod):
    base = {"metric": "m", "value": 1.0, "unit": "ms", "vs_baseline": 1.0}
    assert any("unknown key" in p for p in bench_mod.check_result(
        dict(base, mega_32b_vs_basline=1.0)))  # typo'd baseline key
    assert any("missing required" in p for p in bench_mod.check_result(
        {"metric": "m", "value": 1.0}))
    assert any("malformed value" in p for p in bench_mod.check_result(
        dict(base, pallas_vs_xla=float("nan"))))
    assert any("malformed value" in p for p in bench_mod.check_result(
        dict(base, value=-3.0)))  # negative latency without an error key
    assert any("must be numeric" in p for p in bench_mod.check_result(
        dict(base, gemm_rs_vs_xla="1.0")))


# -- autotuner ---------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _Cfg:
    reps: int


def _make_thunk(cfg: _Cfg):
    x = jnp.ones((128, 128), jnp.float32)

    @jax.jit
    def run(x):
        for _ in range(cfg.reps):
            x = x @ x
        return x

    return lambda: run(x)


def test_autotuner_picks_cheapest_and_caches():
    tuner = ContextualAutotuner("unit")
    res = tuner.tune(_make_thunk, [_Cfg(12), _Cfg(1)], key="k1",
                     iters=2, warmup=1, reps=1)
    assert res.config == _Cfg(1)
    assert res.cost_ms < res.costs[repr(_Cfg(12))]
    # cache hit returns the identical object without re-measuring
    assert tuner.tune(lambda c: 1 / 0, [_Cfg(12), _Cfg(1)], key="k1") is res


def test_autotuner_skips_failing_configs():
    def mk(cfg):
        if cfg.reps == 99:
            raise ValueError("bad config")
        return _make_thunk(cfg)

    res = ContextualAutotuner("unit2").tune(
        mk, [_Cfg(99), _Cfg(1)], key="k", iters=1, warmup=0, reps=1
    )
    assert res.config == _Cfg(1)
    assert res.costs[repr(_Cfg(99))] == float("inf")


def test_autotuner_all_fail_raises():
    with pytest.raises(RuntimeError, match="every config failed"):
        ContextualAutotuner("unit3").tune(
            lambda c: 1 / 0, [_Cfg(1)], key="k", iters=1, warmup=0, reps=1
        )


def test_autotuner_prune_uses_perf_model():
    seen = []

    def mk(cfg):
        seen.append(cfg)
        return _make_thunk(cfg)

    ContextualAutotuner("unit4").tune(
        mk, [_Cfg(1), _Cfg(12)], key="k", iters=1, warmup=0, reps=1,
        prune=lambda c: c.reps < 10,
    )
    assert seen == [_Cfg(1)]


def test_autotuner_disk_cache(tmp_path):
    path = str(tmp_path / "cache.json")
    t1 = ContextualAutotuner("unit5", cache_path=path)
    res = t1.tune(_make_thunk, [_Cfg(3), _Cfg(1)], key="k",
                  iters=1, warmup=0, reps=1)
    with open(path) as f:
        disk = json.load(f)
    assert any(v["config"] == repr(res.config) for v in disk.values())
    # a fresh tuner instance resolves from disk without measuring
    t2 = ContextualAutotuner("unit5", cache_path=path)
    assert t2.tune(lambda c: 1 / 0, [_Cfg(3), _Cfg(1)], key="k").config \
        == res.config


def test_autotune_decorator():
    calls = []

    @autotune("unit6", configs=[_Cfg(8), _Cfg(1)], iters=1, warmup=0, reps=1)
    def fn(x, config=None):
        calls.append(config)
        y = x
        for _ in range(config.reps):
            y = y @ x
        return y

    x = jnp.eye(64)
    out = fn(x)
    assert out.shape == (64, 64)
    assert calls[-1] == _Cfg(1)  # final run uses the winner
    n = len(calls)
    fn(x)  # same shapes -> cached, exactly one more call
    assert len(calls) == n + 1


def test_get_tuner_singleton():
    assert get_tuner("same") is get_tuner("same")


# ---------- xslice perf model (ISSUE 18) ----------


def test_xslice_collective_estimator_structure():
    from triton_dist_tpu import perf_model as pm

    nb, n = 8 << 20, 4
    # slices=1 degenerates to the flat ICI estimate exactly
    assert pm.estimate_xslice_collective_ms(nb, n, 1, "allgather") \
        == pm.estimate_ag_ms(nb, n)
    assert pm.estimate_xslice_collective_ms(nb, n, 1, "reduce_scatter") \
        == pm.estimate_rs_ms(nb, n)
    # a DCN hop is never free: 2 slices strictly dearer than 1
    for coll in ("allgather", "reduce_scatter", "allreduce"):
        assert pm.estimate_xslice_collective_ms(nb, n, 2, coll) \
            > pm.estimate_xslice_collective_ms(nb, n, 1, coll)
    # slower DCN -> strictly dearer (bandwidth term is live)
    fast = pm.estimate_xslice_collective_ms(nb, n, 2, dcn_gbps=25.0)
    slow = pm.estimate_xslice_collective_ms(nb, n, 2, dcn_gbps=2.0)
    assert slow > fast
    # chunk overlap can only help a 2-leg pipeline, never beat the
    # slower leg's serial floor
    c1 = pm.estimate_xslice_collective_ms(nb, n, 2, dcn_gbps=2.0)
    c4 = pm.estimate_xslice_collective_ms(nb, n, 2, dcn_gbps=2.0,
                                          chunks=4)
    assert c4 < c1
    # a wire format pays codec passes but shrinks the DCN bytes: on a
    # slow link it must win, and the saving must be bounded by the
    # native DCN cost itself
    wired = pm.estimate_xslice_collective_ms(nb, n, 2, dcn_gbps=2.0,
                                             wire_format="fp8")
    assert wired < slow
    import pytest as _pytest
    with _pytest.raises(ValueError):
        pm.estimate_xslice_collective_ms(nb, n, 2, "bogus")


def test_choose_migration_format_monotone():
    from triton_dist_tpu import perf_model as pm
    from triton_dist_tpu.wire import codec as wcodec

    page = 32 << 10
    # zero error budget: only native is admissible
    assert pm.choose_migration_format(page, 64, error_budget=0.0) \
        == wcodec.NATIVE
    # a slow DCN link with a generous budget picks the cheapest
    # quantized format (fp8 shrinks most)
    f = pm.choose_migration_format(page, 256, error_budget=1.0,
                                   dcn_gbps=0.5)
    assert f.kind == "fp8"
    # a budget between the two drifts excludes fp8 but not int8
    d_int8 = pm.estimate_wire_drift("int8", 1, "allgather")
    d_fp8 = pm.estimate_wire_drift("fp8", 1, "allgather")
    assert d_int8 < d_fp8
    mid = (d_int8 + d_fp8) / 2
    g = pm.choose_migration_format(page, 256, error_budget=mid,
                                   dcn_gbps=0.5)
    assert g.kind in ("int8", "native")
    assert g.kind != "fp8"
    # a fast link: the codec passes outweigh the shrink -> native
    assert pm.choose_migration_format(page, 4, error_budget=1.0,
                                      dcn_gbps=400.0) == wcodec.NATIVE
    # migration estimate itself is monotone in payload and bandwidth
    a = pm.estimate_migration_ms(1 << 20, dcn_gbps=2.0)
    b = pm.estimate_migration_ms(2 << 20, dcn_gbps=2.0)
    c = pm.estimate_migration_ms(1 << 20, dcn_gbps=4.0)
    assert b > a > c
