"""Megakernel-resident serving tests (ISSUE 12).

The load-bearing property: per-request tokens from the device-resident
step loop (work injected through mega.ring, up to `window` steps per
dispatch, decode self-fed on device) are BIT-IDENTICAL to a host-loop
scheduler at the same step width — greedy and sampled, across
admissions and retirements that land mid-loop. Both paths compile the
same `_serve_step_math`, and `mega.ring.slot_plan` reproduces the host
scheduler's per-step inputs field for field. The resident loop keeps
its ONE `(slots, chunk)` geometry; the host loop picks a width a step
(ISSUE 31). So the pin has two statements (the `step_widths` fixture,
tests/_widths.py): against a host loop held to the wide step, bitwise;
against the host loop's own choice, where decode rows alone run the
`(slots, 1)` step, equal tokens on these float32 sizes. These tests
pin that end to end, plus the ring's
visibility/watchdog contract (an abandoned ring trips, never hangs,
never eats tokens), the KVPool↔mega-cache bridge under allocator churn,
and the resident perf model/bench schema.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from triton_dist_tpu.faults.errors import DeadlineExceeded
from triton_dist_tpu.mega import ring as mring
from triton_dist_tpu.models import Engine, ModelConfig
from triton_dist_tpu.runtime import make_mesh
from triton_dist_tpu.serve import KVPool, ResidentWorker, Scheduler

GEO = dict(slots=3, chunk=4, page=8)  # one compiled geometry per module


@pytest.fixture(scope="module")
def mesh1():
    return make_mesh(mesh_shape=(1,), axis_names=("tp",))


@pytest.fixture(scope="module")
def eng1(mesh1):
    cfg = ModelConfig.tiny(num_q_heads=4, num_kv_heads=2,
                           max_positions=64)
    return Engine(cfg, mesh1, decode_mode="ar", max_len=64,
                  donate_cache=False)


@pytest.fixture(scope="module")
def prompts(eng1):
    rng = np.random.default_rng(7)
    v = eng1.cfg.vocab_size
    return [list(map(int, rng.integers(0, v, n))) for n in (12, 10, 9)]


def _host_tokens(eng, prompts, gen, **submit_kw):
    sch = Scheduler(eng, **GEO)
    reqs = [sch.submit(p, max_new_tokens=gen,
                       **{k: (v[i] if isinstance(v, list) else v)
                          for k, v in submit_kw.items()})
            for i, p in enumerate(prompts)]
    sch.run()
    return [r.out_tokens for r in reqs]


# ---------- injection-ring unit contract ----------


def test_ring_seq_visibility_and_overflow():
    r = mring.InjectionRing(cap=2, max_pages=4, prompt_cap=8, chunk=4)
    r.admit(0, [1, 2, 3], 4, 0.0, 0, None, req_id=11,
            table_row=np.arange(1, 5))
    assert r.buf[0, mring.IR_SEQ] == 1  # committed LAST, 1-based
    assert r.pending() == 1
    r.retire(1, req_id=12)
    with pytest.raises(RuntimeError, match="overflow"):
        r.admit(2, [1], 1, 0.0, 0, None, req_id=13,
                table_row=np.zeros(4))
    r.ack(2)
    # consumption alone does NOT free the admission row: slot 0 still
    # streams prefill chunks from it (the pin; see the churn test
    # below for the end-to-end property)
    assert not r.can_claim()
    with pytest.raises(RuntimeError, match="pinned"):
        r.admit(2, [1], 1, 0.0, 0, None, req_id=13,
                table_row=np.zeros(4))
    r.unpin(11)  # first emission came back: prefill done
    r.admit(2, [1], 1, 0.0, 0, None, req_id=13, table_row=np.zeros(4))
    assert r.pending() == 1


def test_ring_version_tracks_mutations():
    """The producer bumps `version` on every buffer mutation — the
    worker's device-upload cache keys on it, so a steady-state window
    (no records) must see an unchanged version."""
    r = mring.InjectionRing(cap=4, max_pages=2, prompt_cap=4, chunk=2)
    v0 = r.version
    r.admit(0, [1], 1, 0.0, 0, None, req_id=1, table_row=np.zeros(2))
    assert r.version == v0 + 1
    r.retire(0, req_id=1)
    assert r.version == v0 + 2
    r.ack(2)
    r.unpin(1)
    assert r.version == v0 + 2  # ack/unpin never touch the buffer
    r.abandon()
    assert r.version == v0 + 3


def test_ring_abandon_publishes_without_commit():
    r = mring.InjectionRing(cap=4, max_pages=2, prompt_cap=4, chunk=2)
    r.abandon()
    assert r.pending() == 1
    assert r.buf[0, mring.IR_SEQ] == 0  # the hole the device must see
    assert bool(mring.head_abandoned(jnp.asarray(r.buf),
                                     jnp.int32(r.published),
                                     jnp.int32(0)))


def test_out_ring_decode_strictness():
    buf = np.zeros((4, mring.OR_WIDTH), np.int32)
    buf[0] = [1, 0, 5, 42, mring.FLAG_EMIT, 0, 9, 0]
    recs = mring.decode_out_ring(buf, 1)
    assert recs[0].token == 42 and recs[0].emitted \
        and not recs[0].retired
    buf[1, mring.OR_SEQ] = 7  # gap
    with pytest.raises(ValueError, match="seq"):
        mring.decode_out_ring(buf, 2)


def test_device_key_stream_matches_worker(eng1):
    """The in-loop fold_in(PRNGKey(seed), n_out) derivation is bitwise
    the host Worker.key_for stream (the sampled bit-identity's key
    half)."""
    import jax

    pool = KVPool(eng1, slots=2, page=8)
    w = ResidentWorker(eng1, pool, chunk=4, window=2)
    dev = jax.jit(lambda s, i: jax.random.fold_in(
        jax.random.PRNGKey(s), i))(jnp.int32(41), jnp.int32(3))
    np.testing.assert_array_equal(np.asarray(dev), w.key_for(41, 3))


# ---------- resident bit-identity (the acceptance oracle) ----------


def test_resident_bit_identical_greedy_with_midloop_retirement(
        eng1, prompts, step_widths):
    """3 staggered requests, one cancelled mid-loop: every request's
    tokens (including the cancelled one's emitted prefix) are bitwise
    the host-loop scheduler's."""
    host = _host_tokens(eng1, prompts, 8)

    sch = Scheduler(eng1, resident=True, window=2, **GEO)
    reqs = [sch.submit(p, max_new_tokens=8) for p in prompts]
    sch.step()
    sch.step()  # a few windows in: all slots live
    sch.cancel(reqs[1])
    sch.run()
    assert reqs[1].state.name == "CANCELLED"
    assert 0 < len(reqs[1].out_tokens) < 8
    assert reqs[1].out_tokens == host[1][:len(reqs[1].out_tokens)]
    assert reqs[0].out_tokens == host[0]
    assert reqs[2].out_tokens == host[2]
    sch.pool.check()
    assert sch.pool.used_pages() == 0


def test_resident_bit_identical_sampled(eng1, prompts, step_widths):
    host = _host_tokens(eng1, prompts, 6, temperature=0.9,
                        seed=[51, 52, 53])
    sch = Scheduler(eng1, resident=True, window=8, **GEO)
    reqs = [sch.submit(p, max_new_tokens=6, temperature=0.9,
                       seed=51 + i) for i, p in enumerate(prompts)]
    sch.run()
    assert [r.out_tokens for r in reqs] == host
    assert len({tuple(t) for t in host}) > 1  # seeds actually diverge


def test_resident_staggered_admission_inside_window(eng1, prompts):
    """An at_step-gated record admits MID-WINDOW: the device consumes
    it at that step boundary (first emission lands at a later device
    step) and the request's tokens are still bitwise the host-loop
    run's — admission time is scheduling, never numerics."""
    host = _host_tokens(eng1, prompts[:2], 5)

    pool = KVPool(eng1, GEO["slots"], GEO["page"])
    w = ResidentWorker(eng1, pool, GEO["chunk"], window=12)
    for slot, (p, at) in enumerate(zip(prompts[:2], (0, 4))):
        total = len(p) + 5
        pool.admit(slot, len(p))
        assert pool.ensure(slot, total)
        w.admit(slot, p, 5, 0.0, 0, None, req_id=slot, at_step=at)
    recs = w.run_window()
    while any(w.slot_state[:, mring.SS_ACTIVE]):
        recs += w.run_window()
    toks = {0: [], 1: []}
    first_step = {}
    for r in recs:
        if r.emitted:
            toks[r.req_id].append(r.token)
            first_step.setdefault(r.req_id, r.step)
    assert [toks[0], toks[1]] == host
    # slot 1's prefill (10 tokens, chunk 4 -> 3 steps) started at
    # device step 4, so its first emission is at step >= 6
    assert first_step[1] >= 6 > first_step[0]


def test_resident_matches_engine_serve_oracle(eng1, prompts, step_widths):
    """Transitivity spot-check against the ORIGINAL sequential oracle
    (Engine.serve stepwise), not just the host-loop scheduler."""
    sch = Scheduler(eng1, resident=True, window=8, **GEO)
    reqs = [sch.submit(p, max_new_tokens=6) for p in prompts]
    sch.run()
    seq = [
        list(map(int, np.asarray(
            eng1.serve(np.asarray([p], np.int32), 6, **GEO))[0]))
        for p in prompts
    ]
    assert [r.out_tokens for r in reqs] == seq


def test_prefill_bit_identical_under_ring_wrap_churn(eng1):
    """Regression (the reuse-while-read bug): an admission row is the
    slot's prefill staging buffer for EVERY later chunk, long after the
    record itself was consumed — ring churn during a long prefill must
    never reclaim and overwrite the row mid-stream. A 40-token prompt
    prefills 4 tokens per window (window=1) while enough short
    requests flow through a cap-4 ring to wrap it twice over; without
    the pin the long request's later chunks read the overwriting
    record's bytes and the tokens silently diverge."""
    rng = np.random.default_rng(23)
    v = eng1.cfg.vocab_size
    long_p = list(map(int, rng.integers(0, v, 40)))
    shorts = [list(map(int, rng.integers(0, v, 5))) for _ in range(8)]
    all_p = [long_p] + shorts

    host = _host_tokens(eng1, all_p, 3)

    sch = Scheduler(eng1, resident=True, window=1, ring_cap=4, **GEO)
    reqs = [sch.submit(p, max_new_tokens=3) for p in all_p]
    sch.run()
    assert [r.out_tokens for r in reqs] == host
    sch.pool.check()
    assert sch.pool.used_pages() == 0
    assert sch.worker.ring._pins == {}  # every pin released


def test_resident_auto_host_pick_tolerates_window_arg(eng1, prompts,
                                                      monkeypatch):
    """resident="auto" endorses window/ring_cap (the chooser may pick
    resident) — when it picks the HOST loop instead, the args are moot,
    not an assertion failure."""
    from triton_dist_tpu import perf_model

    monkeypatch.setattr(perf_model, "choose_serve_mode",
                        lambda *a, **k: "host")
    sch = Scheduler(eng1, resident="auto", window=8, ring_cap=16, **GEO)
    assert sch.resident is False
    reqs = [sch.submit(p, max_new_tokens=4) for p in prompts]
    sch.run()
    assert [r.out_tokens for r in reqs] == _host_tokens(eng1, prompts, 4)


# ---------- ring-abandonment chaos (guard polarity) ----------


def test_abandoned_ring_trips_never_hangs_never_eats_tokens(
        eng1, prompts):
    from triton_dist_tpu import faults

    host = _host_tokens(eng1, prompts[:1], 10)
    sch = Scheduler(eng1, resident=True, window=3, max_step_retries=1,
                    retry_backoff_s=0.0005, **GEO)
    req = sch.submit(prompts[0], max_new_tokens=10)
    sch.step()  # clean window 0
    plan = faults.FaultPlan(faults.AbandonedRing(at_window=1))
    with faults.injecting(plan):
        with pytest.raises(DeadlineExceeded) as ei:
            sch.run()
    trips = ei.value.trips
    assert trips and all(t.site_label == "inject" for t in trips)
    # tokens that streamed before/through the trip are the oracle's
    # prefix — the trip ate nothing and corrupted nothing
    assert req.out_tokens == host[0][:len(req.out_tokens)]
    assert len(req.out_tokens) > 0
    m = sch.metrics()
    assert m["guard_trips"] >= 1 and m["retries"] >= 1


def test_resident_failstep_quarantine_parity(eng1, prompts):
    """A persistent device-step fault quarantines the newest admission
    (host-loop parity) and the survivor's tokens stay bitwise."""
    from triton_dist_tpu import faults

    host = _host_tokens(eng1, prompts[:2], 5)
    sch = Scheduler(eng1, resident=True, window=2, max_step_retries=1,
                    retry_backoff_s=0.0005, **GEO)
    reqs = [sch.submit(p, max_new_tokens=5) for p in prompts[:2]]
    plan = faults.FaultPlan(faults.FailStep(at_step=1, times=3))
    with faults.injecting(plan):
        sch.run()
    assert sch.metrics()["quarantined"] == 1
    assert reqs[1].state.name == "FAILED"
    assert reqs[0].out_tokens == host[0]
    sch.pool.check()
    assert sch.pool.used_pages() == 0  # quarantine released the lane


def test_chaos_cell_serve_resident_dropped_signal(mesh1, eng1):
    from triton_dist_tpu.faults import chaos

    cells = chaos.run_matrix(
        mesh1, protocols=("serve_resident",),
        faults=("none", "dropped_signal"), serve_engine=eng1)
    by = {(c.protocol, c.fault): c.outcome for c in cells}
    assert by[("serve_resident", "none")] == "recovered"
    assert by[("serve_resident", "dropped_signal")] == "detected"
    assert chaos.check_matrix(cells) == []


# ---------- KVPool -> mega cache bridge under churn ----------


def _dense_from_mega(pc, lengths):
    """Reconstruct each sequence's valid prefix from a
    PagedMegaKVCache through ITS page table (numpy gather)."""
    k = np.asarray(pc.k)
    tbl = np.asarray(pc.table)
    page = k.shape[3]
    out = []
    for b, ln in enumerate(lengths):
        rows = [k[:, :, tbl[b, i // page], i % page] for i in range(ln)]
        out.append(np.stack(rows, axis=2) if rows
                   else np.zeros(k.shape[:2] + (0, k.shape[-1]),
                                 k.dtype))
    return out


def test_pool_mega_export_bitwise_under_churn(eng1, prompts):
    """Allocate/grow/evict/re-admit churn: at every checkpoint the
    pool's as_mega_cache export reconstructs (through its own table)
    bitwise the same sequences as paged_cache_from_dense of the dense
    view, and unallocated table entries stay on the null page 0."""
    sch = Scheduler(eng1, total_pages=4, **GEO)  # tight: forces churn
    reqs = [sch.submit(p, max_new_tokens=12) for p in prompts]
    checked = 0
    for _ in range(40):
        if not sch.step() and sch.queue.peek() is None:
            break
        if not sch.active:
            continue
        sch.pool.check()
        pc = sch.pool.as_mega_cache()
        lens = [int(x) for x in np.asarray(pc.length)]
        # null-page discipline: no allocated position maps to page 0,
        # and unallocated table entries are exactly 0
        from triton_dist_tpu.mega.qwen3 import PagedMegaKVCache
        from triton_dist_tpu.serve import pages_for

        tbl = np.asarray(pc.table)
        for s, ln in enumerate(lens):
            held = sch.pool.used_pages(s)  # may run AHEAD of length
            # (ensure() allocates the next chunk before the step runs)
            assert held >= (pages_for(ln, sch.pool.page) if ln else 0)
            assert (tbl[s, :held] > 0).all()
            assert (tbl[s, held:] == 0).all()
        pc_ref = PagedMegaKVCache.from_dense(
            sch.pool.to_dense(), sch.pool.page, 1 + sch.pool.capacity,
            sch.pool.max_pages)
        got = _dense_from_mega(pc, lens)
        want = _dense_from_mega(pc_ref, lens)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        checked += 1
    assert sum(r.n_evictions for r in reqs) > 0, "churn never evicted"
    assert checked >= 5


def test_pool_mega_export_bitwise_under_resident_serving(eng1, prompts):
    """The same bridge holds mid-flight in RESIDENT mode (the pool's
    lengths mirror the device truth after each window)."""
    from triton_dist_tpu.mega.qwen3 import PagedMegaKVCache

    sch = Scheduler(eng1, resident=True, window=2, **GEO)
    _ = [sch.submit(p, max_new_tokens=8) for p in prompts]
    sch.step()
    sch.step()
    sch.pool.check()
    pc = sch.pool.as_mega_cache()
    lens = [int(x) for x in np.asarray(pc.length)]
    assert sum(lens) > 0
    pc_ref = PagedMegaKVCache.from_dense(
        sch.pool.to_dense(), sch.pool.page, 1 + sch.pool.capacity,
        sch.pool.max_pages)
    for g, w in zip(_dense_from_mega(pc, lens),
                    _dense_from_mega(pc_ref, lens)):
        np.testing.assert_array_equal(g, w)
    sch.run()


# ---------- mega decode_resident (the saturation-loop primitive) ------


def test_mega_decode_resident_bitwise_over_pool_export(eng1, prompts):
    from triton_dist_tpu.mega.qwen3 import MegaQwen3

    cfg = eng1.cfg
    sch = Scheduler(eng1, slots=2, chunk=4, page=8)
    reqs = [sch.submit(p, max_new_tokens=20) for p in prompts[:2]]
    for _ in range(6):
        sch.step()
    assert all(r.state.name == "DECODE" for r in reqs)
    mega = MegaQwen3(cfg, eng1.mesh, batch=2, s_max=sch.pool.t_max,
                     params=eng1.params, donate_cache=False, paged=True,
                     page_size=sch.pool.page,
                     total_pages=1 + sch.pool.capacity)
    tok = jnp.asarray([r.out_tokens[-1] for r in reqs], jnp.int32)
    cache = sch.pool.as_mega_cache()
    seq_t, c = [], cache
    t = tok
    for _ in range(3):
        lg, c = mega.decode_step(t, c)
        t = jnp.argmax(lg, -1).astype(jnp.int32)
        seq_t.append(np.asarray(t))
    out, c2 = mega.decode_resident(tok, sch.pool.as_mega_cache(),
                                   steps=3)
    np.testing.assert_array_equal(np.asarray(out), np.stack(seq_t, 1))
    np.testing.assert_array_equal(np.asarray(c.k), np.asarray(c2.k))


# ---------- perf model + metrics + bench schema ----------


def test_resident_step_model_amortizes_dispatch():
    from triton_dist_tpu.perf_model import (
        SERVE_DISPATCH_US,
        estimate_resident_step_ms,
        estimate_serve_step_ms,
    )

    args = dict(num_layers=36, hidden=4096, inter_loc=1536, hq_loc=4,
                hkv_loc=1, head_dim=128, vocab_loc=18992, n_tokens=4,
                kv_tokens=2048)
    host = estimate_serve_step_ms(**args) + SERVE_DISPATCH_US * 1e-3
    # window=1 pays the poll ON TOP of the undivided dispatch — the
    # resident mode only wins by amortizing, which is the point
    assert estimate_resident_step_ms(**args, window=1) > host
    prev = float("inf")
    for w in (1, 2, 8, 32, 128):
        cur = estimate_resident_step_ms(**args, window=w)
        assert cur < prev + 1e-12  # strictly monotone in window
        prev = cur
    assert estimate_resident_step_ms(**args, window=64) < host


def test_choose_serve_mode_flips_on_dispatch_fraction():
    from triton_dist_tpu.perf_model import choose_serve_mode

    # a small shard: the step is fast, dispatch is material -> resident
    small = choose_serve_mode(4, 256, 128, 4, 2, 64, 1024, slots=4,
                              window=16)
    assert small == "resident"
    # a giant step drowns the dispatch tax -> host loop keeps its
    # eviction flexibility
    big = choose_serve_mode(128, 16384, 53248, 64, 8, 128, 152064,
                            slots=4, kv_tokens=131072, window=16)
    assert big == "host"


def test_resident_metrics_and_gauges(eng1, prompts):
    sch = Scheduler(eng1, resident=True, window=4, **GEO)
    _ = [sch.submit(p, max_new_tokens=4) for p in prompts]
    sch.run()
    m = sch.metrics()
    assert m["resident_windows"] >= 1
    assert m["resident_steps"] >= 4
    assert m["ring_depth"] == 0
    snap = sch.obs.snapshot()
    assert "serve_ring_depth" in snap["gauges"]
    assert snap["counters"]["serve_resident_windows"] == \
        m["resident_windows"]


def test_check_result_serve_resident_keys_travel_together():
    import bench

    base = {"metric": "m", "value": 1.0, "unit": "ms",
            "vs_baseline": 1.0}
    full = dict(base)
    full.update({
        "serve_resident_tokens_per_s": 100.0,
        "serve_resident_hostloop_tokens_per_s": 90.0,
        "serve_resident_vs_hostloop": 1.11,
        "serve_resident_saturation_tokens_per_s": 120.0,
        "serve_resident_window_steps": 16,
        "serve_resident_ring_depth_max": 8,
        "serve_resident_ring_depth_mean": 2.5,
        "serve_resident_raw": {"diffs_ms": [1.0], "p25_ms": 1.0,
                               "min_ms": 1.0},
    })
    assert bench.check_result(full) == []
    missing = dict(full)
    del missing["serve_resident_saturation_tokens_per_s"]
    assert any("travel together" in p
               for p in bench.check_result(missing))
    noraw = dict(full)
    del noraw["serve_resident_raw"]
    assert any("serve_resident_raw" in p
               for p in bench.check_result(noraw))


def test_bench_serve_resident_smoke(mesh1, monkeypatch):
    """Tiny-shape end-to-end smoke of the whole bench arm (schema +
    in-arm bit-identity assert + saturation loop)."""
    import bench

    tiny = ModelConfig.tiny(num_q_heads=4, num_kv_heads=2,
                            max_positions=64)
    monkeypatch.setattr(bench, "_shard_cfg", lambda: tiny)
    monkeypatch.setattr(bench, "CTX", 64)
    out = bench.bench_serve_resident(mesh1, n_requests=3, prompt_len=9,
                                     gen_len=4, window=4,
                                     sat_windows=2)
    assert bench.check_result({
        "metric": "m", "value": 1.0, "unit": "ms", "vs_baseline": 1.0,
        **out}) == []
    assert out["serve_resident_tokens_per_s"] > 0
    assert out["serve_resident_saturation_tokens_per_s"] > 0
    assert out["serve_resident_ring_depth_max"] >= 1
