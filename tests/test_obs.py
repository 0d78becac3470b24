"""Always-on telemetry tests (ISSUE 11): metrics registry, in-kernel
stat rows, flight recorder, SLO health, exporters.

The acceptance pins live here: stat-row sums agree with
trace.attribution per-region totals on a shared traced+metered run;
zero-cost-off bit-identity + unchanged pallas_call_count; a guard-trip
chaos cell produces a flight-recorder dump whose last snapshot contains
the decoded guard row; the bench --obs overhead arm's mechanics.
"""

import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from triton_dist_tpu import faults, obs, trace
from triton_dist_tpu.kernels import AgGemmConfig, ag_gemm
from triton_dist_tpu.kernels.allreduce import (
    AllReduceMethod,
    all_reduce_op,
)
from triton_dist_tpu.lang.core import pallas_call_count
from triton_dist_tpu.obs import stats as ost
from triton_dist_tpu.obs.health import SLOMonitor, SLORule
from triton_dist_tpu.obs.recorder import FlightRecorder
from triton_dist_tpu.obs.registry import Histogram, Registry, log_buckets


@pytest.fixture(scope="module")
def mesh4():
    from triton_dist_tpu.runtime import make_mesh

    return make_mesh(mesh_shape=(4,), axis_names=("tp",))


@pytest.fixture(autouse=True)
def _reset_degraded():
    faults.reset_degraded()
    yield
    faults.reset_degraded()


def _make(shape, seed=0, scale=0.1):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)


# ---------- registry units ----------


def test_counters_gauges_labels():
    r = Registry()
    r.inc("serve_evicted", site="growth")
    r.inc("serve_evicted", 2, site="preemption")
    r.set_gauge("serve_queue_depth", 7)
    assert r.counter("serve_evicted", site="growth") == 1
    assert r.counter("serve_evicted", site="preemption") == 2
    assert r.counter("serve_evicted", site="nope") == 0
    assert r.gauge("serve_queue_depth") == 7
    with pytest.raises(AssertionError):
        r.inc("serve_evicted", -1)  # counters are monotone


def test_histogram_quantile_relative_error():
    rng = np.random.default_rng(3)
    vals = rng.lognormal(mean=8, sigma=1.5, size=4000)
    h = Histogram(log_buckets(10.0, 1e8, 1.05))
    for v in vals:
        h.observe(v)
    for q in (0.5, 0.9, 0.99):
        exact = float(np.quantile(vals, q))
        est = h.quantile(q)
        assert abs(est - exact) / exact < 0.06, (q, est, exact)
    # p0/p100 clamp to the exact observed extremes
    assert h.quantile(0.0) == pytest.approx(vals.min())
    assert h.quantile(1.0) == pytest.approx(vals.max())


def test_snapshot_delta_merge():
    r = Registry()
    r.declare_histogram("serve_ttft_us", 10, 1e8)
    r.inc("serve_steps", 3)
    r.observe("serve_ttft_us", 100.0)
    s0 = r.snapshot()
    r.inc("serve_steps", 2)
    r.observe("serve_ttft_us", 900.0)
    s1 = r.snapshot()
    d = Registry.delta(s1, s0)
    assert d["counters"] == {"serve_steps": 2}
    assert d["histograms"]["serve_ttft_us"]["count"] == 1
    # merging two snapshots of the same traffic doubles counts exactly
    # (the fixed-bucket determinism property)
    m = Registry()
    m.merge(s1)
    m.merge(s1)
    assert m.counter("serve_steps") == 10
    assert m.hist_count("serve_ttft_us") == 4
    # bound mismatch is loud, not silently lossy
    other = Registry()
    other.declare_histogram("serve_ttft_us", 10, 1e8, growth=1.5)
    other.observe("serve_ttft_us", 5.0)
    with pytest.raises(ValueError, match="bounds differ"):
        other.merge(s1)


def test_snapshot_strictness():
    with pytest.raises(ValueError, match="not a metrics snapshot"):
        Registry.check_snapshot({"magic": "nope"})
    bad = Registry().snapshot()
    bad["histograms"]["h"] = {"bounds": [1.0, 2.0], "counts": [1],
                              "count": 1, "sum": 1.0}
    with pytest.raises(ValueError, match="counts"):
        Registry.check_snapshot(bad)


def test_registry_thread_safety():
    r = Registry()

    def work():
        for _ in range(500):
            r.inc("serve_tokens_out")
            r.observe("serve_ttft_us", 100.0)

    ts = [threading.Thread(target=work) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert r.counter("serve_tokens_out") == 4000
    assert r.hist_count("serve_ttft_us") == 4000


# ---------- exporters ----------


def test_prometheus_exposition():
    r = Registry()
    r.inc("serve_evicted", 2, site="growth")
    r.set_gauge("serve_pool_occupancy", 0.5)
    r.declare_histogram("serve_ttft_us", 10, 1000, growth=2.0)
    r.observe("serve_ttft_us", 15.0)
    r.observe("serve_ttft_us", 500.0)
    text = obs.to_prometheus(r)
    assert '# TYPE serve_evicted_total counter' in text
    assert 'serve_evicted_total{site="growth"} 2' in text
    assert 'serve_pool_occupancy 0.5' in text
    # histogram buckets are CUMULATIVE and end at +Inf
    lines = [ln for ln in text.splitlines()
             if ln.startswith("serve_ttft_us_bucket")]
    counts = [int(ln.split()[-1]) for ln in lines]
    assert counts == sorted(counts) and counts[-1] == 2
    assert 'le="+Inf"' in lines[-1]
    assert "serve_ttft_us_count 2" in text


def test_snapshot_file_roundtrip(tmp_path):
    r = Registry()
    r.inc("obs_kernel_events", 5, kernel="ag_gemm")
    p = obs.write_snapshot(r, str(tmp_path / "snap.json"))
    doc = obs.load_snapshot(p)
    r2 = Registry()
    r2.merge(doc)
    assert r2.counter("obs_kernel_events", kernel="ag_gemm") == 5
    bad = tmp_path / "bad.json"
    bad.write_text("{\"magic\": \"wrong\"}")
    with pytest.raises(ValueError):
        obs.load_snapshot(str(bad))


# ---------- stat rows: decode units ----------


def test_stat_row_decode_and_totals():
    row = np.zeros((1, ost.STAT_WORDS), np.int32)
    row[0] = [ost.OMAGIC, 3, 10, 4, 2, 4096, 1, 1]
    (s,) = ost.decode(row)
    assert (s.rank, s.events, s.sem_wait, s.dma_wait, s.send_bytes,
            s.trips, s.fmt_name) == (3, 10, 4, 2, 4096, 1, "fp8")
    tot = ost.totals(np.stack([row, row]))
    assert tot.sem_wait == 8 and tot.send_bytes == 8192
    with pytest.raises(ValueError, match="magic"):
        ost.decode(np.zeros((1, ost.STAT_WORDS), np.int32))


def test_record_stats_feeds_registry():
    r = Registry()
    row = np.zeros((1, ost.STAT_WORDS), np.int32)
    row[0] = [ost.OMAGIC, 0, 6, 3, 1, 512, 0, 2]
    ost.record_stats(r, row, kernel="allreduce")
    assert r.counter("obs_sem_wait_ticks", kernel="allreduce") == 3
    assert r.counter("obs_wire_bytes", kernel="allreduce",
                     fmt="int8") == 512


# ---------- stat rows: the metered kernels ----------


_AG_CFG = AgGemmConfig(16, 128, 64)


def _run_ag(mesh, a, b, n_extra=0):
    return jax.jit(jax.shard_map(
        lambda a, b: ag_gemm(a, b, axis="tp", config=_AG_CFG,
                             force_kernel=True),
        mesh=mesh, in_specs=(P("tp"), P(None, "tp")),
        out_specs=(P("tp"),) + (P("tp"),) * n_extra if n_extra
        else P("tp"),
        check_vma=False))(a, b)


def test_zero_cost_off_ag_gemm(mesh4):
    """No active obs build: identical program, identical bits,
    unchanged pallas_call_count — the trace/guard discipline."""
    a, b = _make((64, 128), 1), _make((128, 4 * 128), 2)
    c0 = pallas_call_count()
    ref = _run_ag(mesh4, a, b)
    plain = pallas_call_count() - c0
    with ost.building():
        pass  # an exited build must leave no residue
    c1 = pallas_call_count()
    again = _run_ag(mesh4, a, b)
    assert pallas_call_count() - c1 == plain
    np.testing.assert_array_equal(np.asarray(again), np.asarray(ref))
    with ost.building():
        c2 = pallas_call_count()
        metered, row = _run_ag(mesh4, a, b, n_extra=1)
        assert pallas_call_count() - c2 == plain, (
            "metering must instrument the SAME kernels, not add calls")
    np.testing.assert_array_equal(np.asarray(metered), np.asarray(ref))
    stats = ost.decode(np.asarray(row).reshape(4, 1, ost.STAT_WORDS))
    assert all(s.rank == i for i, s in enumerate(stats))
    assert all(s.events > 0 and s.sem_wait > 0 and s.dma_wait > 0
               for s in stats)
    # the ring pushes n-1 chunks of m_loc x K f32 per rank
    assert all(s.send_bytes == 3 * 16 * 128 * 4 for s in stats)


def test_stat_rows_agree_with_trace_attribution(mesh4):
    """THE agreement pin (acceptance criterion): on one run built under
    BOTH trace.building() and obs.stats.building(), the O(1) stat rows
    hold exactly the per-region span-time sums trace/attribution
    computes from the full event stream."""
    a, b = _make((64, 128), 3), _make((128, 4 * 128), 4)
    ref = _run_ag(mesh4, a, b)
    with trace.tracing("ag", cap=2048) as (_build, sess):
        with ost.building():
            out, tbuf, orow = _run_ag(mesh4, a, b, n_extra=2)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    tl = sess.assemble({"ag": np.asarray(tbuf).reshape(
        4, -1, trace.RECORD_WORDS)})
    stats = ost.decode(np.asarray(orow).reshape(4, 1, ost.STAT_WORDS))
    ost.agree_with_trace(stats, tl, "ag")  # AssertionError on any diff


@pytest.mark.slow  # agreement is tier-1-pinned by the test above and
# the dryrun obs plane; this variant re-proves it under injected skew
def test_stat_rows_see_injected_skew(mesh4):
    """A straggler's delay ticks the meter's virtual clock exactly as
    it shifts the trace clock, so the agreement pin holds under
    injected skew too. (Per-SOURCE skew attribution is the trace
    tier's delivery replay — attribution.a2a_step_waits; on the
    lockstep clock the O(1) rows see aligned record streams, which is
    exactly what the second assertion pins.)"""
    a, b = _make((64, 128), 5), _make((128, 4 * 128), 6)
    cfg = AgGemmConfig(16, 128, 64, straggler_rank=1, straggler_ns=7)

    def run(n_extra):
        return jax.jit(jax.shard_map(
            lambda a, b: ag_gemm(a, b, axis="tp", config=cfg,
                                 force_kernel=True),
            mesh=mesh4, in_specs=(P("tp"), P(None, "tp")),
            out_specs=(P("tp"),) + (P("tp"),) * n_extra,
            check_vma=False))(a, b)

    with trace.tracing("ag_skew", cap=2048) as (_build, sess):
        with ost.building():
            _out, tbuf, orow = run(2)
    tl = sess.assemble({"ag_skew": np.asarray(tbuf).reshape(
        4, -1, trace.RECORD_WORDS)})
    stats = ost.decode(np.asarray(orow).reshape(4, 1, ost.STAT_WORDS))
    ost.agree_with_trace(stats, tl, "ag_skew")
    # the instrumented kernels emit the SAME static record sequence on
    # every rank (the cross-rank alignment the trace clock rests on) —
    # the meter's event counts must reflect it
    assert len({s.events for s in stats}) == 1


def test_metered_two_shot_ar_and_wire_bytes(mesh4):
    """The ambient-attach style (AR ring legs through the shmem hooks):
    sem-wait ticks land, wire bytes land at the format actually on the
    wire — fp8 rows strictly fewer bytes than native f32 rows — and
    zero-cost-off holds."""
    arr = _make((4, 16, 256), 7)
    c0 = pallas_call_count()
    ref = all_reduce_op(arr, mesh4, axis="tp",
                        method=AllReduceMethod.TwoShot)
    plain = pallas_call_count() - c0
    with ost.metered() as reg:
        c1 = pallas_call_count()
        out = all_reduce_op(arr, mesh4, axis="tp",
                            method=AllReduceMethod.TwoShot)
        assert pallas_call_count() - c1 == plain
        all_reduce_op(arr, mesh4, axis="tp", wire_format="fp8")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert reg.counter("obs_sem_wait_ticks", kernel="allreduce") > 0
    b_nat = reg.counter("obs_wire_bytes", kernel="allreduce",
                        fmt="native")
    b_fp8 = reg.counter("obs_wire_bytes", kernel="allreduce", fmt="fp8")
    assert 0 < b_fp8 < b_nat
    # native RS+AG: each rank puts (n-1) RS hops + (n-1) AG chunk
    # forwards of (m/n x 256) f32 rows, n ranks total
    assert b_nat == 4 * (3 + 3) * 4 * 256 * 4


def test_metered_ll_allgather_op(mesh4):
    from triton_dist_tpu.kernels.low_latency_allgather import (
        ll_all_gather_op,
    )
    from triton_dist_tpu.runtime.symm_mem import SymmetricWorkspace

    ws = SymmetricWorkspace(mesh4)
    x = _make((4 * 8, 128), 8)
    ref = ll_all_gather_op(x, ws, 0, mesh4, axis="tp")
    with ost.metered() as reg:
        out = ll_all_gather_op(x, ws, 1, mesh4, axis="tp")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert reg.counter("obs_sem_wait_ticks",
                       kernel="low_latency_allgather") > 0
    # full-mesh push: n ranks x (n-1) puts of (8 x 128) f32
    assert reg.counter("obs_wire_bytes", kernel="low_latency_allgather",
                       fmt="native") == 4 * 3 * 8 * 128 * 4


@pytest.mark.needs_semaphore_read
def test_guard_trips_land_in_stat_rows(mesh4):
    """Guard + obs coexistence: a tripped watchdog bumps the stat row's
    trip counter (through GuardCtx.octx / the ambient meter)."""
    arr = _make((4, 16, 128), 9)
    plan = faults.FaultPlan(faults.DroppedSignal(2, label="credit"))
    with ost.metered() as reg:
        with faults.building(), faults.injecting(plan):
            with pytest.raises(faults.DeadlineExceeded):
                all_reduce_op(arr, mesh4, axis="tp",
                              method=AllReduceMethod.TwoShot)
    assert reg.counter("obs_guard_trips", kernel="allreduce") > 0


@pytest.mark.needs_semaphore_read
def test_sp_flash_decode_ll_under_guard_and_obs_builds(mesh4):
    """Composite-caller build safety: sp_flash_decode's LL-AG partial
    exchange must strip BOTH trailing buffers (guard row under
    faults.building(), stat row under obs builds) — a missing
    guard.primary here is a trace-time unpack error."""
    from triton_dist_tpu.kernels.flash_decode import (
        create_sp_decode_buf,
        sp_flash_decode,
    )

    b, t, hq, hkv, d = 1, 32, 2, 1, 16
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.standard_normal((b, hq, d)) * 0.1, jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, hkv, d)) * 0.1,
                    jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, hkv, d)) * 0.1,
                    jnp.float32)
    kv_len = jnp.asarray([t])

    def step(qs, ks, vs):
        buf = create_sp_decode_buf(b, hq, d, 4)
        y, _ = sp_flash_decode(qs, ks, vs, kv_len, axis="tp",
                               ll_buf=buf, call_count=0)
        return y

    f = jax.jit(jax.shard_map(
        step, mesh=mesh4, in_specs=(P(), P(None, "tp"), P(None, "tp")),
        out_specs=P(), check_vma=False))
    ref = f(q, k, v)
    with ost.building(), faults.building():
        got = jax.jit(jax.shard_map(
            step, mesh=mesh4,
            in_specs=(P(), P(None, "tp"), P(None, "tp")),
            out_specs=P(), check_vma=False))(q, k, v)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# ---------- flight recorder ----------


def test_flight_ring_bounds_and_roundtrip(tmp_path):
    rec = FlightRecorder(cap=3, dir=str(tmp_path))
    r = Registry()
    for i in range(5):
        r.inc("serve_steps")
        rec.record(registry=r, scheduler_state={"n_steps": i}, step=i)
    assert len(rec) == 3  # bounded ring
    assert [s["step"] for s in rec.snapshots()] == [2, 3, 4]
    # deltas: each step's counter delta is exactly 1
    assert rec.last["metrics_delta"]["counters"] == {"serve_steps": 1}
    path = rec.dump(reason="unit")
    doc = obs.load_dump(path)
    assert doc["reason"] == "unit" and len(doc["snapshots"]) == 3
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"magic": "tdt-flight",
                               "snapshots": [{"step": 0}]}))
    with pytest.raises(ValueError, match="malformed"):
        obs.load_dump(str(bad))


@pytest.mark.needs_semaphore_read
def test_guard_trip_cell_dumps_with_decoded_row(mesh4, tmp_path):
    """Acceptance criterion: a guard-trip chaos cell produces a
    flight-recorder dump whose LAST snapshot contains the decoded
    guard row."""
    arr = _make((4, 16, 128), 10)
    plan = faults.FaultPlan(faults.DroppedSignal(2, label="credit"))
    rec = FlightRecorder(cap=8, dir=str(tmp_path))
    reg = Registry()
    with faults.building(), faults.injecting(plan):
        with pytest.raises(faults.DeadlineExceeded) as ei:
            all_reduce_op(arr, mesh4, axis="tp",
                          method=AllReduceMethod.TwoShot)
    rec.record(registry=reg, error=ei.value)
    path = rec.dump(reason="chaos cell: dropped credit")
    doc = obs.load_dump(path)
    rows = doc["snapshots"][-1]["guard_rows"]
    assert rows, "the dump's last snapshot must carry the guard rows"
    assert rows[0]["site_label"] == "credit"
    assert rows[0]["observed"] == 0 and rows[0]["expected"] >= 1


def test_scheduler_quarantine_dumps_trip_context(tmp_path):
    """The serve integration of the same contract: a step that dies on
    a DeadlineExceeded carrying guard rows quarantines AND auto-dumps;
    the dump's last snapshot holds the rows + the scheduler state."""
    from triton_dist_tpu.models import Engine, ModelConfig
    from triton_dist_tpu.runtime import make_mesh
    from triton_dist_tpu.serve import Scheduler

    mesh = make_mesh(mesh_shape=(1,), axis_names=("tp",))
    eng = Engine(ModelConfig.tiny(max_positions=32), mesh,
                 decode_mode="ar", max_len=32, donate_cache=False)
    sch = Scheduler(eng, slots=2, chunk=4, page=8,
                    recorder=FlightRecorder(cap=8, dir=str(tmp_path)),
                    max_step_retries=0)
    trip = faults.GuardTrip(rank=1, site=faults.SITES["ring"], slot=2,
                            progress=1, expected=8, observed=3, seq=0)
    real_step = sch.worker.step
    state = {"armed": True}

    def failing_step(*a, **k):
        if state.pop("armed", False):
            raise faults.DeadlineExceeded("ring wait tripped",
                                          trips=[trip])
        return real_step(*a, **k)

    sch.worker.step = failing_step
    sch.submit([1, 2, 3], max_new_tokens=2)
    sch.run()
    assert sch.metrics()["quarantined"] == 1
    assert sch.obs.counter("serve_guard_trips", site="ring") == 1
    doc = obs.load_dump(sch.last_flight_dump)
    rows = doc["snapshots"][-1]["guard_rows"]
    assert rows and rows[0]["site_label"] == "ring"
    assert rows[0]["rank"] == 1 and rows[0]["observed"] == 3
    assert doc["snapshots"][-1]["scheduler"]["quarantined"] == 1


# ---------- SLO health ----------


def test_slo_rule_parse():
    r = SLORule.parse("ttft_p99_us < 5000")
    assert (r.metric, r.op, r.threshold) == ("ttft_p99_us", "<", 5000.0)
    assert SLORule.parse("tokens_per_s > 1e3").threshold == 1000.0
    with pytest.raises(ValueError, match="bad SLO rule"):
        SLORule.parse("ttft_p99_us ~= 5")


def test_slo_idle_is_healthy_and_violation_degrades():
    reg = Registry()
    reg.declare_histogram("serve_ttft_us", 10, 1e8)
    mon = SLOMonitor(["ttft_p99_us < 5000"], window=4)
    assert mon.feed(reg).status == "healthy"  # unmeasurable holds
    for _ in range(20):
        reg.observe("serve_ttft_us", 50_000.0)
    st = mon.feed(reg)
    assert st.status == "degraded" and len(st.violations) == 1
    assert "ttft_p99_us" in str(st.violations[0])


def test_slo_degrade_action_feeds_guard_registry():
    reg = Registry()
    mon = SLOMonitor([
        SLORule.parse("guard_trip_rate < 0.5", action="degrade",
                      protocol="allreduce"),
    ], window=8)
    mon.feed(reg)
    for _ in range(4):
        reg.inc("serve_steps")
        # the key exactly as Scheduler._run_step writes it: labelled
        # by trip site — guard_trip_rate must fold across sites
        reg.inc("serve_guard_trips", site="DeadlineExceeded")
        mon.feed(reg)
    assert mon.last.status == "critical"
    assert faults.is_degraded("allreduce"), (
        "a violated degrade-rule must mark its protocol degraded — "
        "the feed into the PR-9 fallback ladder")


def test_slo_absent_metric_stays_unmeasurable():
    # an absent counter is unmeasurable (None), NOT 0.0 — '> N'
    # objectives over a key nothing writes must hold even once the
    # window has two snapshots
    reg = Registry()
    mon = SLOMonitor(["serve_tokens_out > 1"], window=4)
    for _ in range(3):
        assert mon.feed(reg).status == "healthy"
    # same contract for the trip-rate shorthand: steps without any
    # guard-trip series measure 0/steps = 0, which satisfies '< 0.5'
    mon2 = SLOMonitor(["guard_trip_rate < 0.5"], window=4)
    mon2.feed(reg)
    reg.inc("serve_steps")
    assert mon2.feed(reg).status == "healthy"


def test_slo_tokens_per_s_window():
    reg = Registry()
    mon = SLOMonitor(["tokens_per_s > 1"], window=4)
    mon.feed(reg)
    assert mon.last.status == "healthy"  # single snapshot: no window
    for _ in range(3):
        reg.inc("serve_tokens_out", 100000)
        mon.feed(reg)
    assert mon.last.status == "healthy"
    mon2 = SLOMonitor(["tokens_per_s > 1e12"], window=4)
    mon2.feed(reg)
    reg.inc("serve_tokens_out")
    assert mon2.feed(reg).status == "degraded"


# ---------- trace_report --metrics ----------


def _report_cli():
    import importlib.util
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "_tdt_trace_report", os.path.join(repo, "scripts",
                                          "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_report_metrics_mode(tmp_path, capsys):
    cli = _report_cli()
    r = Registry()
    r.inc("serve_admitted", 3)
    r.declare_histogram("serve_ttft_us", 10, 1e8)
    r.observe("serve_ttft_us", 777.0)
    snap = obs.write_snapshot(r, str(tmp_path / "s.json"))
    rec = FlightRecorder(cap=4, dir=str(tmp_path))
    rec.record(registry=r, scheduler_state={"queue_depth": 1})
    dump = rec.dump(reason="unit")
    assert cli.main(["--metrics", snap, dump]) == 0
    out = capsys.readouterr().out
    assert "serve_admitted" in out and "flight recorder" in out
    bad = tmp_path / "bad.json"
    bad.write_text("{}")
    assert cli.main(["--metrics", str(bad)]) == 1
    # and a metrics file fed to the TRACE mode path fails loudly too
    assert cli.main([snap]) == 1


# ---------- summarize on registry histograms ----------


def test_summarize_quantiles_match_exact_within_bucket_error():
    from triton_dist_tpu.serve.request import Request, RequestState, \
        summarize

    rng = np.random.default_rng(11)
    reqs = []
    for i in range(200):
        r = Request(prompt=[1], max_new_tokens=3)
        r.state = RequestState.FINISHED
        r.t_submit = 0
        base = int(rng.lognormal(10, 1) * 1e3)
        r.token_times = [base, base + 2_000_000, base + 4_000_000]
        r.out_tokens = [1, 2, 3]
        reqs.append(r)
    m = summarize(reqs)
    exact = np.quantile([r.ttft_us() for r in reqs], 0.99)
    assert abs(m["ttft_p99_us"] - exact) / exact < 0.06
    assert m["n"] == 200


# ---------- bench --obs arm (tiny-shape smoke) ----------


@pytest.fixture(scope="module")
def mesh1():
    from triton_dist_tpu.runtime import make_mesh

    return make_mesh(mesh_shape=(1,), axis_names=("tp",))


@pytest.mark.slow
def test_bench_obs_arm_smoke(mesh1):
    import sys

    sys.path.insert(0, ".")
    import bench

    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (64, 256)) * 0.02, jnp.bfloat16)
    w1 = jnp.asarray(np.random.default_rng(1).standard_normal(
        (256, 512)) * 0.02, jnp.bfloat16)
    # ceil relaxed: sub-ms chains are timer noise; the arm's mechanics
    # (metered chain runs, nonzero event audit) are the test. The
    # chain timer refuses t_hi <= t_lo rather than clamping — retry
    # through transient scheduler noise like bench.main does.
    for attempt in range(3):
        try:
            frac, m_ms, un_ms, nev = bench.bench_obs_overhead(
                mesh1, x, w1, k_hi=9, pairs=3, out_cols=256, ceil=10.0)
            break
        except RuntimeError:
            if attempt == 2:
                raise
    assert nev > 0 and m_ms > 0 and un_ms > 0
    r = {"metric": "m", "value": 1.0, "unit": "ms", "vs_baseline": 1.0,
         "obs_overhead_frac": float(frac), "obs_stat_events": nev}
    assert bench.check_result(r) == []
    r.pop("obs_stat_events")
    assert any("travel together" in p for p in bench.check_result(r))
    r["obs_stat_events"] = 0
    assert any("must be > 0" in p for p in bench.check_result(r))
