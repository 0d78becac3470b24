"""Guarded-execution tests (ISSUE 10): fault-injection plane, bounded
watchdogs, wire integrity, liveness under symbolic faults, serve
degradation, and the chaos matrix.

The heavyweight full matrix runs in __graft_entry__'s dryrun chaos
plane; here tier-1 covers every mechanism at n=2/4 on the shared mesh.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from triton_dist_tpu import faults, verify, wire
from triton_dist_tpu.faults import chaos
from triton_dist_tpu.faults import guard as fguard
from triton_dist_tpu.kernels.allreduce import (
    all_reduce_op,
    two_shot_all_reduce,
)
from triton_dist_tpu.kernels.low_latency_allgather import (
    create_ll_ag_buffer,
    ll_all_gather,
    ll_all_gather_op,
)
from triton_dist_tpu.lang.core import pallas_call_count


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


# ---------- fault-plan units ----------


def test_plan_queries():
    p = faults.FaultPlan(
        faults.DelayedSend(1, 1000, protocol="allgather"),
        faults.StalledRank(2, 9999),
        faults.DroppedSignal(3, label="credit"),
    )
    # StalledRank dominates and matches any protocol
    assert p.straggler_for("allgather") == (2, 9999)
    assert p.straggler_for("other") == (2, 9999)
    assert p.dropped_signal_rank("credit") == 3
    assert p.dropped_signal_rank("barrier") is None
    assert faults.FaultPlan(
        faults.DroppedSignal(1)).dropped_signal_rank("barrier") == 1


def test_plan_step_fault_consumes_times():
    p = faults.FaultPlan(faults.FailStep(at_step=2, times=2))
    assert p.step_fault(0) is None
    e1, e2, e3 = (p.step_fault(2) for _ in range(3))
    assert isinstance(e1, faults.DeadlineExceeded)
    assert isinstance(e2, faults.DeadlineExceeded)
    assert e3 is None  # times exhausted
    pi = faults.FaultPlan(faults.FailStep(0, error="integrity"))
    assert isinstance(pi.step_fault(0), faults.WireIntegrityError)


def test_plan_unknown_fault_rejected():
    with pytest.raises(TypeError, match="unknown fault"):
        faults.FaultPlan("dropped_signal")


def test_injecting_restores_previous_plan():
    assert faults.active() is None
    p1 = faults.FaultPlan(faults.DroppedSignal(0))
    with faults.injecting(p1):
        assert faults.active() is p1
        with faults.injecting(faults.FaultPlan()):
            assert faults.active() is not p1
        assert faults.active() is p1
    assert faults.active() is None


# ---------- guard buffer / decode units ----------


def test_guard_stream_decode_roundtrip():
    b = faults.GuardBuild(cap=4)
    g = fguard.new_stream(b, rank=3)
    assert faults.decode(np.asarray(g)) == []
    g = fguard.stream_trip(g, jnp.asarray(False), site="wire", slot=2,
                           rank=3)
    g = fguard.stream_trip(g, jnp.asarray(True), site="wire")  # no-op
    trips = faults.decode(np.asarray(g))
    assert len(trips) == 1
    t = trips[0]
    assert (t.site_label, t.slot, t.rank) == ("wire", 2, 3)


def test_guard_decode_rejects_clobbered_header():
    b = faults.GuardBuild(cap=2)
    g = np.asarray(fguard.new_stream(b)).copy()
    g[0, 0] = 0
    with pytest.raises(ValueError, match="magic"):
        faults.decode(g)


def test_guard_check_error_classes():
    b = faults.GuardBuild(cap=4)
    gw = fguard.stream_trip(fguard.new_stream(b), jnp.asarray(False),
                            site="wire")
    with pytest.raises(faults.WireIntegrityError):
        faults.check(np.asarray(gw))
    gd = fguard.stream_trip(fguard.new_stream(b), jnp.asarray(False),
                            site="barrier")
    with pytest.raises(faults.DeadlineExceeded) as ei:
        faults.check(np.asarray(gd), np.asarray(gw), context="unit")
    assert "unit" in str(ei.value) and len(ei.value.trips) == 2
    faults.check(np.asarray(fguard.new_stream(b)))  # clean: no raise


# ---------- zero cost when off (tentpole contract) ----------


def _run_ar(mesh4, x, guarded, plan=None, fmt=None):
    b = faults.building() if guarded else contextlib.nullcontext()
    inj = faults.injecting(plan) if plan else contextlib.nullcontext()
    with b, inj:
        fn = jax.jit(jax.shard_map(
            lambda xs: two_shot_all_reduce(xs[0], "tp", wire_format=fmt),
            mesh=mesh4, in_specs=P("tp"),
            out_specs=(P("tp"), P("tp")) if guarded else P("tp"),
            check_vma=False))
        return fn(x)


def test_guards_off_bit_identity_and_call_count(mesh4):
    x = _make((4, 16, 128), seed=1)
    c0 = pallas_call_count()
    ref = _run_ar(mesh4, x, guarded=False)
    plain_calls = pallas_call_count() - c0
    # an EXITED build/plan must leave no residue on later builds
    with faults.building():
        pass
    with faults.injecting(faults.FaultPlan(faults.DroppedSignal(0))):
        pass
    c1 = pallas_call_count()
    again = jax.jit(jax.shard_map(
        lambda xs: two_shot_all_reduce(xs[0], "tp"), mesh=mesh4,
        in_specs=P("tp"), out_specs=P("tp"), check_vma=False))(x)
    assert pallas_call_count() - c1 == plain_calls
    np.testing.assert_array_equal(np.asarray(again), np.asarray(ref))


@pytest.mark.needs_semaphore_read
def test_guards_on_clean_is_bit_identical(mesh4):
    x = _make((4, 16, 128), seed=2)
    ref = _run_ar(mesh4, x, guarded=False)
    out, g = _run_ar(mesh4, x, guarded=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert faults.decode(np.asarray(g)) == []


# ---------- watchdog trips on the kernel families ----------


@pytest.mark.needs_semaphore_read
def test_ar_dropped_credit_trips_watchdog(mesh4):
    x = _make((4, 16, 128), seed=3)
    plan = faults.FaultPlan(faults.DroppedSignal(2, label="credit"))
    _out, g = _run_ar(mesh4, x, guarded=True, plan=plan)
    trips = faults.decode(np.asarray(g))
    assert trips, "dropped credit must trip the credit watchdog"
    assert {t.site_label for t in trips} == {"credit"}
    t = trips[0]
    assert t.expected == 1 and t.observed == 0
    with pytest.raises(faults.DeadlineExceeded):
        faults.check(np.asarray(g), context="two_shot_ar")


@pytest.mark.needs_semaphore_read
def test_ar_dropped_barrier_trips_all_ranks(mesh4):
    x = _make((4, 16, 128), seed=4)
    plan = faults.FaultPlan(faults.DroppedSignal(2, label="barrier"))
    _out, g = _run_ar(mesh4, x, guarded=True, plan=plan)
    trips = faults.decode(np.asarray(g))
    assert {t.site_label for t in trips} == {"barrier"}
    # the neighbor barrier is 2-deep: the dropped rank's two neighbors
    # see one missing contribution each, on BOTH ring legs
    assert {t.rank for t in trips} == {1, 3}
    assert all(t.observed == t.expected - 1 for t in trips)


@pytest.mark.needs_semaphore_read
def test_ar_delay_and_stall_recover_bitwise(mesh4):
    x = _make((4, 16, 128), seed=5)
    ref = _run_ar(mesh4, x, guarded=False)
    for fault in (faults.DelayedSend(3, 60_000),
                  faults.StalledRank(2, 800_000)):
        out, g = _run_ar(mesh4, x, guarded=True,
                         plan=faults.FaultPlan(fault))
        assert faults.decode(np.asarray(g)) == []
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def _run_ll(mesh4, guarded, plan=None, fmt=None, n=4):
    x = _make((n * 8, 128), seed=6, scale=1.0)
    b = faults.building() if guarded else contextlib.nullcontext()
    inj = faults.injecting(plan) if plan else contextlib.nullcontext()
    with b, inj:
        def per_dev(xs):
            buf = create_ll_ag_buffer(xs.shape, xs.dtype, n,
                                      wire_format=fmt)
            return ll_all_gather(xs, buf, 0, "tp", wire_format=fmt)

        fn = jax.jit(jax.shard_map(
            per_dev, mesh=mesh4, in_specs=P("tp"),
            out_specs=(P(None, "tp"), P("tp"))
            + ((P("tp"),) if guarded else ()),
            check_vma=False))
        return fn(x)


@pytest.mark.needs_semaphore_read
def test_ll_ag_dropped_barrier_trips(mesh4):
    plan = faults.FaultPlan(faults.DroppedSignal(1, label="barrier"))
    res = _run_ll(mesh4, guarded=True, plan=plan)
    g = np.asarray(res[2]).reshape(4, -1, faults.GUARD_WORDS)
    trips = faults.decode(g)
    # full-team barrier: every rank is short rank 1's contribution
    assert len(trips) == 4
    assert all(t.site_label == "barrier" and t.observed == 3
               for t in trips)


@pytest.mark.needs_semaphore_read
def test_ll_ag_wire_corruption_detected(mesh4):
    fmt = wire.WireFormat("fp8", checksum=True)
    clean = _run_ll(mesh4, guarded=True, fmt=fmt)
    assert faults.decode(np.asarray(clean[2]).reshape(
        4, -1, faults.GUARD_WORDS)) == []
    plan = faults.FaultPlan(faults.BitFlipPayload(row=1, byte=3, bit=2))
    res = _run_ll(mesh4, guarded=True, plan=plan, fmt=fmt)
    g = np.asarray(res[2]).reshape(4, -1, faults.GUARD_WORDS)
    trips = faults.decode(g)
    assert trips and all(t.site_label == "wire" for t in trips)
    with pytest.raises(faults.WireIntegrityError):
        faults.check(g)


@pytest.mark.needs_semaphore_read
def test_sp_flash_prefill_dropped_barrier_trips(mesh4):
    from triton_dist_tpu.kernels.flash_prefill import sp_flash_prefill

    q = _make((1, 4 * 8, 2, 32), seed=7, scale=1.0)
    kv = _make((1, 4 * 8, 1, 32), seed=8, scale=1.0)
    plan = faults.FaultPlan(faults.DroppedSignal(3, label="barrier"))
    with faults.building(), faults.injecting(plan):
        fn = jax.jit(jax.shard_map(
            lambda q, k, v: sp_flash_prefill(q, k, v, "tp", block=8),
            mesh=mesh4,
            in_specs=(P(None, "tp"), P(None, "tp"), P(None, "tp")),
            out_specs=(P(None, "tp"), P("tp")), check_vma=False))
        _out, g = fn(q, kv, kv)
    trips = faults.decode(np.asarray(g).reshape(4, -1,
                                                faults.GUARD_WORDS))
    assert len(trips) == 4
    assert all(t.site_label == "barrier" for t in trips)


@pytest.mark.needs_semaphore_read
def test_a2a_chunked_guarded_clean_and_dropped(mesh4):
    from triton_dist_tpu.kernels.all_to_all import all_to_all_chunked

    x = _make((16, 8, 128), seed=9)
    splits = jnp.asarray(np.arange(16) % 7 + 1, jnp.int32)

    def run(plan):
        b = faults.building()
        inj = faults.injecting(plan) if plan else contextlib.nullcontext()
        with b, inj:
            fn = jax.jit(jax.shard_map(
                lambda xs, ss: all_to_all_chunked(xs, ss, "tp",
                                                  n_chunks=2),
                mesh=mesh4, in_specs=(P("tp"), P("tp")),
                out_specs=(P("tp"), P("tp"), P("tp")), check_vma=False))
            return fn(x, splits)

    out_c, sp_c, g_c = run(None)
    assert faults.decode(np.asarray(g_c).reshape(
        4, -1, faults.GUARD_WORDS)) == []
    _o, _s, g_f = run(faults.FaultPlan(faults.DroppedSignal(0)))
    trips = faults.decode(np.asarray(g_f).reshape(
        4, -1, faults.GUARD_WORDS))
    assert trips and {t.site_label for t in trips} == {"barrier"}


# ---------- degradation: guard-tripped fallback="xla" ----------


@pytest.mark.needs_semaphore_read
def test_ll_op_fallback_degrades_and_completes(mesh4):
    from triton_dist_tpu.runtime.symm_mem import SymmetricWorkspace

    ws = SymmetricWorkspace(mesh4)
    x = _make((4 * 8, 128), seed=12, scale=1.0)
    ref = np.asarray(jax.jit(jax.shard_map(
        lambda xs: jax.lax.all_gather(xs, "tp"), mesh=mesh4,
        in_specs=P("tp"), out_specs=P(None, "tp"), check_vma=False))(x))

    plan = faults.FaultPlan(faults.DroppedSignal(0, label="barrier"))
    with faults.building(), faults.injecting(plan):
        out = ll_all_gather_op(x, ws, 0, mesh4, fallback="xla",
                               name="deg")
    assert faults.is_degraded("low_latency_allgather")
    np.testing.assert_array_equal(np.asarray(out), ref)
    # degraded: later calls route straight to XLA, no guard build needed
    out2 = ll_all_gather_op(x, ws, 1, mesh4, fallback="xla", name="deg")
    np.testing.assert_array_equal(np.asarray(out2), ref)


@pytest.mark.needs_semaphore_read
def test_ll_op_without_fallback_raises(mesh4):
    from triton_dist_tpu.runtime.symm_mem import SymmetricWorkspace

    ws = SymmetricWorkspace(mesh4)
    x = _make((4 * 8, 128), seed=13, scale=1.0)
    plan = faults.FaultPlan(faults.DroppedSignal(2, label="barrier"))
    with faults.building(), faults.injecting(plan):
        with pytest.raises(faults.DeadlineExceeded):
            ll_all_gather_op(x, ws, 0, mesh4, name="raise")
    assert not faults.is_degraded("low_latency_allgather")


@pytest.mark.needs_semaphore_read
def test_ar_op_fallback_degrades(mesh4):
    x = _make((4, 16, 128), seed=14)
    ref = np.asarray(all_reduce_op(x, mesh4))
    plan = faults.FaultPlan(faults.DroppedSignal(1, label="credit"))
    from triton_dist_tpu.kernels.allreduce import AllReduceMethod

    with faults.building(), faults.injecting(plan):
        out = all_reduce_op(x, mesh4, method=AllReduceMethod.TwoShot,
                            fallback="xla")
    assert faults.is_degraded("allreduce")
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-6,
                               atol=1e-6)
    out2 = all_reduce_op(x, mesh4, method=AllReduceMethod.TwoShot,
                         fallback="xla")
    np.testing.assert_allclose(np.asarray(out2), ref, rtol=1e-6,
                               atol=1e-6)


# ---------- wire integrity units ----------


def test_wire_checksum_roundtrip_and_detect():
    fmt = wire.WireFormat("int8", block=64, checksum=True)
    x = _make((8, 256), seed=15, scale=1.0)
    w = wire.pack(x, fmt)
    assert w.shape[1] == wire.wire_cols(256, fmt)
    assert bool(np.asarray(wire.verify_rows(w, 256, fmt)).all())
    # checksum format decodes to the same values as its plain twin
    plain = wire.WireFormat("int8", block=64)
    np.testing.assert_array_equal(
        np.asarray(wire.unpack_checked(w, (256,), fmt, jnp.float32)),
        np.asarray(wire.roundtrip(x, plain)))
    with faults.injecting(faults.FaultPlan(
            faults.BitFlipScale(row=4, byte=2, bit=4))):
        wc = wire.pack(x, fmt)
    ok = np.asarray(wire.verify_rows(wc, 256, fmt))
    assert not ok[4] and ok.sum() == 7
    with pytest.raises(faults.WireIntegrityError) as ei:
        wire.unpack_checked(wc, (256,), fmt, jnp.float32)
    assert ei.value.rows == [4]
    # unpack (the default consume edge) also raises on concrete images
    with pytest.raises(faults.WireIntegrityError):
        wire.unpack(wc, (256,), fmt, jnp.float32)


def test_wire_flips_inject_once_per_plan():
    fmt = wire.WireFormat("fp8", checksum=True)
    x = _make((4, 128), seed=16, scale=1.0)
    with faults.injecting(faults.FaultPlan(
            faults.BitFlipPayload(row=0, byte=0, bit=0))):
        w1 = wire.pack(x, fmt)
        w2 = wire.pack(x, fmt)  # second encode passes clean
    assert not bool(np.asarray(wire.verify_rows(w1, 128, fmt)).all())
    assert bool(np.asarray(wire.verify_rows(w2, 128, fmt)).all())


def test_checksum_native_rejected():
    with pytest.raises(ValueError, match="checksum"):
        wire.WireFormat("native", checksum=True)


# ---------- verify: liveness under symbolic fault models ----------


def test_liveness_shipped_clean():
    assert verify.check_liveness(ns=(2,)) == []


def test_liveness_chunked_a2a_cells():
    from triton_dist_tpu.kernels.all_to_all import _a2a_chunked_protocol

    cells = verify.liveness_cells(_a2a_chunked_protocol, 4, q=2)
    assert cells and all(ok for _k, _p, ok in cells)
    # the chunked A2A is pure put/wait: every site is a delivery drop
    assert {k for k, _p, _ok in cells} == {verify.DROP_DELIVERY}


def test_liveness_covers_signal_sites_on_credit_ring():
    from triton_dist_tpu.verify import capture as cap
    from triton_dist_tpu.verify import engine, liveness
    from triton_dist_tpu.verify.registry import load_shipped

    spec = load_shipped()["reduce_scatter"]
    with cap.capturing(4) as c:
        spec.fn(4)
    progs = engine.concretize(c.ops, 4)
    kinds = {k for k, _p in liveness.fault_sites(progs)}
    # the credit grants are explicit signals: both fault models apply
    assert kinds == {verify.DROP_SIGNAL, verify.DROP_DELIVERY}
    cells = liveness.liveness_cells(spec.fn, 4)
    assert cells and all(ok for _k, _p, ok in cells)


def test_liveness_flags_slack_protocol():
    """Polarity: a protocol with a genuinely slack signal (nobody ever
    needs it) completes silently under its drop — the checker must say
    so, not vacuously pass."""
    from triton_dist_tpu.lang import shmem
    from triton_dist_tpu.verify import liveness

    def slack(n):
        me = verify.me()
        s = verify.sem("slack")
        # two grants, only one ever consumed: one is pure slack
        shmem.signal(s.at(), 1, shmem.SIGNAL_ADD, (me + 1) % n, "tp")
        shmem.signal(s.at(), 1, shmem.SIGNAL_ADD, (me + 1) % n, "tp")
        shmem.signal_wait_until(s.at(), shmem.CMP_GE, 1)

    cells = liveness.liveness_cells(slack, 2)
    assert any(not ok for _k, _p, ok in cells), (
        "a slack-signal drop must be reported as silent")


def test_run_faulted_drop_delivery_detected():
    from triton_dist_tpu.kernels.flash_prefill import _fp_protocol
    from triton_dist_tpu.verify import engine, liveness

    with verify.capturing(2) as c:
        _fp_protocol(2)
    progs = engine.concretize(c.ops, 2)
    sites = liveness.fault_sites(progs, rank=0)
    puts = [(k, p) for k, p in sites if k == verify.DROP_DELIVERY]
    assert puts
    ex = liveness.run_faulted(_fp_protocol, 2, *puts[0])
    assert any(f.klass in (engine.DEADLOCK, engine.RACE)
               for f in ex.findings)


# ---------- guard-polarity mutant (red/green corpus) ----------


@pytest.mark.needs_semaphore_read
def test_watchdog_mutant_polarity():
    assert chaos.watchdog_mutant_findings(2, impl="shipped") == []
    fs = chaos.watchdog_mutant_findings(2, impl="reset_poll")
    assert len(fs) == 1 and fs[0].klass == "guard-no-trip"


@pytest.mark.needs_semaphore_read
def test_guard_mutant_registered_in_corpus():
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(__file__), "_mutants.py")
    spec = importlib.util.spec_from_file_location("_tdt_mut_faults", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    muts = verify.mutants()
    assert "guard_reset_poll" in muts
    assert muts["guard_reset_poll"].expect == "guard-no-trip"
    fs = verify.verify_spec(muts["guard_reset_poll"])
    assert fs and all(f.klass == "guard-no-trip" for f in fs)


# ---------- chaos matrix (tier-1 subset; full matrix in dryrun) ----------


@pytest.mark.slow
def test_chaos_matrix_subset(mesh4):
    res = chaos.run_matrix(
        mesh4, protocols=("two_shot_all_reduce", "low_latency_allgather"),
        faults=("none", "dropped_signal", "bitflip_payload"))
    assert chaos.check_matrix(res) == []
    by = {(r.protocol, r.fault): r.outcome for r in res}
    assert by[("two_shot_all_reduce", "dropped_signal")] == "detected"
    assert by[("low_latency_allgather", "bitflip_payload")] == "detected"
    assert by[("two_shot_all_reduce", "none")] == "recovered"


def test_chaos_check_matrix_polarity():
    bad = [chaos.CellResult("p", "dropped_signal", "silent-wrong", "x"),
           chaos.CellResult("p", "none", "detected", "y")]
    probs = chaos.check_matrix(bad)
    # silent-wrong is out of the OK set; a clean cell that trips is
    # flagged by the polarity rule even though "detected" is OK per se
    assert len(probs) == 2
    assert any("silent-wrong" in p for p in probs)
    assert any("must be 'recovered'" in p for p in probs)


# ---------- serve degradation ladder ----------


def _tiny_engine(mesh1):
    from triton_dist_tpu.models import Engine, ModelConfig

    cfg = ModelConfig.tiny(max_positions=32)
    return Engine(cfg, mesh1, decode_mode="ar", max_len=32,
                  donate_cache=False)


@pytest.fixture(scope="module")
def mesh1():
    from triton_dist_tpu.runtime import make_mesh

    return make_mesh(mesh_shape=(1,), axis_names=("tp",))


def test_serve_transient_fault_retries_bitwise(mesh1):
    from triton_dist_tpu.serve import Scheduler

    eng = _tiny_engine(mesh1)
    rng = np.random.default_rng(20)
    prompts = [rng.integers(0, eng.cfg.vocab_size, k).tolist()
               for k in (5, 7)]

    def run(plan):
        sch = Scheduler(eng, slots=2, chunk=4, page=8,
                        retry_backoff_s=0.0005)
        reqs = [sch.submit(p, max_new_tokens=4) for p in prompts]
        with (faults.injecting(plan) if plan
              else contextlib.nullcontext()):
            sch.run()
        return sch, reqs

    sch_c, reqs_c = run(None)
    sch_f, reqs_f = run(faults.FaultPlan(
        faults.FailStep(at_step=1, times=1)))
    # one retry, no quarantine, tokens BIT-IDENTICAL to the clean run
    assert sch_f.metrics()["step_retries"] == 1
    assert sch_f.metrics()["quarantined"] == 0
    assert [r.out_tokens for r in reqs_f] == \
        [r.out_tokens for r in reqs_c]
    # the retry is attributable in the span timeline
    assert any(name == "step.retry"
               for name, _t0, _t1 in sch_f.timeline().host_spans)


def test_serve_persistent_fault_quarantines_poisoner(mesh1):
    from triton_dist_tpu.serve import Scheduler
    from triton_dist_tpu.serve.request import RequestState

    eng = _tiny_engine(mesh1)
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, eng.cfg.vocab_size, k).tolist()
               for k in (5, 7)]
    sch = Scheduler(eng, slots=2, chunk=4, page=8, max_step_retries=1,
                    retry_backoff_s=0.0005)
    reqs = [sch.submit(p, max_new_tokens=4) for p in prompts]
    plan = faults.FaultPlan(faults.FailStep(at_step=0, times=2))
    with faults.injecting(plan):
        sch.run()
    m = sch.metrics()
    assert m["quarantined"] == 1
    victim = sch.quarantined[0]
    # the most recently admitted request is the suspected poisoner
    assert victim is reqs[1]
    assert victim.state is RequestState.FAILED and victim.done
    assert victim.finish_reason.startswith("quarantined")
    # the survivor finished with the sequential-run tokens
    survivor = reqs[0]
    assert survivor.state is RequestState.FINISHED
    seq = np.asarray(eng.serve(np.asarray([prompts[0]], np.int32), 4,
                               slots=2, chunk=4, page=8))[0].tolist()
    assert survivor.out_tokens == seq
    # pool invariants hold after the quarantine path
    sch.pool.check()
    assert any(name.endswith("/quarantined")
               for name, _t0, _t1 in sch.timeline().host_spans)


def test_serve_programming_errors_stay_loud(mesh1):
    from triton_dist_tpu.serve import Scheduler

    eng = _tiny_engine(mesh1)
    sch = Scheduler(eng, slots=2, chunk=4, page=8)
    sch.submit([1, 2, 3], max_new_tokens=2)
    sch.worker.step = None  # simulate a real bug, not a FaultError
    with pytest.raises(TypeError):
        sch.step()


# ---------- bench --faults arm (tiny-shape smoke) ----------


@pytest.mark.slow
def test_bench_faults_arm_smoke(mesh1):
    import sys

    sys.path.insert(0, ".")
    import bench

    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (64, 256)) * 0.02, jnp.bfloat16)
    w1 = jnp.asarray(np.random.default_rng(1).standard_normal(
        (256, 512)) * 0.02, jnp.bfloat16)
    # ceil relaxed: sub-ms chains are timer noise; the arm's mechanics
    # (guarded chain runs, clean-chain trip audit == 0) are the test
    frac, g_ms, un_ms, trips = bench.bench_faults_overhead(
        mesh1, x, w1, k_hi=9, pairs=2, out_cols=256, ceil=10.0)
    assert trips == 0 and g_ms > 0 and un_ms > 0
    r = {"metric": "m", "value": 1.0, "unit": "ms", "vs_baseline": 1.0,
         "faults_overhead_frac": float(frac), "faults_guard_trips": 0}
    assert bench.check_result(r) == []
    r.pop("faults_guard_trips")
    assert any("travel together" in p for p in bench.check_result(r))


# ---------- guard trips are trace-attributable ----------


@pytest.mark.slow
def test_guard_trip_lands_in_trace(mesh4):
    from triton_dist_tpu import trace
    from triton_dist_tpu.kernels.all_to_all import all_to_all_chunked
    from triton_dist_tpu.trace.attribution import guard_trips

    x = _make((16, 8, 128), seed=30)
    splits = jnp.ones((16,), jnp.int32)
    plan = faults.FaultPlan(faults.DroppedSignal(3, label="barrier"))
    with trace.building(cap=128), faults.building(), \
            faults.injecting(plan):
        fn = jax.jit(jax.shard_map(
            lambda xs, ss: all_to_all_chunked(xs, ss, "tp", n_chunks=2),
            mesh=mesh4, in_specs=(P("tp"), P("tp")),
            out_specs=(P("tp"), P("tp"), P("tp"), P("tp")),
            check_vma=False))
        _o, _s, tbuf, gbuf = fn(x, splits)
    tl = trace.assemble({"a2a": np.asarray(tbuf).reshape(
        4, -1, trace.RECORD_WORDS)})
    rows = guard_trips(tl)
    trips = faults.decode(np.asarray(gbuf).reshape(
        4, -1, faults.GUARD_WORDS))
    assert trips and rows, "trips must land in BOTH planes"
    assert len(rows) == len(trips)
    assert {r["site"] for r in rows} == {"barrier"}
    assert sorted(r["rank"] for r in rows) == \
        sorted(t.rank for t in trips)
