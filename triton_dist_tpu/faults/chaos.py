"""The chaos matrix: every (fault class x protocol) cell must end in
`detected` or `recovered` — never a hang, never a silent wrong answer.

Each cell builds a FRESH guarded program (`guard.building()` +
`faults.injecting(plan)`), runs it on the provided mesh, and
classifies:

  detected      guard rows present (a watchdog or integrity check
                fired) — the host raises DeadlineExceeded /
                WireIntegrityError from them;
  recovered     no guard rows AND the output matches the fault-free
                reference (delay/stall faults perturb timing only);
  n/a           the fault class has no injection point on this
                protocol (bit flips need a wire image);
  silent-wrong  no guard rows but the output DIFFERS from the
                reference — the exact failure class this plane exists
                to kill. `check_matrix` fails on it.

Hangs are structurally impossible on the test rig (the lockstep
interpreter never blocks; on hardware the watchdog deadline bounds
every guarded wait), so a cell that returns at all has either detected
or completed.

The same module carries the guard-polarity corpus runner: the
`guard_reset_poll` mutant (tests/_mutants.py) swaps in a watchdog whose
poll budget resets on every re-read — it never trips on a real lost
signal — and `watchdog_mutant_findings` flags it with the
`guard-no-trip` class (red/green polarity, the verify-mutant
discipline applied to the guards themselves).

Wired into `__graft_entry__`'s dryrun chaos plane and
tests/test_faults.py.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import List, Optional

import numpy as np

from triton_dist_tpu.faults import guard as _guard
from triton_dist_tpu.faults import plan as _fplan
from triton_dist_tpu.faults.errors import FaultError
from triton_dist_tpu.faults.plan import (
    BitFlipPayload,
    BitFlipScale,
    DelayedSend,
    DroppedSignal,
    FailStep,
    FaultPlan,
    StalledRank,
)

PROTOCOLS = ("two_shot_all_reduce", "all_to_all_chunked",
             "low_latency_allgather", "flash_prefill", "serve_step",
             "serve_spec", "serve_disagg")
FAULTS = ("none", "delayed_send", "stalled_rank", "dropped_signal",
          "bitflip_payload", "bitflip_scale")
OK_OUTCOMES = ("detected", "recovered", "n/a")

# interpreter-churn delay scales: big enough to skew, small enough that
# an n<=8 lockstep run stays fast
_DELAY_NS = 60_000
_STALL_NS = 1_500_000


@dataclasses.dataclass(frozen=True)
class CellResult:
    protocol: str
    fault: str
    outcome: str   # detected | recovered | n/a | silent-wrong
    detail: str = ""

    def __str__(self):
        d = f" ({self.detail})" if self.detail else ""
        return f"{self.protocol:<24} x {self.fault:<16} -> " \
               f"{self.outcome}{d}"


def fault_plan(fault: str, rank: int = 1) -> Optional[FaultPlan]:
    if fault == "none":
        return None
    if fault == "delayed_send":
        return FaultPlan(DelayedSend(rank, _DELAY_NS))
    if fault == "stalled_rank":
        return FaultPlan(StalledRank(rank, _STALL_NS))
    if fault == "dropped_signal":
        return FaultPlan(DroppedSignal(rank))
    if fault == "bitflip_payload":
        return FaultPlan(BitFlipPayload(row=1, byte=5, bit=3))
    if fault == "bitflip_scale":
        return FaultPlan(BitFlipScale(row=0, byte=1, bit=6))
    raise ValueError(f"unknown fault {fault!r} (one of {FAULTS})")


def _contexts(plan):
    inj = _fplan.injecting(plan) if plan is not None \
        else contextlib.nullcontext()
    return _guard.building(), inj


def _verdict(protocol, fault, trips, out, ref,
             exact: bool = True) -> CellResult:
    if trips:
        sites = sorted({t.site_label for t in trips})
        return CellResult(protocol, fault, "detected",
                          f"{len(trips)} trip(s) at {sites}")
    out = np.asarray(out)
    ref = np.asarray(ref)
    match = (np.array_equal(out, ref) if exact
             else np.allclose(out, ref, rtol=2e-5, atol=2e-5))
    if match:
        return CellResult(protocol, fault, "recovered")
    return CellResult(protocol, fault, "silent-wrong",
                      "output differs from the fault-free reference "
                      "with no guard row")


# -- per-protocol cell runners ------------------------------------------------


def _run_two_shot_ar(mesh, axis, fault: str) -> CellResult:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from triton_dist_tpu.kernels.allreduce import two_shot_all_reduce
    from triton_dist_tpu.wire.codec import WireFormat

    n = int(mesh.shape[axis])
    wirey = fault in ("bitflip_payload", "bitflip_scale")
    # bit-flip cells ride the checksummed wire (the integrity surface);
    # the rest run the native payload
    fmt = WireFormat("fp8", checksum=True) if wirey else None
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((n, 8 * n, 128)) * 0.1,
                    jnp.float32)

    def run(plan, guarded):
        b, inj = _contexts(plan)
        ctx = b if guarded else contextlib.nullcontext()
        with ctx, inj:
            fn = jax.jit(jax.shard_map(
                lambda xs: two_shot_all_reduce(xs[0], axis,
                                               wire_format=fmt),
                mesh=mesh, in_specs=P(axis),
                out_specs=(P(axis), P(axis)) if guarded else P(axis),
                check_vma=False))
            return fn(x)

    ref = run(None, guarded=False)
    out, g = run(fault_plan(fault), guarded=True)
    return _verdict("two_shot_all_reduce", fault,
                    _guard.decode(np.asarray(g)), out, ref)


def _run_a2a_chunked(mesh, axis, fault: str) -> CellResult:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from triton_dist_tpu.kernels.all_to_all import all_to_all_chunked

    if fault in ("bitflip_payload", "bitflip_scale"):
        return CellResult("all_to_all_chunked", fault, "n/a",
                          "native payload — no wire image to flip")
    n = int(mesh.shape[axis])
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.standard_normal((n * n, 8, 128)) * 0.1,
                    jnp.float32)
    splits = jnp.asarray(rng.integers(1, 8, (n * n,)), jnp.int32)

    def run(plan, guarded):
        b, inj = _contexts(plan)
        ctx = b if guarded else contextlib.nullcontext()
        with ctx, inj:
            fn = jax.jit(jax.shard_map(
                lambda xs, ss: all_to_all_chunked(xs, ss, axis,
                                                  n_chunks=2),
                mesh=mesh, in_specs=(P(axis), P(axis)),
                out_specs=(P(axis), P(axis))
                + ((P(axis),) if guarded else ()),
                check_vma=False))
            return fn(x, splits)

    ref = run(None, guarded=False)
    res = run(fault_plan(fault), guarded=True)
    out, _sp, g = res
    return _verdict("all_to_all_chunked", fault,
                    _guard.decode(np.asarray(g).reshape(
                        n, -1, _guard.GUARD_WORDS)), out, ref[0])


def _run_ll_ag(mesh, axis, fault: str) -> CellResult:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from triton_dist_tpu.kernels.low_latency_allgather import (
        create_ll_ag_buffer,
        ll_all_gather,
    )
    from triton_dist_tpu.wire.codec import WireFormat

    n = int(mesh.shape[axis])
    wirey = fault in ("bitflip_payload", "bitflip_scale")
    fmt = WireFormat("int8", checksum=True) if wirey else None
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((n * 8, 128)), jnp.float32)

    def run(plan, guarded):
        b, inj = _contexts(plan)
        ctx = b if guarded else contextlib.nullcontext()
        with ctx, inj:
            def per_dev(xs):
                buf = create_ll_ag_buffer(xs.shape, xs.dtype, n,
                                          wire_format=fmt)
                return ll_all_gather(xs, buf, 0, axis, wire_format=fmt)

            fn = jax.jit(jax.shard_map(
                per_dev, mesh=mesh, in_specs=P(axis),
                out_specs=(P(None, axis), P(axis))
                + ((P(axis),) if guarded else ()),
                check_vma=False))
            return fn(x)

    ref = run(None, guarded=False)[0]
    res = run(fault_plan(fault), guarded=True)
    out, _buf, g = res
    return _verdict("low_latency_allgather", fault,
                    _guard.decode(np.asarray(g).reshape(
                        n, -1, _guard.GUARD_WORDS)), out, ref)


def _run_flash_prefill(mesh, axis, fault: str) -> CellResult:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from triton_dist_tpu.kernels.flash_prefill import sp_flash_prefill

    if fault in ("bitflip_payload", "bitflip_scale"):
        return CellResult("flash_prefill", fault, "n/a",
                          "native payload — no wire image to flip")
    n = int(mesh.shape[axis])
    rng = np.random.default_rng(10)
    q = jnp.asarray(rng.standard_normal((1, n * 8, 2, 32)), jnp.float32)
    kv = jnp.asarray(rng.standard_normal((1, n * 8, 1, 32)), jnp.float32)

    def run(plan, guarded):
        b, inj = _contexts(plan)
        ctx = b if guarded else contextlib.nullcontext()
        with ctx, inj:
            fn = jax.jit(jax.shard_map(
                lambda q, k, v: sp_flash_prefill(q, k, v, axis, block=8),
                mesh=mesh,
                in_specs=(P(None, axis), P(None, axis), P(None, axis)),
                out_specs=((P(None, axis), P(axis)) if guarded
                           else P(None, axis)),
                check_vma=False))
            res = fn(q, kv, kv)
            return res if guarded else (res,)

    (ref,) = run(None, guarded=False)
    out, g = run(fault_plan(fault), guarded=True)
    return _verdict("flash_prefill", fault,
                    _guard.decode(np.asarray(g).reshape(
                        n, -1, _guard.GUARD_WORDS)), out, ref)


def _run_serve_step(mesh, fault: str, engine=None) -> CellResult:
    """The serve-plane cell: the chaos vector is a host-level FailStep
    (the device step itself is world-local here; distributed-step
    failures arrive as the same FaultError class via the guarded
    collectives). Outcomes: a transient failure retries and recovers; a
    persistent one quarantines the poisoner while the survivors finish
    — both loud in metrics() and the span timeline."""
    from triton_dist_tpu.serve import Scheduler

    if engine is None:
        return CellResult("serve_step", fault, "n/a",
                          "no engine provided")
    persistent = fault in ("dropped_signal", "stalled_rank")
    if fault == "none":
        plan = None
    else:
        err = "integrity" if fault.startswith("bitflip") else "deadline"
        times = 4 if persistent else 1
        plan = FaultPlan(FailStep(at_step=2, times=times, error=err))
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, engine.cfg.vocab_size, k).tolist()
               for k in (5, 7)]

    sch = Scheduler(engine, slots=2, chunk=4, page=8,
                    max_step_retries=2, retry_backoff_s=0.0005)
    reqs = [sch.submit(p, max_new_tokens=4) for p in prompts]
    with (contextlib.nullcontext() if plan is None
          else _fplan.injecting(plan)):
        sch.run()
    m = sch.metrics()
    survivors_ok = all(r.done for r in reqs)
    if not survivors_ok:
        return CellResult("serve_step", fault, "silent-wrong",
                          "scheduler drained with live requests")
    if plan is None:
        outcome = ("recovered" if m["quarantined"] == 0
                   and m["step_retries"] == 0 else "silent-wrong")
        return CellResult("serve_step", fault, outcome, "clean run")
    if persistent:
        ok = m["quarantined"] == 1 and m["step_retries"] >= 3
        return CellResult(
            "serve_step", fault,
            "detected" if ok else "silent-wrong",
            f"quarantined={m['quarantined']} "
            f"retries={m['step_retries']}")
    ok = m["quarantined"] == 0 and m["step_retries"] >= 1
    return CellResult(
        "serve_step", fault, "recovered" if ok else "silent-wrong",
        f"retries={m['step_retries']}")


def _run_serve_spec(mesh, fault: str, engine=None) -> CellResult:
    """The spec/prefix cell (ISSUE 14): a FailStep lands DURING a
    spec-verify step — the retry ladder (or quarantine) must absorb it
    WITHOUT double-emitting accepted tokens (the draft proposer is
    deterministic in the unchanged history, so a retried verify step
    rebuilds the identical row; emissions only happen once, after the
    successful attempt). Every token that did stream is re-checked
    against the fault-free plain-decode reference — bitwise. The
    clean column additionally pins the pool-pressure polarity pair:
    reclaim must pick an UNSHARED victim under pressure, and forcing
    the eviction of a refcount>1 shared block must be REFUSED
    (assert)."""
    from triton_dist_tpu.serve import Scheduler
    from triton_dist_tpu.spec import SpecConfig

    if engine is None:
        return CellResult("serve_spec", fault, "n/a",
                          "no engine provided")

    class _CycleDraft:
        # always proposes (repeat the last token): EVERY decode step
        # is a verify step, so the injected fault provably lands on
        # one. Deterministic in the history, like the contract demands.
        def propose(self, history, k):
            return [int(history[-1])] * k

    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, engine.cfg.vocab_size, 9).tolist()
               for _ in range(2)]
    geo = dict(slots=2, chunk=6, page=8)
    spec = SpecConfig(k=3, draft=_CycleDraft())

    ref = Scheduler(engine, **geo)
    ref_reqs = [ref.submit(p, max_new_tokens=8) for p in prompts]
    ref.run()

    persistent = fault in ("dropped_signal", "stalled_rank")
    if fault == "none":
        plan = None
    else:
        err = "integrity" if fault.startswith("bitflip") else "deadline"
        times = 4 if persistent else 1
        # at_step 3+: past both prefills — the failing step is a
        # decode/verify step
        plan = FaultPlan(FailStep(at_step=3, times=times, error=err))

    sch = Scheduler(engine, spec=spec, max_step_retries=2,
                    retry_backoff_s=0.0005, **geo)
    reqs = [sch.submit(p, max_new_tokens=8) for p in prompts]
    with (contextlib.nullcontext() if plan is None
          else _fplan.injecting(plan)):
        sch.run()
    m = sch.metrics()
    # the double-emission check IS the bitwise prefix check: a replayed
    # verify step would duplicate accepted tokens in the stream
    for r, rr in zip(reqs, ref_reqs):
        if r.out_tokens != rr.out_tokens[:len(r.out_tokens)]:
            return CellResult("serve_spec", fault, "silent-wrong",
                              f"req{r.request_id} tokens diverged "
                              "(double emission?)")
    if not all(r.done for r in reqs):
        return CellResult("serve_spec", fault, "silent-wrong",
                          "scheduler drained with live requests")
    if plan is None:
        ok = (m["quarantined"] == 0 and m["step_retries"] == 0
              and m["spec_proposed"] > 0
              and all(r.out_tokens == rr.out_tokens
                      for r, rr in zip(reqs, ref_reqs)))
        if ok:
            ok = _shared_page_polarity(engine)
        return CellResult(
            "serve_spec", fault, "recovered" if ok else "silent-wrong",
            f"clean run (proposed={m['spec_proposed']}, shared-page "
            "polarity checked)")
    if persistent:
        ok = m["quarantined"] == 1 and m["step_retries"] >= 3
        return CellResult(
            "serve_spec", fault,
            "detected" if ok else "silent-wrong",
            f"quarantined={m['quarantined']} "
            f"retries={m['step_retries']}")
    ok = m["quarantined"] == 0 and m["step_retries"] >= 1
    return CellResult(
        "serve_spec", fault, "recovered" if ok else "silent-wrong",
        f"retries={m['step_retries']}")


def _shared_page_polarity(engine) -> bool:
    """Both polarities of the refcount>1 eviction rule on a
    pressure-sized pool: (a) reclaim under pool pressure frees ONLY
    unshared cached blocks (live readers keep their pages, allocator
    invariants hold); (b) force-dropping a node whose pages a live
    slot still reads raises AssertionError (the refusal)."""
    from triton_dist_tpu.serve import Scheduler

    rng = np.random.default_rng(14)
    v = engine.cfg.vocab_size
    shared_prompt = rng.integers(0, v, 9).tolist()
    other = rng.integers(0, v, 9).tolist()
    sch = Scheduler(engine, slots=2, chunk=6, page=8, total_pages=6,
                    prefix_cache=True, prefix_block=8)
    # donor populates the cache, then finishes (cache = only holder)
    a = sch.submit(shared_prompt, max_new_tokens=2)
    b = sch.submit(other, max_new_tokens=2)
    sch.run()
    if sch.prefix.n_blocks() < 2:
        return False
    # reader shares the donor's block; its node is now ref>1
    c = sch.submit(shared_prompt, max_new_tokens=2)
    sch.step()
    if c.prefix_len == 0:
        return False
    shared_node = next(
        nd for nd in sch.prefix._iter_leaves()
        if not sch.prefix._droppable(nd))
    # polarity (b): forced eviction of the shared block is REFUSED
    try:
        sch.prefix._drop(shared_node)
        return False  # the refusal did not fire
    except AssertionError:
        pass
    # polarity (a): pressure reclaim picks an unshared victim and the
    # shared node survives
    before = sch.prefix.n_blocks()
    freed = sch.prefix.reclaim(6)
    ok = (freed > 0 and sch.prefix.n_blocks() < before
          and not sch.prefix._droppable(shared_node))
    sch.run()
    sch.pool.check()
    sch.prefix.check()
    return ok and all(r.done for r in (a, b, c))


def _run_serve_disagg(mesh, fault: str, engine=None) -> CellResult:
    """The DCN-hop cell (ISSUE 18): the chaos vector is the MIGRATION
    CHANNEL between a prefill slice and a decode slice — dropped
    records (the DCN packet-loss analog) and corrupted page images
    (the bitflip analog), one-shot (transient) or persistent. The
    contract is the usual polarity: transients RECOVER through the
    resend/nack ladder with tokens bitwise the fault-free single-slice
    reference; persistent faults exhaust the retry budget and FAIL the
    request loudly (detected). Any token that did stream must be a
    bitwise prefix of the reference — silent-wrong is the only losing
    outcome."""
    from triton_dist_tpu.serve import Scheduler
    from triton_dist_tpu.xslice import DisaggPair

    if engine is None:
        return CellResult("serve_disagg", fault, "n/a",
                          "no engine provided")
    rng = np.random.default_rng(17)
    prompts = [rng.integers(0, engine.cfg.vocab_size, k).tolist()
               for k in (5, 9)]
    geo = dict(slots=2, chunk=4, page=8)

    ref = Scheduler(engine, **geo)
    ref_reqs = [ref.submit(p, max_new_tokens=4) for p in prompts]
    ref.run()

    pair = DisaggPair(
        engine,
        prefill_kw=dict(max_migration_retries=2,
                        migration_resend_after=2, **geo),
        decode_kw=dict(**geo))
    ch = pair.channel
    persistent = fault in ("dropped_signal", "stalled_rank",
                           "bitflip_scale")
    if fault in ("delayed_send",):
        ch.drop_next = 1            # one lost record -> resend ladder
    elif fault in ("dropped_signal", "stalled_rank"):
        ch.drop_all = True          # the hop is down
    elif fault == "bitflip_payload":
        ch.corrupt_next = 1         # one corrupted image -> nack/resend
    elif fault == "bitflip_scale":
        ch.corrupt_all = True       # every image corrupt

    reqs = [pair.submit(p, max_new_tokens=4) for p in prompts]
    pair.run()
    pm = pair.prefill.metrics()
    dm = pair.decode.metrics()
    # universal gate: whatever streamed must be a reference prefix
    for r, rr in zip(reqs, ref_reqs):
        if r.out_tokens != rr.out_tokens[:len(r.out_tokens)]:
            return CellResult("serve_disagg", fault, "silent-wrong",
                              f"req{r.request_id} tokens diverged")
    if not all(r.done for r in reqs):
        return CellResult("serve_disagg", fault, "silent-wrong",
                          "pair drained with live requests")
    if fault == "none":
        ok = (all(r.out_tokens == rr.out_tokens
                  for r, rr in zip(reqs, ref_reqs))
              and pm["migrations_failed"] == 0
              and dm["migrations_rejected"] == 0)
        return CellResult("serve_disagg", fault,
                          "recovered" if ok else "silent-wrong",
                          f"clean run (out={pm['migrations_out']} "
                          f"in={dm['migrations_in']})")
    if persistent:
        # the hop never heals: the migrated requests must FAIL loudly
        # after the retry budget — detected, not silent
        failed = [r for r in reqs if r.state.value == "failed"]
        ok = (pm["migrations_failed"] >= 1 and len(failed) >= 1
              and pm["migrations_resent"] >= 2)
        return CellResult(
            "serve_disagg", fault, "detected" if ok else "silent-wrong",
            f"failed={pm['migrations_failed']} "
            f"resent={pm['migrations_resent']} "
            f"rejected={dm['migrations_rejected']}")
    # transient: the ladder must absorb it and finish bitwise
    ok = (all(r.out_tokens == rr.out_tokens
              for r, rr in zip(reqs, ref_reqs))
          and pm["migrations_failed"] == 0)
    if fault == "delayed_send":
        ok = ok and pm["migrations_resent"] >= 1 and ch.n_dropped >= 1
    elif fault == "bitflip_payload":
        ok = ok and dm["migrations_rejected"] >= 1 \
            and pm["migrations_nacked"] >= 1
    return CellResult(
        "serve_disagg", fault, "recovered" if ok else "silent-wrong",
        f"resent={pm['migrations_resent']} "
        f"rejected={dm['migrations_rejected']}")


# -- the matrix ---------------------------------------------------------------


def run_matrix(mesh, axis: str = "tp", protocols=None, faults=None,
               serve_engine=None) -> List[CellResult]:
    """Run every requested (protocol x fault) cell on `mesh`. Cells
    whose detection surfaces raised (DeadlineExceeded /
    WireIntegrityError from an op wrapper) classify as detected."""
    runners = {
        "two_shot_all_reduce": lambda f: _run_two_shot_ar(mesh, axis, f),
        "all_to_all_chunked": lambda f: _run_a2a_chunked(mesh, axis, f),
        "low_latency_allgather": lambda f: _run_ll_ag(mesh, axis, f),
        "flash_prefill": lambda f: _run_flash_prefill(mesh, axis, f),
        "serve_step": lambda f: _run_serve_step(mesh, f,
                                                engine=serve_engine),
        "serve_spec": lambda f: _run_serve_spec(
            mesh, f, engine=serve_engine),
        "serve_disagg": lambda f: _run_serve_disagg(
            mesh, f, engine=serve_engine),
    }
    out: List[CellResult] = []
    for p in (protocols or PROTOCOLS):
        for f in (faults or FAULTS):
            try:
                out.append(runners[p](f))
            except FaultError as e:
                out.append(CellResult(p, f, "detected",
                                      f"raised {type(e).__name__}"))
    return out


def check_matrix(results: List[CellResult]) -> List[str]:
    """Problem strings for cells outside the acceptable outcomes, plus
    polarity: the fault-free column must be `recovered` (a guard that
    trips without a fault is as broken as one that never trips)."""
    problems = []
    for r in results:
        if r.outcome not in OK_OUTCOMES:
            problems.append(str(r))
        if r.fault == "none" and r.outcome != "recovered":
            problems.append(f"{r} — clean cell must be 'recovered'")
    return problems


# -- guard-polarity mutant corpus ---------------------------------------------


def _ll_dropped_barrier_trips(n: int, impl: str):
    """Run the LL-AG dropped-barrier cell under the named watchdog
    implementation; return the decoded trips."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from triton_dist_tpu.kernels.low_latency_allgather import (
        create_ll_ag_buffer,
        ll_all_gather,
    )
    from triton_dist_tpu.runtime import make_mesh

    if len(jax.devices()) < n:
        raise RuntimeError(
            f"guard-polarity mutant needs an n={n} CPU mesh; run under "
            "--xla_force_host_platform_device_count (tests/conftest.py "
            "or scripts/verify_kernels.py set it up)")
    mesh = make_mesh(mesh_shape=(n,), axis_names=("tp",))
    x = jnp.asarray(
        np.random.default_rng(3).standard_normal((n * 8, 128)),
        jnp.float32)
    plan = FaultPlan(DroppedSignal(0, label="barrier"))
    with _guard.building(), _fplan.injecting(plan), \
            _guard._watchdog_override(impl):
        fn = jax.jit(jax.shard_map(
            lambda xs: ll_all_gather(
                xs, create_ll_ag_buffer(xs.shape, xs.dtype, n), 0, "tp"),
            mesh=mesh, in_specs=P("tp"),
            out_specs=(P(None, "tp"), P("tp"), P("tp")),
            check_vma=False))
        _out, _buf, g = fn(x)
    return _guard.decode(np.asarray(g).reshape(n, -1,
                                               _guard.GUARD_WORDS))


def watchdog_mutant_findings(n: int = 2, impl: str = "reset_poll"):
    """Registry runner for the guard-polarity mutant corpus
    (tests/_mutants.py): a finding of class `guard-no-trip` iff the
    named watchdog implementation FAILS to trip on a real dropped
    barrier signal. The shipped watchdog must trip (sanity-checked
    first — an inert detection harness would vacuously 'flag' every
    mutant)."""
    from triton_dist_tpu.verify.engine import GUARD, Finding

    shipped = _ll_dropped_barrier_trips(n, "shipped")
    if not shipped:
        raise RuntimeError(
            "chaos harness inert: the SHIPPED watchdog did not trip on "
            "a dropped barrier signal — mutant polarity is unfalsifiable")
    trips = _ll_dropped_barrier_trips(n, impl)
    if trips:
        return []  # watchdog tripped: not the seeded bug
    return [Finding(
        GUARD,
        f"watchdog impl {impl!r} never trips on a real dropped signal "
        "(its poll budget resets on every re-read) — the lost message "
        "degrades to a silent wrong answer")]
