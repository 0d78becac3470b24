"""Megakernel task-graph + scheduler tests (ref test model:
mega_triton_kernel scheduling is exercised through its op tests; here the
planner is a library with the native/C++ and Python paths cross-checked).
"""

import numpy as np
import pytest

from triton_dist_tpu.mega import _native
from triton_dist_tpu.mega.core import Graph
from triton_dist_tpu.mega.scheduler import (
    Schedule,
    after_vectors,
    monotone_watermarks,
    predicted_stalls,
    prefetch_specs,
    schedule_graph,
    validate_schedule,
)


def diamond_graph():
    """a -> (b, c) -> d over four buffers."""
    g = Graph(batch=1)
    x = g.buffer(128, "x", pinned=True)
    b1 = g.buffer(128, "b1")
    b2 = g.buffer(128, "b2")
    out = g.buffer(128, "out", pinned=True)
    g.add_task("op", ("op", 128), [0], reads=[x], writes=[b1], tag="a")
    g.add_task("op", ("op", 128), [1], reads=[b1], writes=[b2], tag="b")
    g.add_task("op2", ("op2", 128), [2], reads=[b1], writes=[out], tag="c")
    g.add_task("op", ("op", 128), [3], reads=[b2, out], writes=[out],
               tag="d")
    return g


def chain_graph(n=12):
    g = Graph(batch=1)
    bufs = [g.buffer(128, "in", pinned=True)]
    for i in range(n):
        bufs.append(g.buffer(128, f"t{i}"))
        g.add_task("op", ("op", 128), [i], reads=[bufs[-2]],
                   writes=[bufs[-1]])
    return g


@pytest.fixture(params=["native", "python"])
def backend(request):
    if request.param == "native" and _native.load() is None:
        pytest.skip("TDT_NO_NATIVE=1: Python scheduler asked for")
    return request.param == "native"


def test_schedule_topological_and_valid(backend):
    g = diamond_graph()
    s = schedule_graph(g, num_cores=1, use_native=backend)
    validate_schedule(g, s)
    assert s.native == backend
    assert s.order[0] == 0 and s.order[-1] == 3  # a first, d last
    assert (s.watermarks == 0).all()  # single core: in-order covers deps


def test_schedule_two_cores_watermarks(backend):
    g = diamond_graph()
    s = schedule_graph(g, num_cores=2, strategy="round_robin",
                       use_native=backend)
    validate_schedule(g, s)
    # some dep must cross cores in a 2-core round robin of a diamond
    crossing = [(a, b) for a, b in g.edges if s.core[a] != s.core[b]]
    assert crossing
    for a, b in crossing:
        assert s.watermarks[b, s.core[a]] >= s.pos[a] + 1


def test_blocked_strategy_deps_point_backward(backend):
    """The interpret-safe layout: cross-core deps only target earlier
    cores (core-major sequential execution then satisfies every wait)."""
    g = chain_graph(10)
    s = schedule_graph(g, num_cores=2, strategy="blocked",
                       use_native=backend)
    validate_schedule(g, s)
    for a, b in g.edges:
        assert s.core[a] <= s.core[b]


def test_slot_reuse(backend):
    g = chain_graph(12)
    s = schedule_graph(g, num_cores=1, use_native=backend)
    validate_schedule(g, s)
    # 13 buffers, but a chain only needs ~2 non-pinned slots + 1 pinned
    assert s.n_slots <= 4
    # pinned buffer keeps a dedicated slot
    assert (s.buf_slot == s.buf_slot[0]).sum() == 1


def test_native_and_python_agree():
    if _native.load() is None:
        pytest.skip("TDT_NO_NATIVE=1: Python scheduler asked for")
    g = diamond_graph()
    a = schedule_graph(g, num_cores=2, strategy="blocked", use_native=True)
    b = schedule_graph(g, num_cores=2, strategy="blocked", use_native=False)
    np.testing.assert_array_equal(a.core, b.core)
    np.testing.assert_array_equal(a.pos, b.pos)
    np.testing.assert_array_equal(a.watermarks, b.watermarks)
    np.testing.assert_array_equal(a.buf_slot, b.buf_slot)


def two_chains_graph(n=6):
    """Two fully independent chains — under 2 cores these run
    CONCURRENTLY, so their buffers must never share workspace slots."""
    g = Graph(batch=1)
    outs = []
    for c in range(2):
        bufs = [g.buffer(128, f"in{c}", pinned=True)]
        for i in range(n):
            bufs.append(g.buffer(128, f"c{c}t{i}"))
            g.add_task("op", ("op", 128), [i], reads=[bufs[-2]],
                       writes=[bufs[-1]])
        outs.append(bufs)
    return g, outs


def test_monotone_watermarks_and_after_vectors():
    g = diamond_graph()
    s = schedule_graph(g, num_cores=2, strategy="round_robin",
                       use_native=False)
    wm = monotone_watermarks(s)
    for q in s.queues:
        run = np.zeros(s.num_cores, np.int64)
        for t in q:
            run = np.maximum(run, s.watermarks[t])
            assert (wm[t] == run).all()
    A = after_vectors(s, wm)
    # same-core successor starts after its predecessor completes
    for q in s.queues:
        for a, b in zip(q, q[1:]):
            assert A[a][s.core[b]] <= s.pos[b]
    # every dependency edge is covered by the happens-before closure
    for a, b in g.edges:
        assert s.pos[b] >= A[a][s.core[b]]


def test_multicore_independent_chains_never_share_slots():
    g, outs = two_chains_graph()
    s = schedule_graph(g, num_cores=2, strategy="least_loaded",
                       use_native=False)
    validate_schedule(g, s)
    if any(s.core[t] != s.core[0] for t in range(len(g.tasks))):
        # chains landed on different cores: their intermediate buffers
        # are concurrently live — slots must be disjoint between chains
        slots0 = {int(s.buf_slot[b.id]) for b in outs[0][1:]}
        slots1 = {int(s.buf_slot[b.id]) for b in outs[1][1:]}
        # only assert disjointness when the chains really are on
        # different cores end to end
        cores0 = {int(s.core[t.id]) for t in g.tasks[:6]}
        cores1 = {int(s.core[t.id]) for t in g.tasks[6:]}
        if cores0.isdisjoint(cores1):
            assert slots0.isdisjoint(slots1)


def test_multicore_slot_validation_catches_concurrent_sharing():
    """Hand-forcing two concurrently-live buffers into one slot must trip
    the HB validator (the single-core interval check would PASS this —
    the core-major order hides the concurrency)."""
    g, outs = two_chains_graph(3)
    s = schedule_graph(g, num_cores=2, strategy="least_loaded",
                       use_native=False)
    cores0 = {int(s.core[t.id]) for t in g.tasks[:3]}
    cores1 = {int(s.core[t.id]) for t in g.tasks[3:]}
    if not cores0.isdisjoint(cores1):
        pytest.skip("scheduler interleaved the chains")
    bad = np.array(s.buf_slot, copy=True)
    # alias one mid-chain buffer from each chain
    bad[outs[1][2].id] = bad[outs[0][2].id]
    s_bad = Schedule(core=s.core, pos=s.pos, watermarks=s.watermarks,
                     order=s.order, queues=s.queues, buf_slot=bad,
                     n_slots=s.n_slots, native=False)
    with pytest.raises(AssertionError):
        validate_schedule(g, s_bad)


def mlp_chain_graph(layers=3):
    """A realistic matmul-bearing graph (norm -> gate_up -> silu -> down
    -> add, repeated) for the weight-streaming plan invariants."""
    from triton_dist_tpu.mega.builder import ModelBuilder

    mb = ModelBuilder(batch=2, world=1)
    x = mb.buffer(128, "x", pinned=True)
    h = x
    for layer in range(layers):
        h1 = mb.make_rms_norm(layer, h, 128, 1e-6)
        gu = mb.make_matmul("w_gate_up", layer, h1, 128, 512)
        act = mb.make_silu_mul(gu, 256)
        dn = mb.make_matmul("w_down", layer, act, 256, 128)
        h = mb.make_add(dn, h, 128)
    mb.graph.pinned[h.id] = True
    return mb.graph


@pytest.mark.parametrize("depth", [1, 2, 3])
@pytest.mark.parametrize("num_cores", [1, 2])
def test_prefetch_plan_covers_every_matmul(depth, num_cores):
    """The prefetch-coverage invariant: every prefetchable matmul is
    either fed by an issuing predecessor on its own queue or explicitly
    flagged cold — never silently unhinted (ISSUE 1 tentpole (b))."""
    g = mlp_chain_graph()
    s = schedule_graph(g, num_cores=num_cores, use_native=False,
                       pf_depth=depth)
    validate_schedule(g, s)
    plan = s.prefetch
    assert plan is not None and plan.depth == depth
    _, code_of = prefetch_specs(g.tasks)
    assert code_of, "MLP chain must expose prefetchable weights"
    cold = set(plan.cold)
    for t in g.tasks:
        if t.op == "matmul" and t.branch_key[1] in code_of:
            fed = int(plan.consume[t.id]) > 0
            assert fed != (t.id in cold), (
                f"task {t.id} must be exactly fed-or-cold")
    # a chain of matmuls must actually stream: at least one is fed
    assert any(plan.consume[t.id] > 0 for t in g.tasks
               if t.op == "matmul")


def test_prefetch_deeper_arena_never_loses_coverage():
    """Growing the rotating arena can only convert cold opens into fed
    ones (depth bounds the number of in-flight first tiles; it never
    forbids an issue that a shallower arena allowed)."""
    g = mlp_chain_graph(layers=4)
    cold_by_depth = []
    for depth in (1, 2, 3):
        s = schedule_graph(g, num_cores=1, use_native=False,
                           pf_depth=depth)
        cold_by_depth.append(set(s.prefetch.cold))
    assert cold_by_depth[1] <= cold_by_depth[0]
    assert cold_by_depth[2] <= cold_by_depth[1]


def test_prefetch_plan_tamper_detected():
    """validate_schedule replays the arena: un-flagging a cold consumer,
    consuming an empty slot, or double-issuing into a filled slot all
    trip the prefetch invariant."""
    g = mlp_chain_graph()
    s = schedule_graph(g, num_cores=1, use_native=False, pf_depth=2)
    validate_schedule(g, s)
    plan = s.prefetch
    fed = [t for t in range(len(g.tasks)) if plan.consume[t] > 0]
    assert fed

    # un-flag a fed consumer: now neither fed nor cold
    plan.consume[fed[0]] = 0
    with pytest.raises(AssertionError):
        validate_schedule(g, s)

    s2 = schedule_graph(g, num_cores=1, use_native=False, pf_depth=2)
    validate_schedule(g, s2)

    # an issue whose tile is never consumed must not survive either
    issuers = [t for t in range(len(g.tasks)) if s2.prefetch.issue_code[t]]
    s2.prefetch.consume[:] = 0
    s2.prefetch.cold = [t.id for t in g.tasks
                        if t.op == "matmul"
                        and t.branch_key[1] in prefetch_specs(g.tasks)[1]]
    assert issuers
    with pytest.raises(AssertionError):
        validate_schedule(g, s2)  # prefetches left in flight at queue end


@pytest.mark.parametrize("num_cores", [1, 2])
def test_predicted_stall_recorded_and_monotone(num_cores):
    """Schedules expose the cost-model scoreboard stall per queue, and
    the monotone-watermark rewrite the kernel actually waits on must
    reproduce it exactly (the no-extra-blocking theorem)."""
    g = mlp_chain_graph()
    s = schedule_graph(g, num_cores=num_cores, use_native=False)
    assert s.stall is not None and len(s.stall) == num_cores
    raw = predicted_stalls(g, s)
    mono = predicted_stalls(g, s, monotone=True)
    np.testing.assert_allclose(raw, np.asarray(s.stall))
    np.testing.assert_allclose(mono, raw)
    if num_cores == 1:
        # one queue never waits on a scoreboard
        assert float(raw[0]) == 0.0
    # a corrupted recorded prediction must be caught
    s.stall = np.asarray(s.stall) + 1.0
    with pytest.raises(AssertionError):
        validate_schedule(g, s)


def test_cycle_detection(backend):
    g = Graph(batch=1)
    x = g.buffer(128, "x")
    g.add_task("op", ("op", 128), [], reads=[x], writes=[x])
    g.edges.append((0, 0))  # forced self-cycle
    with pytest.raises(ValueError):
        schedule_graph(g, use_native=backend)


def test_war_and_waw_edges():
    g = Graph(batch=1)
    x = g.buffer(128, "x")
    y = g.buffer(128, "y")
    t0 = g.add_task("w", ("w",), [], reads=[], writes=[x])
    t1 = g.add_task("r", ("r",), [], reads=[x], writes=[y])
    t2 = g.add_task("w", ("w",), [], reads=[], writes=[x])  # WAR vs t1
    assert (t0.id, t1.id) in set(g.edges)
    assert (t1.id, t2.id) in set(g.edges)  # reader before overwrite
    assert (t0.id, t2.id) in set(g.edges)  # WAW
