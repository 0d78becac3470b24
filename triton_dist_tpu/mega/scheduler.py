"""Megakernel scheduler: task -> per-core work queues + scoreboard
watermarks + workspace slot plan.

TPU-native re-design of the reference's scheduler
(ref: python/triton_dist/mega_triton_kernel/core/scheduler.py:30-95). The
reference round-robins task tuples over NUM_SMS queues; a TPU chip has
1-2 TensorCores, so the default is critical-path list scheduling
(strategy "least_loaded") and the scoreboard is per-core *progress
watermarks* rather than per-tile signals: core c broadcasts "I completed
my k-th task"; a task waits until progress[c'] >= wm[c'] for every other
core. Same-core order subsumes same-core deps, so at num_cores=1 (v5e,
CPU interpret) every watermark is zero and the queue is simply a
topological order.

The heavy lifting lives in the native C++ library (csrc/scheduler.cc via
mega/_native.py); the pure-Python mirrors below implement the identical
algorithms and are used when the native build is unavailable
(TDT_NO_NATIVE=1 forces them — the tests cross-check both).
"""

from __future__ import annotations

import ctypes
import dataclasses
import os
import heapq
from typing import Any, List, Optional, Tuple

import numpy as np

from triton_dist_tpu.mega import _native
from triton_dist_tpu.mega.core import Graph, plan_mm_tiles

STRATEGIES = {"round_robin": 0, "blocked": 1, "least_loaded": 2}


def pf_arena_bytes() -> int:
    """Prefetch-arena VMEM byte budget (TDT_MEGA_PF_ARENA_BYTES,
    default 32 MiB — two 32B-class first tiles in flight)."""
    return int(os.environ.get("TDT_MEGA_PF_ARENA_BYTES", str(32 << 20)))


def auto_pf_depth(specs) -> int:
    """Byte-aware arena depth: as many rotating slots as the byte
    budget buys at this graph's arena-rectangle size (the arena is one
    (depth, max K, max TN) VMEM block — the RECTANGLE is what occupies
    VMEM, not the per-weight tile), clamped to [2, 4]. The floor of 2
    keeps one tile in flight across every task boundary (depth 1 is
    the legacy single-tile lookahead, opt-in via TDT_MEGA_PF_DEPTH);
    the ceiling of 4 bounds plan churn — deeper arenas stopped
    converting cold opens well before 4 on the Qwen3 graphs
    (tests/test_mega_core.py monotonicity corpus)."""
    env = os.environ.get("TDT_MEGA_PF_DEPTH")
    if env:
        return max(1, int(env))
    if not specs:
        return 2
    rect = max(kk for _, kk, _ in specs) * max(tn for _, _, tn in specs)
    return max(2, min(4, pf_arena_bytes() // max(rect * 2, 1)))


@dataclasses.dataclass
class PrefetchPlan:
    """The cross-task weight-streaming plan (see kernel.py ROW comment):
    each prefetchable matmul ("consumer") is assigned a rotating arena
    slot and an earlier row of the SAME queue ("issuer") that starts the
    first weight tile's DMA. depth = arena slots = max prefetches in
    flight. Consumers with no legal issuer open cold and are recorded in
    `cold` — validate_schedule enforces that every consumer is exactly
    one of the two."""

    depth: int
    specs: List[Tuple[str, int, int]]   # [(wname, K, TN)] — pf_code order
    issue_code: np.ndarray              # (n_tasks,) 0 = row carries no hint
    issue_layer: np.ndarray
    issue_slot: np.ndarray
    consume: np.ndarray                 # (n_tasks,) pf_in: slot+1, 0 = cold
    cold: List[int]                     # consumer task ids opening cold


@dataclasses.dataclass
class StorePlan:
    """The cross-task store/forward pipeline (single-core queues only —
    under concurrent cores a scoreboard completion must imply the data is
    in HBM, which a deferred store would break). defer_st=1 rows leave
    their workspace store in flight; the FOLLOWING row drains it (pend_w
    = 1 + index into `widths`), before its own loads when pend_early=1
    (reads alias the stored slot, or the branch has no late-drain site)
    or right before it first overwrites vout otherwise. fwd_in=1 rows
    read their main input straight from the previous task's vout."""

    widths: Tuple[int, ...]
    defer_st: np.ndarray
    pend_w: np.ndarray
    pend_early: np.ndarray
    fwd_in: np.ndarray


@dataclasses.dataclass
class Schedule:
    core: np.ndarray         # (n_tasks,) core of each task
    pos: np.ndarray          # (n_tasks,) position within its core queue
    watermarks: np.ndarray   # (n_tasks, num_cores) scoreboard waits
    order: List[int]         # global order (core-major: core0 queue, ...)
    queues: List[List[int]]  # per-core task id lists
    buf_slot: np.ndarray     # (n_bufs,) workspace slot per buffer
    n_slots: int
    native: bool             # True when produced by the C++ scheduler
    # predicted scoreboard stall per queue (cost-model time a core spends
    # waiting on other cores' watermarks beyond its own availability),
    # from predicted_stalls; validate_schedule asserts monotonized
    # watermarks reproduce it exactly
    stall: Any = None
    prefetch: Optional[PrefetchPlan] = None
    # fusion-plan provenance: schedule_graph(plan=...) stamps the
    # triton_dist_tpu.plan.Plan id so serving and one-shot
    # forwards can be checked to agree on pairings
    plan_id: Optional[str] = None

    @property
    def num_cores(self) -> int:
        return int(self.watermarks.shape[1])


def _i32(a) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(a, dtype=np.int32))


# -- pure-Python mirrors of the native algorithms ----------------------------


def _py_schedule(n, edges, cost, num_cores, strategy, affinity=None):
    """affinity (optional, least_loaded only): per-task bool marking
    prefetch consumers (matmuls whose first weight tile can stream from
    an earlier row of the same queue — kernel.py ROW comment). Such a
    task prefers the core of its latest-scheduled predecessor among
    near-tied loads, so a branch able to ISSUE its prefetch precedes it
    in the same queue (the hint and the arena are per-core VMEM: a
    cross-core predecessor cannot feed it)."""
    succ = [[] for _ in range(n)]
    pred = [[] for _ in range(n)]
    indeg = [0] * n
    for s, d in edges:
        succ[s].append(d)
        pred[d].append(s)
        indeg[d] += 1
    # critical-path priorities over reverse topo order
    order = []
    stack = [t for t in range(n) if indeg[t] == 0]
    deg = list(indeg)
    while stack:
        t = stack.pop()
        order.append(t)
        for s in succ[t]:
            deg[s] -= 1
            if deg[s] == 0:
                stack.append(s)
    if len(order) != n:
        raise ValueError("dependency cycle in megakernel graph")
    def cost_of(t):
        return cost[t] if cost is not None else 1.0

    prio = [0.0] * n
    for t in reversed(order):
        prio[t] = cost_of(t) + max((prio[s] for s in succ[t]), default=0.0)

    ready = [(-prio[t], t) for t in range(n) if indeg[t] == 0]
    heapq.heapify(ready)
    deg = list(indeg)
    core = [0] * n
    pos = [0] * n
    sched_at = [0] * n  # scheduling step, for the affinity tie-break
    core_load = [0.0] * num_cores
    core_len = [0] * num_cores
    scheduled = 0
    rr = 0
    per = (n + num_cores - 1) // num_cores
    while ready:
        _, t = heapq.heappop(ready)
        if num_cores == 1:
            c = 0
        elif strategy == 0:
            c = rr % num_cores
            rr += 1
        elif strategy == 1:
            c = min(scheduled // per, num_cores - 1)
        else:
            c = min(range(num_cores), key=lambda k: core_load[k])
            if affinity is not None and affinity[t] and pred[t]:
                # prefetch co-location: among near-tied cores, follow the
                # latest-scheduled predecessor (load slack bounded by the
                # task's own cost — never trades real balance for it)
                want = core[max(pred[t], key=lambda p: sched_at[p])]
                if (want != c
                        and core_load[want] <= core_load[c] + cost_of(t)):
                    c = want
        core[t] = c
        pos[t] = core_len[c]
        core_len[c] += 1
        core_load[c] += cost_of(t)
        sched_at[t] = scheduled
        scheduled += 1
        for s in succ[t]:
            deg[s] -= 1
            if deg[s] == 0:
                heapq.heappush(ready, (-prio[s], s))
    return np.array(core, np.int32), np.array(pos, np.int32)


def _py_watermarks(n, edges, core, pos, num_cores):
    wm = np.zeros((n, num_cores), np.int32)
    for s, d in edges:
        if core[s] == core[d]:
            if pos[s] >= pos[d]:
                raise ValueError(f"invalid schedule: dep {s}->{d} inverted")
            continue
        wm[d, core[s]] = max(wm[d, core[s]], pos[s] + 1)
    return wm


def monotone_watermarks(sched: "Schedule") -> np.ndarray:
    """Watermarks rewritten as a running max along each core queue.

    Waiting for the running max blocks no longer than the original wait
    (every earlier task on the queue already waited for its own watermark,
    so by the time task d runs, progress has reached the prefix max) and
    makes consumed-count tracking static: the kernel's per-row wait is
    simply wm_mono[d] - wm_mono[previous row], a compile-time delta."""
    wm = np.array(sched.watermarks, np.int32, copy=True)
    for q in sched.queues:
        run = np.zeros(wm.shape[1], np.int32)
        for t in q:
            run = np.maximum(run, wm[t])
            wm[t] = run
    return wm


INF_POS = 1 << 30


def after_vectors(sched: "Schedule", wm_mono: np.ndarray) -> np.ndarray:
    """A[t, c] = the smallest queue position p such that task (c, p) is
    guaranteed to START strictly after task t COMPLETES (INF_POS if no
    such task). This is the happens-before closure of the multi-core
    execution order — same-core program order plus scoreboard watermark
    waits — used by the slot planner to prove that a workspace slot's
    previous tenant is fully drained before its next definer can run.

    At num_cores=1 this degenerates to A[t, 0] = pos[t] + 1 and the
    planner below reproduces the linear-interval planner exactly."""
    n, nc = wm_mono.shape
    core = np.asarray(sched.core)
    pos = np.asarray(sched.pos)
    # HB successor edges on tasks: same-core next task, plus each task u
    # whose (monotone) watermark on core c equals p+1 starts after task
    # (c, p) completes. Larger watermarks are reached transitively.
    succ: List[List[int]] = [[] for _ in range(n)]
    by_cp = {(int(core[t]), int(pos[t])): t for t in range(n)}
    for q in sched.queues:
        for a, b in zip(q, q[1:]):
            succ[a].append(b)
    for u in range(n):
        for c in range(nc):
            w = int(wm_mono[u, c])
            if w > 0 and c != core[u]:
                succ[by_cp[(c, w - 1)]].append(u)
    indeg = np.zeros(n, np.int64)
    for t in range(n):
        for s in succ[t]:
            indeg[s] += 1
    topo = [t for t in range(n) if indeg[t] == 0]
    head = 0
    while head < len(topo):
        t = topo[head]
        head += 1
        for s in succ[t]:
            indeg[s] -= 1
            if indeg[s] == 0:
                topo.append(s)
    assert len(topo) == n, "cycle in happens-before graph"
    A = np.full((n, nc), INF_POS, np.int64)
    for t in reversed(topo):
        for s in succ[t]:
            # start(s) is after comp(t): s's own position counts, and
            # everything after comp(s) is after start(s) >= comp(t)
            A[t] = np.minimum(A[t], A[s])
            A[t, core[s]] = min(A[t, core[s]], pos[s])
    return A


def _buffer_users(graph: Graph) -> Tuple[List[int], List[List[int]]]:
    """(defining task per buffer (-1 if external), every accessing task
    per buffer) — shared by the HB slot planner and its validator, whose
    agreement the multi-core slot safety argument depends on."""
    nb = len(graph.buffers)
    def_task = [-1] * nb
    users: List[List[int]] = [[] for _ in range(nb)]
    for t in graph.tasks:
        for b in t.writes:
            if def_task[b] < 0:
                def_task[b] = t.id
            users[b].append(t.id)
        for b in t.reads:
            users[b].append(t.id)
    return def_task, users


def _py_plan_slots_hb(graph: Graph, sched: "Schedule",
                      A: np.ndarray) -> Tuple[np.ndarray, int]:
    """Slot planning under concurrent cores: slot reuse is legal only
    when every task touching the previous tenant happens-before the new
    tenant's defining task (proved via the `after_vectors` closure, not
    linear order — two tasks adjacent in the core-major order may run
    CONCURRENTLY on different cores)."""
    nb = len(graph.buffers)
    nc = A.shape[1]
    core = np.asarray(sched.core)
    pos = np.asarray(sched.pos)
    gpos = {t: i for i, t in enumerate(sched.order)}
    def_task, users = _buffer_users(graph)
    order_b = sorted(range(nb),
                     key=lambda b: gpos.get(def_task[b], -1))
    slot = np.zeros(nb, np.int64)
    # release[s][c] = min position on core c from which a new tenant's
    # def task may start (max over the old tenant's users' A vectors)
    release: List[np.ndarray] = []
    for b in order_b:
        pinned = graph.pinned.get(b, False)
        d = def_task[b]
        chosen = -1
        if not pinned and d >= 0:
            for s, rel in enumerate(release):
                if rel is None:
                    continue  # pinned slot
                if pos[d] >= rel[core[d]]:
                    chosen = s
                    break
        if chosen < 0:
            chosen = len(release)
            release.append(np.zeros(nc, np.int64))
        slot[b] = chosen
        if pinned:
            release[chosen] = None
        else:
            rel = np.zeros(nc, np.int64)
            for u in users[b]:
                rel = np.maximum(rel, A[u])
            if not users[b]:
                rel[:] = INF_POS  # unused buffer: never reusable safely
            release[chosen] = rel
    return np.array(slot, np.int32), len(release)


def _py_plan_slots(ndef, last, pinned):
    n = len(ndef)
    free_at: List[int] = []
    slot = [0] * n
    for b in sorted(range(n), key=lambda b: ndef[b]):
        chosen = -1
        if not pinned[b]:
            for s, fa in enumerate(free_at):
                if fa <= ndef[b]:
                    chosen = s
                    break
        if chosen < 0:
            chosen = len(free_at)
            free_at.append(0)
        slot[b] = chosen
        free_at[chosen] = (1 << 30) if pinned[b] else last[b] + 1
    return np.array(slot, np.int32), len(free_at)


# -- prefetch / store-pipeline planning ---------------------------------------


def prefetch_specs(tasks) -> Tuple[List[Tuple[str, int, int]], dict]:
    """([(wname, K, TN)] in pf_code order, wname -> pf_code). A weight is
    prefetchable only when every matmul using it shares one (K, TN) —
    the single arena-tile geometry the issuer and consumer must agree
    on. Shared by kernel.compile_graph (builds the arena) and
    plan_prefetch/validate_schedule (assign and check the hints). Tiles
    come from the byte-budgeted plan_mm_tiles map — the same map the
    kernel tiles with."""
    tn_of = plan_mm_tiles([t.branch_key for t in tasks
                           if t.op == "matmul"])
    name_dims: dict = {}
    for t in tasks:
        if t.op != "matmul":
            continue
        k = t.branch_key
        name_dims.setdefault(k[1], set()).add((k[2], tn_of[k]))
    specs: List[Tuple[str, int, int]] = []
    code_of: dict = {}
    for wname in sorted(name_dims):
        if len(name_dims[wname]) == 1:
            (kk, tn), = name_dims[wname]
            code_of[wname] = len(specs) + 1
            specs.append((wname, kk, tn))
    return specs, code_of


def _matmul_nt(task, tn_of) -> int:
    n_cols = task.branch_key[3]
    return n_cols // tn_of[task.branch_key]


def plan_prefetch(graph: Graph, sched: "Schedule",
                  depth: Optional[int] = None) -> PrefetchPlan:
    """Assign each prefetchable matmul a rotating arena slot and an
    issuing predecessor row in the same queue.

    Policy: the hint rides the IMMEDIATELY preceding row (assigning it to
    the closest previous matmul instead — streaming through intervening
    small tasks — was measured WORSE on the 32B model: the 3-5 MB pf
    tile head-of-line-blocks every intervening task's small input DMA in
    the shared HBM->VMEM queue; what helps is issuing EARLY WITHIN the
    task — see the kernel branch bodies). The arena's job is different:
    with depth >= 2 an nt==1 matmul can issue the NEXT matmul's tile
    before its own last dot instead of in its store epilogue, and the
    slot being written is never the slot being read.

    Slot-safety invariant (replayed in _validate_prefetch): an issue into
    slot s must come strictly after the previous consumer of s has read
    it — equality (issue and previous consume on one row) is legal only
    when that row is a matmul with nt > 1, which reads its own tile at
    j==0 before issuing at j==nt-1; an nt==1 matmul under depth > 1
    issues BEFORE its read."""
    tasks = graph.tasks
    n = len(tasks)
    specs, code_of = prefetch_specs(tasks)
    tn_of = plan_mm_tiles([t.branch_key for t in tasks
                           if t.op == "matmul"])
    if depth is None:
        depth = auto_pf_depth(specs)
    plan = PrefetchPlan(
        depth=depth, specs=specs,
        issue_code=np.zeros(n, np.int32),
        issue_layer=np.zeros(n, np.int32),
        issue_slot=np.zeros(n, np.int32),
        consume=np.zeros(n, np.int32), cold=[],
    )
    for q in sched.queues:
        cons_rows: List[int] = []  # queue rows of slot-using consumers
        for qi, tid in enumerate(q):
            t = tasks[tid]
            if t.op != "matmul" or t.branch_key[1] not in code_of:
                continue
            k = len(cons_rows)
            lo = cons_rows[k - depth] if k >= depth else -1
            isr = qi - 1
            ok = isr >= 0 and plan.issue_code[q[isr]] == 0
            if ok and isr == lo:
                # issuer row IS the slot's previous consumer: only safe
                # when it reads its own tile before issuing (nt > 1)
                prev = tasks[q[isr]]
                ok = prev.op == "matmul" and _matmul_nt(prev, tn_of) > 1
            elif ok:
                ok = isr > lo
            if not ok:
                plan.cold.append(tid)
                continue
            slot = k % depth
            plan.issue_code[q[isr]] = code_of[t.branch_key[1]]
            plan.issue_layer[q[isr]] = t.args[0]
            plan.issue_slot[q[isr]] = slot
            plan.consume[tid] = slot + 1
            cons_rows.append(qi)
    _validate_prefetch(graph, sched, plan)  # self-check at plan time
    return plan


def _validate_prefetch(graph: Graph, sched: "Schedule",
                       plan: PrefetchPlan) -> None:
    """Replay the arena per queue: every issue targets a drained slot,
    every consume finds its slot filled with the matching weight tile,
    and every prefetchable matmul either consumes or is flagged cold."""
    tasks = graph.tasks
    specs, code_of = prefetch_specs(tasks)
    tn_of = plan_mm_tiles([t.branch_key for t in tasks
                           if t.op == "matmul"])
    assert plan.specs == specs, "prefetch plan built for a different graph"
    cold = set(plan.cold)
    seen = set()
    for q in sched.queues:
        filled: dict = {}  # slot -> (pf_code, layer)
        for qi, tid in enumerate(q):
            t = tasks[tid]
            is_consumer = (t.op == "matmul"
                           and t.branch_key[1] in code_of)
            code = int(plan.issue_code[tid])
            cons = int(plan.consume[tid])
            if not is_consumer:
                assert cons == 0, (
                    f"non-matmul task {tid} marked as prefetch consumer")
            # same-row ordering: nt>1 matmuls consume then issue;
            # everything else (incl. nt==1 under depth>1) issues first
            consume_first = (is_consumer and cons > 0
                             and _matmul_nt(t, tn_of) > 1)

            def do_consume():
                slot = cons - 1
                assert slot in filled, (
                    f"task {tid} consumes arena slot {slot} but no "
                    "prefetch is in flight there")
                got_code, got_layer = filled.pop(slot)
                assert got_code == code_of[t.branch_key[1]], (
                    f"task {tid}: arena slot {slot} holds weight code "
                    f"{got_code}, expected {code_of[t.branch_key[1]]}")
                assert got_layer == t.args[0], (
                    f"task {tid}: arena slot {slot} holds layer "
                    f"{got_layer}, expected {t.args[0]}")

            if consume_first:
                do_consume()
            if code:
                slot = int(plan.issue_slot[tid])
                assert 0 <= slot < plan.depth
                assert slot not in filled, (
                    f"task {tid} issues into arena slot {slot} while the "
                    "previous tile there is unconsumed")
                filled[slot] = (code, int(plan.issue_layer[tid]))
            if is_consumer:
                if cons > 0:
                    if not consume_first:
                        do_consume()
                    seen.add(tid)
                else:
                    assert tid in cold, (
                        f"matmul task {tid} ({t.tag}) has no issuing "
                        "predecessor and is not flagged cold")
                    seen.add(tid)
        assert not filled, (
            f"prefetches left in flight at queue end: {filled}")
    # coverage: every prefetchable matmul is either fed or flagged cold
    for t in tasks:
        if t.op == "matmul" and t.branch_key[1] in code_of:
            assert t.id in seen
    assert cold.isdisjoint(
        {t for t in range(len(tasks)) if plan.consume[t] > 0})


def plan_store_forward(
    graph: Graph,
    sched: "Schedule",
    store_width,
    can_late_drain,
    fwd_spec,
) -> StorePlan:
    """Build the deferred-store / forward plan for a single-core queue.

    store_width[t]: width of task t's deferrable workspace store (0 =
    the branch cannot defer: attention's multi-store epilogue, barrier).
    can_late_drain[t]: the branch drains a pending store right before
    overwriting vout (matmul/rms/silu/add/AR); others must drain EARLY,
    in the dispatch wrapper, before their loads. fwd_spec[t]: (main
    source buffer id, rows read from vout) for branches that can read
    their input from the previous task's vout, else None."""
    n = len(graph.tasks)
    empty = StorePlan((), np.zeros(n, np.int32), np.zeros(n, np.int32),
                      np.zeros(n, np.int32), np.zeros(n, np.int32))
    if sched.num_cores != 1:
        # concurrent queues: a scoreboard completion must imply the data
        # reached HBM — never defer across the scoreboard
        return empty
    q = sched.queues[0]
    tasks = graph.tasks
    pairs = []  # (producer, consumer, width, early, fwd)
    for qi in range(len(q) - 1):
        p, c = q[qi], q[qi + 1]
        w = int(store_width[p])
        if w == 0:
            continue
        tp, tc = tasks[p], tasks[c]
        assert len(tp.writes) == 1, (
            f"deferrable task {p} must write exactly one buffer")
        dst = tp.writes[0]
        fs = fwd_spec[c]
        fwd = (fs is not None and fs[0] == dst and fs[1] <= w
               # reads of dst beyond the main source still hit HBM and
               # would need the store drained first — no forward then
               and tc.reads.count(dst) == 1)
        if fwd:
            assert can_late_drain[c], "forward-capable branches late-drain"
            early = 0
        elif dst in tc.reads:
            early = 1  # consumer loads the stored slot from HBM
        else:
            early = 0 if can_late_drain[c] else 1
        pairs.append((p, c, w, early, 1 if fwd else 0))
    if not pairs:
        return empty
    widths = tuple(sorted({w for _, _, w, _, _ in pairs}))
    plan = StorePlan(widths, np.zeros(n, np.int32), np.zeros(n, np.int32),
                     np.zeros(n, np.int32), np.zeros(n, np.int32))
    for p, c, w, early, fwd in pairs:
        plan.defer_st[p] = 1
        plan.pend_w[c] = widths.index(w) + 1
        plan.pend_early[c] = early
        plan.fwd_in[c] = fwd
    return plan


# -- predicted scoreboard stall ----------------------------------------------


def predicted_stalls(graph: Graph, sched: "Schedule",
                     monotone: bool = False) -> np.ndarray:
    """Cost-model simulation of the multi-queue execution: each core runs
    its queue in order; a task starts at max(own core free, dep ends).
    Returns per-core stall = total time a core sits waiting on OTHER
    cores' watermarks beyond its own availability.

    monotone=True derives deps from the monotonized watermarks the
    kernel actually waits on (task t waits for task (c, wm_mono[t,c]-1))
    instead of the raw graph edges; validate_schedule asserts both give
    identical stalls — the monotone rewrite's no-extra-blocking theorem
    (see monotone_watermarks)."""
    tasks = graph.tasks
    n = len(tasks)
    nc = sched.num_cores
    core = np.asarray(sched.core)
    deps: List[List[int]] = [[] for _ in range(n)]
    if monotone:
        wm = monotone_watermarks(sched)
        by_cp = {(int(core[t]), int(sched.pos[t])): t for t in range(n)}
        for t in range(n):
            for c in range(nc):
                w = int(wm[t, c])
                if w > 0 and c != core[t]:
                    deps[t].append(by_cp[(c, w - 1)])
    else:
        for s, d in graph.edges:
            if core[s] != core[d]:
                deps[d].append(s)
    ptr = [0] * nc
    t_end = [None] * n
    core_time = [0.0] * nc
    stall = np.zeros(nc, np.float64)
    done = 0
    while done < n:
        best = None
        for c in range(nc):
            if ptr[c] >= len(sched.queues[c]):
                continue
            t = sched.queues[c][ptr[c]]
            if any(t_end[d] is None for d in deps[t]):
                continue
            start = max([core_time[c]] + [t_end[d] for d in deps[t]])
            if best is None or start < best[0]:
                best = (start, c, t)
        if best is None:
            raise ValueError("schedule simulation deadlocked "
                             "(inconsistent watermarks?)")
        start, c, t = best
        stall[c] += start - core_time[c]
        t_end[t] = start + tasks[t].cost
        core_time[c] = t_end[t]
        ptr[c] += 1
        done += 1
    return stall


# -- public entry -------------------------------------------------------------


def schedule_graph(
    graph: Graph,
    num_cores: int = 1,
    strategy: str = "least_loaded",
    use_native: Optional[bool] = None,
    pf_depth: Optional[int] = None,
    plan=None,
) -> Schedule:
    """Schedule + plan a Graph. use_native=None auto-selects the C++ lib.

    pf_depth sets the weight-prefetch arena depth the plan is built for
    (default: byte-aware auto_pf_depth from the graph's tile rectangle;
    TDT_MEGA_PF_DEPTH pins it); the returned schedule carries
    `prefetch` (PrefetchPlan) and `stall` (predicted per-queue scoreboard
    stall), both asserted by validate_schedule.

    plan (optional triton_dist_tpu.plan.Plan): the fusion plan this
    graph was lowered under — the schedule adopts its mega_strategy and
    carries its plan_id, so the megakernel and the layer-forward planes
    provably run the SAME pairing decisions. The plan_id hashes the
    plan's applied tune-cache winners (Plan.applied_configs) along with
    the routing, so a schedule built before the cache was populated can
    never be confused with one inheriting a measured config."""
    n = len(graph.tasks)
    if n == 0:
        raise ValueError("empty megakernel graph")
    if plan is not None:
        strategy = plan.mega_strategy
    if pf_depth is None:
        # byte-aware default: size the rotating arena from this graph's
        # actual tile rectangle (auto_pf_depth; TDT_MEGA_PF_DEPTH wins)
        pf_depth = auto_pf_depth(prefetch_specs(graph.tasks)[0])
    strat = STRATEGIES[strategy]
    edges = graph.edges
    cost = [t.cost for t in graph.tasks]
    lib = _native.load() if use_native in (None, True) else None
    if use_native is True and lib is None:
        raise RuntimeError("native scheduler requested but unavailable")

    def _finalize(sched: Schedule) -> Schedule:
        sched.stall = predicted_stalls(graph, sched)
        sched.prefetch = plan_prefetch(graph, sched, depth=pf_depth)
        if plan is not None:
            sched.plan_id = plan.plan_id
        return sched

    if lib is not None:
        src = _i32([e[0] for e in edges])
        dst = _i32([e[1] for e in edges])
        costs = np.ascontiguousarray(np.asarray(cost, np.float64))
        core = np.zeros(n, np.int32)
        pos = np.zeros(n, np.int32)
        rc = lib.tdt_schedule(
            n, len(edges),
            src.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            dst.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            costs.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            num_cores, strat,
            core.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            pos.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        )
        if rc != 0:
            raise ValueError(f"native scheduler failed rc={rc} "
                             "(dependency cycle?)")
        wm = np.zeros((n, num_cores), np.int32)
        rc = lib.tdt_watermarks(
            n, len(edges),
            src.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            dst.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            core.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            pos.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            num_cores,
            wm.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        )
        if rc != 0:
            raise ValueError(f"native watermarks failed rc={rc}")
    else:
        # prefetch-aware placement (pure-Python path): a prefetchable
        # matmul prefers its predecessor's core so the issuing row and
        # the consuming matmul share a queue (and a VMEM arena)
        _, code_of = prefetch_specs(graph.tasks)
        affinity = [t.op == "matmul" and t.branch_key[1] in code_of
                    for t in graph.tasks]
        core, pos = _py_schedule(n, edges, cost, num_cores, strat,
                                 affinity=affinity)
        wm = _py_watermarks(n, edges, core, pos, num_cores)

    queues: List[List[int]] = [[] for _ in range(num_cores)]
    for t in range(n):
        queues[core[t]].append(t)
    for q in queues:
        q.sort(key=lambda t: pos[t])
    order = [t for q in queues for t in q]

    if num_cores > 1:
        # concurrent queues: interval liveness over the core-major order
        # is unsound (adjacent order positions may run concurrently on
        # different cores) — plan via the happens-before closure instead
        sched = Schedule(core=np.asarray(core), pos=np.asarray(pos),
                         watermarks=wm, order=order, queues=queues,
                         buf_slot=np.zeros(len(graph.buffers), np.int32),
                         n_slots=0, native=lib is not None)
        slot, n_slots = _py_plan_slots_hb(
            graph, sched, after_vectors(sched, monotone_watermarks(sched)))
        sched.buf_slot = slot
        sched.n_slots = int(n_slots)
        return _finalize(sched)

    ndef, last = graph.liveness(order)
    pinned = [graph.pinned.get(b.id, False) for b in graph.buffers]
    if lib is not None:
        nd = _i32(ndef)
        lt = _i32(last)
        pn = np.ascontiguousarray(np.asarray(pinned, np.uint8))
        slot = np.zeros(len(graph.buffers), np.int32)
        n_slots = lib.tdt_plan_slots(
            len(graph.buffers),
            nd.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            lt.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            pn.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            slot.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        )
    else:
        slot, n_slots = _py_plan_slots(ndef, last, pinned)

    return _finalize(Schedule(core=np.asarray(core), pos=np.asarray(pos),
                              watermarks=wm, order=order, queues=queues,
                              buf_slot=slot, n_slots=int(n_slots),
                              native=lib is not None))


def validate_schedule(graph: Graph, sched: Schedule) -> None:
    """Sanity invariants (tests + compile-time assert): every dep either
    precedes its consumer on the same core or carries a watermark; no two
    buffers sharing a slot can be live concurrently (proved by interval
    order at one core, by the happens-before closure under many); the
    prefetch plan covers every prefetchable matmul (fed by an issuing
    predecessor or explicitly flagged cold) with a race-free arena
    replay; and the predicted scoreboard stall is reproduced exactly by
    the monotonized watermarks the kernel actually waits on (the
    monotone rewrite must add no blocking)."""
    for s, d in graph.edges:
        if sched.core[s] == sched.core[d]:
            assert sched.pos[s] < sched.pos[d], (s, d)
        else:
            assert sched.watermarks[d, sched.core[s]] >= sched.pos[s] + 1
    # prefetch-coverage invariant (weight-streaming pipeline)
    plan = sched.prefetch
    if plan is None:
        plan = plan_prefetch(graph, sched)
    else:
        _validate_prefetch(graph, sched, plan)
    # predicted-stall invariant: raw-edge and monotone-watermark
    # simulations must agree, and must match the recorded prediction
    raw = predicted_stalls(graph, sched)
    mono = predicted_stalls(graph, sched, monotone=True)
    assert np.allclose(mono, raw), (
        f"monotone watermark rewrite changes predicted stall: "
        f"{mono} vs {raw}")
    if sched.stall is not None:
        assert np.allclose(np.asarray(sched.stall), raw), (
            f"recorded stall prediction {sched.stall} does not match "
            f"the schedule's simulation {raw}")
    if sched.num_cores > 1:
        _validate_slots_hb(graph, sched)
        return
    ndef, last = graph.liveness(sched.order)
    by_slot: dict = {}
    for b in graph.buffers:
        by_slot.setdefault(sched.buf_slot[b.id], []).append(
            (ndef[b.id], last[b.id], b.id))
    for slot, spans in by_slot.items():
        spans.sort()
        for (d1, l1, b1), (d2, l2, b2) in zip(spans, spans[1:]):
            assert l1 < d2, (
                f"slot {slot}: buffers {b1} and {b2} overlap "
                f"([{d1},{l1}] vs [{d2},{l2}])"
            )


def task_hb_graph(sched: Schedule) -> "HBGraph":
    """The multi-core execution's happens-before DAG on task ids, built
    on the shared verify.hb engine (one HB implementation for protocol
    verification AND schedule validation): same-queue program order plus
    one edge per monotone-watermark wait (task u waiting wm[u, c] = w
    starts after task (c, w-1) completes). Edge semantics are
    completion(a) <= start(b), so `reaches(u, d)` iff task u is fully
    drained before task d can run — the slot-reuse safety predicate."""
    from triton_dist_tpu.verify.hb import HBGraph

    g = HBGraph()
    for t in range(len(sched.core)):
        g.add_node(t)
    for q in sched.queues:
        for a, b in zip(q, q[1:]):
            g.add_edge(a, b)
    wm = monotone_watermarks(sched)
    core = np.asarray(sched.core)
    by_cp = {(int(core[t]), int(sched.pos[t])): t
             for t in range(len(core))}
    for u in range(len(core)):
        for c in range(wm.shape[1]):
            w = int(wm[u, c])
            if w > 0 and c != core[u]:
                g.add_edge(by_cp[(c, w - 1)], u)
    return g


def _validate_slots_hb(graph: Graph, sched: Schedule) -> None:
    """Multi-core slot check: for each pair of buffers sharing a slot,
    one buffer's every accessor must happen-before the other's defining
    task (recomputed independently of the planner's choices — the
    planner proves via `after_vectors` position minima, the validator
    via shared-engine reachability; their agreement is the check)."""
    g = task_hb_graph(sched)
    def_task, users = _buffer_users(graph)

    def all_before(b1: int, b2: int) -> bool:
        d = def_task[b2]
        if d < 0:
            return False
        return all(g.reaches(u, d) for u in users[b1])

    by_slot: dict = {}
    for b in graph.buffers:
        by_slot.setdefault(int(sched.buf_slot[b.id]), []).append(b.id)
    for slot, bufs in by_slot.items():
        for i, b1 in enumerate(bufs):
            for b2 in bufs[i + 1:]:
                assert all_before(b1, b2) or all_before(b2, b1), (
                    f"slot {slot}: buffers {b1} and {b2} may be live "
                    "concurrently under the multi-core schedule"
                )
