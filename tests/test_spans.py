"""The serve plane's span log (obs/spans.py): the ring itself, the
spans and counters the host loop and the worker write at every step,
the views `timeline()` / `write_request_trace` read, and the `tdt.*`
annotations a profiler session sees."""

import glob
import statistics
import threading
import time

import numpy as np
import pytest

from triton_dist_tpu.models import Engine, ModelConfig
from triton_dist_tpu.obs.spans import SpanLog, default_log
from triton_dist_tpu.runtime import make_mesh
from triton_dist_tpu.serve import Scheduler

GEO = dict(slots=3, chunk=4, page=8)
ROUND = ["sched.admit", "sched.assemble", "worker.step", "sched.emit",
         "sched.observe"]
NESTED = {"sched.assemble": ["sched.keys"],
          "worker.step": ["worker.put", "worker.launch", "worker.wait"]}
SPANS_A_STEP = 1 + len(ROUND) + sum(len(v) for v in NESTED.values())


@pytest.fixture(scope="module")
def eng1():
    mesh = make_mesh(mesh_shape=(1,), axis_names=("tp",))
    cfg = ModelConfig.tiny(num_q_heads=4, num_kv_heads=2, max_positions=64)
    return Engine(cfg, mesh, decode_mode="ar", max_len=64,
                  donate_cache=False)


def _prompts(eng, lens=(12, 10, 9), seed=1):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(0, eng.cfg.vocab_size, n)))
            for n in lens]


@pytest.fixture(scope="module")
def served(eng1):
    """One synchronous host-loop run: (scheduler, requests, its
    log's records)."""
    sch = Scheduler(eng1, **GEO)
    reqs = [sch.submit(p, max_new_tokens=5) for p in _prompts(eng1)]
    sch.run()
    return sch, reqs, sch.spans.records()


def _children(records, parent):
    return sorted((r for r in records if r.parent == parent.id),
                  key=lambda r: r.t0_ns)


# ---------- the ring ----------


def test_span_nests_by_thread_and_stamps_in_order():
    log = SpanLog()
    with log.span("outer", step=3):
        with log.span("inner", step=3, request=7):
            pass
        log.add("mark", 5, 5, request=7)
    inner, mark, outer = log.records()
    assert (inner.name, mark.name, outer.name) == ("inner", "mark", "outer")
    assert inner.parent == outer.id and outer.parent is None
    assert mark.parent is None  # add() records are roots
    assert outer.t0_ns <= inner.t0_ns <= inner.t1_ns <= outer.t1_ns
    assert (inner.step, inner.request, outer.request) == (3, 7, None)
    assert len({inner.id, mark.id, outer.id}) == 3


def test_span_closes_on_an_exception_and_unwinds_the_stack():
    log = SpanLog()
    with pytest.raises(ValueError):
        with log.span("outer"):
            with log.span("inner"):
                raise ValueError("boom")
    assert [r.name for r in log.records()] == ["inner", "outer"]
    with log.span("next"):
        pass
    assert log.records()[-1].parent is None


def test_another_thread_s_span_is_no_child_of_this_one():
    log = SpanLog()
    with log.span("serving"):
        t = threading.Thread(target=lambda: log.span("client").__enter__()
                             .__exit__(None, None, None))
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    client = next(r for r in log.records() if r.name == "client")
    assert client.parent is None


def test_the_ring_drops_the_oldest_at_cap_and_counts_it():
    log = SpanLog(cap=4)
    for i in range(7):
        log.add("mark", i, i)
    assert len(log) == 4 and log.dropped == 3
    assert [r.t0_ns for r in log.records()] == [3, 4, 5, 6]


def test_triples_put_the_request_s_id_back_into_the_name():
    log = SpanLog()
    log.add("req.prefill", 1, 2, request=4)
    log.add("req.evicted", 2, 2, step=9, request=4)
    log.add("step.retry", 3, 4, step=9)
    log.add("sched.idle", 9, 10, step=12)
    with log.span("sched.step", step=12):
        pass
    names = [n for n, _t0, _t1 in log.triples()]
    assert names == ["req4/prefill", "req4/evicted", "step.retry",
                     "sched.idle", "sched.step"]
    assert log.triples()[0][1:] == (1, 2)


def test_each_scheduler_has_a_log_and_the_default_is_the_newest(eng1):
    sch_a = Scheduler(eng1, **GEO)
    assert default_log() is sch_a.spans is sch_a.worker.spans
    sch_b = Scheduler(eng1, **GEO)
    assert sch_b.spans is not sch_a.spans
    assert default_log() is sch_b.spans is default_log()
    # the log, not the scheduler, is what is held: it outlives a
    # freed scheduler for a reader that runs afterwards
    del sch_b
    assert len(default_log()) == 0 and default_log() is not sch_a.spans


# ---------- the host loop's spans ----------


def test_every_device_step_is_one_root_with_the_named_children(served):
    sch, _reqs, records = served
    roots = [r for r in records if r.name == "sched.step"]
    steps = [h["step"] for h in sch.history]
    assert [r.step for r in roots] == steps == list(range(len(steps)))
    for root in roots:
        assert root.parent is None
        kids = _children(records, root)
        assert [k.name for k in kids] == ROUND
        last = root.t0_ns
        for k in kids:  # nested inside the root, one after another
            assert last <= k.t0_ns <= k.t1_ns <= root.t1_ns
            last = k.t1_ns
            assert [g.name for g in _children(records, k)] == \
                NESTED.get(k.name, [])
            assert all(k.t0_ns <= g.t0_ns <= g.t1_ns <= k.t1_ns
                       and g.step == root.step
                       for g in _children(records, k))


def test_self_times_sum_to_the_root_s_duration(served):
    _sch, _reqs, records = served
    for root in (r for r in records if r.name == "sched.step"):
        family = [root]
        for k in _children(records, root):
            family += [k] + _children(records, k)

        def own(r):
            return (r.t1_ns - r.t0_ns) - sum(
                c.t1_ns - c.t0_ns for c in _children(records, r))

        assert all(own(r) >= 0 for r in family)
        assert sum(own(r) for r in family) == root.t1_ns - root.t0_ns


def test_worker_step_span_is_the_history_entry_of_its_step(served):
    sch, _reqs, records = served
    spans = {r.step: r for r in records if r.name == "worker.step"}
    assert sorted(spans) == [h["step"] for h in sch.history]
    apart = []
    for h in sch.history:
        s = spans[h["step"]]
        # the history's stamps (`_attempt_span`) lie just outside the
        # span's: a lambda call and a context-manager entry apart
        assert h["t0"] <= s.t0_ns <= s.t1_ns <= h["t1"]
        apart.append((s.t0_ns - h["t0"]) + (h["t1"] - s.t1_ns))
    # microseconds; the median, so that one preempted step of a busy
    # test machine does not count
    assert statistics.median(apart) < 1_000_000


def test_request_and_step_fields_are_filled(served):
    sch, reqs, records = served
    for r in records:
        if r.name.startswith(("sched.", "worker.")):
            assert r.step is not None and r.request is None
        elif r.name.startswith("req."):
            assert r.request in {q.request_id for q in reqs}
    for q in reqs:
        mine = {r.name for r in records if r.request == q.request_id}
        assert {"req.queued", "req.prefill", "req.decode"} <= mine
        for phase in ("queued", "prefill", "decode"):
            total = sum(r.t1_ns - r.t0_ns for r in records
                        if r.request == q.request_id
                        and r.name == "req." + phase)
            assert total == q.phase_ns[phase]


def test_two_schedulers_on_the_default_keep_their_exports_apart(
        eng1, served, tmp_path):
    """Request ids and step indices start at 0 in every scheduler: a
    second one built beside the first must not land on its tracks."""
    from triton_dist_tpu import trace
    from triton_dist_tpu.trace.export import load_trace_json

    sch_a = served[0]
    before = sch_a.spans.records()
    sch_b = Scheduler(eng1, **GEO)
    req_b = sch_b.submit(_prompts(eng1)[0], max_new_tokens=2)
    sch_b.run()
    assert req_b.request_id in {q.request_id for q in sch_a.requests}
    assert sch_a.spans.records() == before
    mine = sch_b.spans.records()
    assert {r.request for r in mine} == {None, req_b.request_id}
    assert sorted(r.step for r in mine if r.name == "sched.step") == \
        list(range(len(sch_b.history)))
    assert len(sch_b.timeline().host_spans) == len(mine)
    assert sch_a.timeline().host_spans == sch_a.spans.triples()
    path = trace.write_request_trace(sch_b, str(tmp_path / "b.json"))
    events = load_trace_json(path)["traceEvents"]
    assert {e["args"]["name"] for e in events if e.get("ph") == "M"} == \
        {"serve", f"req{req_b.request_id}"}
    assert sum(1 for e in events if e.get("ph") == "X"
               and e["name"] == "worker.wait") == len(sch_b.history)


def test_an_idle_decode_slice_polls_its_channel_and_leaves_no_span(eng1):
    from triton_dist_tpu.xslice.migrate import MigrationChannel

    sch = Scheduler(eng1, role="decode", admit_from=MigrationChannel(),
                    **GEO)
    for _ in range(5):
        assert sch.step() is False
    assert len(sch.spans) == 0


def test_a_step_appends_a_fixed_number_of_spans_and_no_jax_call(
        eng1, monkeypatch):
    """Counts, not timings: decode-only steps each append the same
    records, and the log adds no jax call to a step — the same step
    with the log's spans switched to no-ops makes as many."""
    import contextlib

    import jax
    import jax.numpy as jnp

    calls = {"n": 0}

    def counting(fn):
        def wrapped(*a, **kw):
            calls["n"] += 1
            return fn(*a, **kw)
        return wrapped

    for mod, name in ((jnp, "asarray"), (jax.random, "PRNGKey"),
                      (jax.random, "fold_in"), (jax, "device_put")):
        monkeypatch.setattr(mod, name, counting(getattr(mod, name)))

    class NoSpans(SpanLog):
        def span(self, name, step=None, request=None, counts=None):
            return contextlib.nullcontext()

    per_step = {}
    for kind, log in (("on", SpanLog()), ("off", NoSpans())):
        sch = Scheduler(eng1, **GEO)
        sch.spans = sch.worker.spans = log
        for p in _prompts(eng1, lens=(3, 3, 3)):
            sch.submit(p, max_new_tokens=6)
        sch.step()  # the prompts' one prefill chunk each
        counts = []
        for _ in range(3):  # decode-only steps, nothing retires
            n0, c0 = len(log), calls["n"]
            assert sch.step()
            counts.append((len(log) - n0, calls["n"] - c0))
        assert len(set(counts)) == 1, counts
        per_step[kind] = counts[0]
    assert per_step["on"][0] == SPANS_A_STEP
    assert per_step["off"][0] == 0
    assert per_step["on"][1] == per_step["off"][1] > 0


def test_counters_equal_the_hand_count_from_history(served):
    sch, _reqs, _records = served
    c = sch.obs.snapshot()["counters"]
    rows = {"prefill": 0, "decode": 0}
    live = 0
    ctx = {}
    prompt_len = {q.request_id: len(q.prompt) for q in sch.requests}
    for h in sch.history:
        for _slot, (rid, state, n) in h["slots"].items():
            rows[state] += n
            ctx[rid] = ctx.get(rid, 0) + n
            live += ctx[rid]  # the slot's pool length after the step
    steps = len(sch.history)
    pool = sch.pool
    assert c["serve_rows{state=prefill}"] == rows["prefill"] \
        == sum(prompt_len.values())
    assert c["serve_rows{state=decode}"] == rows["decode"]
    assert c["serve_kv_tokens_live"] == live
    # what the KV layer says its dense view gathers is what the step's
    # view of this pool's table holds
    view = pool.to_dense()
    assert pool.dense_view_tokens() == view.k.shape[1] * view.k.shape[2]
    assert c["serve_kv_tokens_gathered"] == \
        steps * pool.dense_view_tokens()
    assert 0 < live < c["serve_kv_tokens_gathered"]


def test_a_retry_and_a_failed_attempt_land_in_the_log(eng1):
    from triton_dist_tpu import faults

    sch = Scheduler(eng1, retry_backoff_s=0.0005, **GEO)
    sch.submit(_prompts(eng1)[0], max_new_tokens=3)
    with faults.injecting(faults.FaultPlan(
            faults.FailStep(at_step=1, times=1))):
        sch.run()
    records = sch.spans.records()
    retry = [r for r in records if r.name == "step.retry"]
    assert len(retry) == 1 and retry[0].step == 1
    # the failed attempt closed its worker.step span too
    assert sum(1 for r in records
               if r.name == "worker.step" and r.step == 1) == 2
    assert sum(1 for n, _t0, _t1 in sch.timeline().host_spans
               if n == "step.retry") == 1


# ---------- the views ----------


def test_timeline_and_request_trace_read_the_log(served, tmp_path):
    from triton_dist_tpu import trace
    from triton_dist_tpu.trace.export import load_trace_json

    sch, reqs, records = served
    tl = sch.timeline()
    assert tl.host_spans == sch.spans.triples()
    names = [n for n, _t0, _t1 in tl.host_spans]
    for q in reqs:
        for phase in ("queued", "prefill", "decode"):
            assert f"req{q.request_id}/{phase}" in names
    assert names.count("sched.step") == len(sch.history)
    path = trace.write_trace(tl, str(tmp_path / "serve.trace.json"))
    assert trace.load_trace_json(path)["traceEvents"]

    path = trace.write_request_trace(sch, str(tmp_path / "req.trace.json"))
    events = load_trace_json(path)["traceEvents"]
    tracks = {e["pid"]: e["args"]["name"] for e in events
              if e.get("ph") == "M"}
    assert set(tracks.values()) == {"serve"} | {
        f"req{q.request_id}" for q in reqs}
    by_track = {}
    for e in events:
        if e.get("ph") == "X":
            by_track.setdefault(tracks[e["pid"]], []).append(e["name"])
    for q in reqs:  # a request's phases, under their bare names
        assert set(by_track[f"req{q.request_id}"]) == {
            "queued", "prefill", "decode"}
    assert by_track["serve"].count("worker.wait") == len(sch.history)


def test_trace_session_host_spans_are_a_span_log():
    from triton_dist_tpu.trace.collect import TraceSession

    sess = TraceSession("unit")
    with sess.host_span("region"):
        time.sleep(0.001)
    assert isinstance(sess.log, SpanLog)
    tl = sess.assemble({})
    (name, t0, t1), = tl.host_spans
    assert name == "region" and t1 - t0 >= 1_000_000


# ---------- the serving thread ----------


def test_an_idle_stretch_is_one_span_and_idle_rounds_leave_none(eng1):
    sch = Scheduler(eng1, **GEO)
    sch.start()
    try:
        time.sleep(0.05)  # some twenty rounds with nothing to do
        assert len(sch.spans) == 0
        req = sch.submit(_prompts(eng1)[0], max_new_tokens=2, stream=True)
        assert len(list(req.stream)) == 2
    finally:
        sch.stop()
    records = sch.spans.records()
    idle = [r for r in records if r.name == "sched.idle"]
    roots = [r for r in records if r.name == "sched.step"]
    assert len(roots) == len(sch.history)
    # the stretch before the request, and the one after it if stop()
    # left the loop the time to go round once more
    assert 1 <= len(idle) <= 2 and idle[0].parent is None
    # most of the 50 ms (the rest is the thread's start on a busy machine)
    assert idle[0].t1_ns - idle[0].t0_ns >= 20_000_000
    assert idle[0].t1_ns <= roots[0].t0_ns
    assert all(i.t0_ns >= roots[-1].t1_ns for i in idle[1:])


def test_retired_requests_beyond_the_cap_are_dropped_and_counted(eng1):
    sch = Scheduler(eng1, **GEO)
    sch.requests_cap = 2
    prompts = _prompts(eng1, lens=(3, 4, 5, 6))
    first = [sch.submit(p, max_new_tokens=1) for p in prompts[:3]]
    # nothing has retired: a live request is never dropped
    assert sch.requests == first and sch.requests_dropped == 0
    sch.run()
    assert all(r.done for r in first)
    last = sch.submit(prompts[3], max_new_tokens=1)
    assert sch.requests == first[1:] + [last]
    assert sch.requests_dropped == 1
    sch.run()
    assert last.done and sch.metrics()["submitted"] == 4


# ---------- on the profiler's clock ----------


def test_a_profiler_session_sees_the_spans_as_tdt_events(eng1, tmp_path):
    """The harness discards its profile, so only a session of the
    test's own can show that the program's spans reach one."""
    import jax
    from jax.profiler import ProfileData

    sch = Scheduler(eng1, **GEO)
    sch.submit(_prompts(eng1)[0], max_new_tokens=4)
    sch.step()  # before the session: no event
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        n0 = sch.worker.n_steps
        sch.run()
        traced = sch.worker.n_steps - n0
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    assert files
    data = ProfileData.from_file(files[0])
    events = {}
    for plane in data.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("tdt."):
                        events.setdefault(e.name, []).append(e)
    assert traced >= 3
    assert len(events["tdt.sched.step"]) == traced
    assert len(events["tdt.worker.wait"]) == traced
    assert set(events) >= {"tdt." + n for n in
                           ROUND + NESTED["sched.assemble"]
                           + NESTED["worker.step"]}
    # one clock for both: the log's spans and the profile's events
    # have the same lengths (an event is stamped just outside its span)
    waits = sorted(r.t1_ns - r.t0_ns for r in sch.spans.records()
                   if r.name == "worker.wait" and r.step >= n0)
    seen = sorted(e.duration_ns for e in events["tdt.worker.wait"])
    assert len(waits) == len(seen)
    assert statistics.median(
        abs(a - b) for a, b in zip(waits, seen)) < 1_000_000


# ---------- what a record counts (ISSUE 40) ----------


def test_counts_are_kept_by_records_and_ignored_by_triples():
    log = SpanLog()
    with log.span("worker.step", step=2, counts={"width": 4, "rows": 9}):
        with log.span("worker.put", step=2):
            pass
    log.add("step.retry", 5, 6, step=2, counts={"attempt": 1})
    log.add("mark", 7, 7)
    put, step, retry, mark = log.records()
    assert step.counts == {"width": 4, "rows": 9}
    assert retry.counts == {"attempt": 1}
    assert put.counts is None and mark.counts is None  # the default
    assert step[-1] is step.counts and len(step) == 8  # the one new field
    assert log.triples() == [(r.name, r.t0_ns, r.t1_ns)
                             for r in (put, step, retry, mark)]


def _steps_agree_with_history(sch):
    spans = {r.step: r for r in sch.spans.records()
             if r.name == "worker.step"}
    steps = [h for h in sch.history if h.get("kind") == "step"]
    assert steps and sorted(spans) == [h["step"] for h in steps]
    for h in steps:
        assert spans[h["step"]].counts == {
            "width": h["width"],
            "rows": sum(n for _rid, _state, n in h["slots"].values())}
    return {h["width"] for h in steps}


def test_worker_step_counts_the_width_and_rows_history_has(served):
    sch, _reqs, records = served
    # prompts of 9-12 tokens in chunks of 4, then decode rows alone:
    # both compiled widths ran
    assert _steps_agree_with_history(sch) == {1, GEO["chunk"]}
    others = [r for r in records if r.name != "worker.step"]
    assert others and all(r.counts is None or r.name.startswith("jit.")
                          for r in others)


def test_a_hybrid_worker_step_counts_its_one_width():
    mesh = make_mesh(mesh_shape=(1,), axis_names=("tp",))
    cfg = ModelConfig.tiny_next(max_positions=64)
    eng = Engine(cfg, mesh, max_len=64, fast_init=True)
    sch = Scheduler(eng, slots=2, page=8)
    for p in _prompts(eng, lens=(7, 5)):
        sch.submit(p, max_new_tokens=3)
    sch.run()
    assert _steps_agree_with_history(sch) == {sch.chunk}


# ---------- jit.* records (ISSUE 40) ----------


def _jit_records(log, fun):
    return [r for r in log.records() if r.name.startswith("jit.")
            and r.counts and fun in r.counts.get("fun", "")]


def test_a_function_jitted_here_leaves_jit_records_in_the_default_log(eng1):
    import jax
    import jax.numpy as jnp

    sch = Scheduler(eng1, **GEO)  # makes the default log, installs
    log = default_log()
    assert log is sch.spans

    @jax.jit
    def a_function_of_this_test(x):
        return jnp.tanh(x) * 3.0

    before = time.perf_counter_ns()
    a_function_of_this_test(jnp.ones((5, 7))).block_until_ready()
    after = time.perf_counter_ns()
    mine = _jit_records(log, "a_function_of_this_test")
    names = [r.name for r in mine]
    assert "jit.trace" in names and "jit.lower" in names
    assert ("jit.compile" in names) != ("jit.cache_load" in names)
    for r in mine:
        assert r.parent is None and r.request is None  # roots, as add()'s
        assert r.t0_ns <= r.t1_ns and before <= r.t1_ns <= after
        assert r.step == log.step  # None: this scheduler ran no step
    # outside `sched.` / `worker.`: the idle split leaves them out
    assert not any(r.name.startswith(("sched.", "worker."))
                   for r in mine)
    # a second call is a cache hit in memory: nothing new
    a_function_of_this_test(jnp.ones((5, 7))).block_until_ready()
    assert len(_jit_records(log, "a_function_of_this_test")) == len(mine)


def test_only_the_outermost_trace_is_recorded():
    """JAX reports a trace for every jitted helper the traced function
    calls, each inside the outer one's seconds: one record, whose
    seconds are the whole of the tracing."""
    import jax
    import jax.numpy as jnp

    from triton_dist_tpu.obs import spans

    heard = []

    def every_report(event, seconds, **kw):
        if event.endswith("jaxpr_trace_duration"):
            heard.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(every_report)

    @jax.jit
    def a_helper_of_this_test(x):
        return jnp.where(x > 0, jnp.sin(x), 0.0)

    @jax.jit
    def the_outer_function_of_this_test(x):
        return a_helper_of_this_test(x).sum() + a_helper_of_this_test(2 * x)

    x = jnp.ones((3, 5))  # its own helpers are traced before the log
    log = spans.new_default_log()
    before = time.perf_counter_ns()
    try:
        the_outer_function_of_this_test(x).block_until_ready()
    finally:
        from jax._src import monitoring

        monitoring.unregister_event_duration_listener(every_report)
    wall = time.perf_counter_ns() - before
    traced = [r for r in log.records() if r.name == "jit.trace"]
    assert [r.counts["fun"] for r in traced] == [
        "the_outer_function_of_this_test"]
    # JAX told of the helper and of jnp's own, inside the outer's time
    assert "a_helper_of_this_test" in heard and len(heard) > 3
    assert sum(r.t1_ns - r.t0_ns for r in log.records()
               if r.name.startswith("jit.")) <= wall
    # the helper, called from the top, is an outermost trace itself
    a_helper_of_this_test(jnp.ones((2,))).block_until_ready()
    assert [r.counts["fun"] for r in log.records()
            if r.name == "jit.trace"][-1] == "a_helper_of_this_test"


def test_an_unrelated_event_leaves_no_record_and_nothing_raises():
    import jax

    from triton_dist_tpu.obs import spans

    spans.install_jit_listener()
    log = spans.new_default_log()
    jax.monitoring.record_event_duration_secs(
        "/jax/some/other/duration", 1.5, fun_name="f")
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    assert len(log) == 0
    # one of the four, without a name and with one that is no string
    jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/jaxpr_trace_duration", 0.25)
    jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/jaxpr_to_mlir_module_duration", 0.5, fun_name=7)
    trace, lower = log.records()
    assert (trace.name, trace.counts) == ("jit.trace", None)
    assert (lower.name, lower.counts) == ("jit.lower", {"fun": "7"})
    assert trace.t1_ns - trace.t0_ns == 250_000_000
    # a broken log must not reach JAX's compile
    spans._default = None
    try:
        jax.monitoring.record_event_duration_secs(
            "/jax/core/compile/backend_compile_duration", 0.1, fun_name="f")
    finally:
        spans.new_default_log()


def test_a_cache_hit_is_a_cache_load_with_the_function_s_name():
    import jax

    from triton_dist_tpu.obs import spans

    log = spans.new_default_log()
    record = jax.monitoring.record_event_duration_secs
    record("/jax/compilation_cache/cache_retrieval_time_sec", 0.75)
    assert len(log) == 0  # the hit has no name yet
    record("/jax/core/compile/backend_compile_duration", 1.0,
           fun_name="jit(step)")
    record("/jax/core/compile/backend_compile_duration", 9.0,
           fun_name="jit(other)")
    load, compiled = log.records()
    assert (load.name, load.counts) == ("jit.cache_load",
                                        {"fun": "jit(step)"})
    assert (compiled.name, compiled.counts) == ("jit.compile",
                                                {"fun": "jit(other)"})


def test_a_listener_installed_twice_writes_once():
    import jax

    from triton_dist_tpu.obs import spans

    def mine():
        from jax._src import monitoring

        return [cb for cb in monitoring.get_event_duration_listeners()
                if cb is spans._on_jit_duration]

    spans.install_jit_listener()
    spans.install_jit_listener()
    log = spans.new_default_log()  # installs too
    assert len(mine()) == 1
    jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/jaxpr_trace_duration", 0.5, fun_name="g")
    assert [r.name for r in log.records()] == ["jit.trace"]
