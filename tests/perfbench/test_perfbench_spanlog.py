"""The readers of the program's span log: the reduction on a hand-made
log and trace (a planted clock offset, a planted idle gap), what a
program without the log reads as, and the tiny cell's traced rehearsal
through the ten metrics' own files."""

import json
import os

import pytest

import perfbench_tiny as tiny
from perfbench import harness
from perfbench.sources import device_trace as dt
from perfbench.sources import program_span
from perfbench.sources import program_spanlog as sl
from triton_dist_tpu.obs.spans import SpanRecord

REPO = tiny.REPO
SPAN_METRICS = {
    "sched.admit_p50_ms": 5.0, "sched.assemble_p50_ms": 5.0,
    "sched.keys_p50_ms": 10.0, "sched.emit_p50_ms": 5.0,
    "sched.observe_p50_ms": 4.0, "worker.dispatch_p50_ms": 4.0,
    "worker.wait_p50_ms": 66.0}
COUNTER_METRICS = {"sched.prefill_rows_pct": 25.0,
                   "kv.gather_live_pct": 12.5}
NEW = sorted(SPAN_METRICS) + sorted(COUNTER_METRICS) \
    + ["device.idle_named_pct"]
OFFSET = 1000.5  # trace clock minus perf_counter, planted
PERIOD = 0.1
# one round, as (name, parent's name, start, end) within its period
ROUND = [("sched.step", None, 0.000, 0.100),
         ("sched.admit", "sched.step", 0.000, 0.005),
         ("sched.assemble", "sched.step", 0.005, 0.020),
         ("sched.keys", "sched.assemble", 0.008, 0.018),
         ("worker.step", "sched.step", 0.020, 0.090),
         ("worker.put", "worker.step", 0.020, 0.022),
         ("worker.launch", "worker.step", 0.022, 0.024),
         ("worker.wait", "worker.step", 0.024, 0.090),
         ("sched.emit", "sched.step", 0.090, 0.095),
         ("sched.observe", "sched.step", 0.095, 0.099)]
DEVICE_BUSY = (0.030, 0.085)  # within each period, on the log's clock


class FakeLog:
    def __init__(self, records):
        self._records = records

    def records(self):
        return list(self._records)


def ns(t):
    return round(t * 1e9)


def make_log(steps, t_first=50.0):
    """`steps` rounds of ROUND back to back, children recorded before
    their parents as the program's log has them, and a request phase
    lying across all of them."""
    records, next_id = [], 0
    for k in range(steps):
        base = t_first + k * PERIOD
        ids = {}
        for name, _parent, _a, _b in ROUND:
            ids[name] = next_id
            next_id += 1
        for name, parent, a, b in reversed(ROUND):
            records.append(SpanRecord(
                ids[name], ids.get(parent), name, ns(base + a), ns(base + b),
                k, None))
    records.append(SpanRecord(next_id, None, "req.decode", ns(t_first),
                              ns(t_first + steps * PERIOD), None, 0))
    return FakeLog(records)


def make_trace(steps, t_first=50.0, jitter=None):
    """A trace of rounds `steps` (indices) of the log above: the
    harness's worker_step event opens 2 us before the program's span,
    one device operation a step, all on the trace's clock."""
    host, ops = [], []
    for i, k in enumerate(steps):
        base = t_first + k * PERIOD + OFFSET
        j = jitter[i] if jitter else 0.0
        host.append((sl.WORKER_STEP_EVENT, base + 0.020 - 2e-6 + j,
                     base + 0.090 + 2e-6))
        ops.append(("fusion.1 bf16[8,8]", base + DEVICE_BUSY[0],
                    base + DEVICE_BUSY[1]))
    return dt.Trace(ops={"/device:TPU:0": ops}, modules={}, host=host)


def view(log_steps=6, traced=(2, 3, 4), counters=None, trace=True):
    steps = [program_span.Step(50.0 + k * PERIOD + 0.020,
                               50.0 + k * PERIOD + 0.090, [])
             for k in range(log_steps)]
    lines = []
    return harness.RunView(
        t0=50.0, t1=50.0 + log_steps * PERIOD, steps=steps,
        trace=make_trace(traced) if trace else None,
        trace_steps=[steps[k] for k in traced] if trace else [],
        counters=counters if counters is not None else {
            "serve_rows{state=prefill}": 30, "serve_rows{state=decode}": 90,
            "serve_kv_tokens_live": 64, "serve_kv_tokens_gathered": 512},
        say=lines.append, lines=lines)


@pytest.fixture
def planted(monkeypatch):
    log = make_log(6)
    monkeypatch.setattr(sl, "program_log", lambda: log)
    return log


# ---------- the reduction ----------


def test_spans_of_keeps_what_lies_inside_the_window_as_seconds():
    log = make_log(4)
    inside = sl.spans_of(log, 50.1, 50.3)
    assert {s.step for s in inside} == {1, 2}
    assert len(inside) == 2 * len(ROUND)  # the request phase lies across
    root = next(s for s in inside if s.name == "sched.step" and s.step == 1)
    assert (root.t0, root.t1) == (pytest.approx(50.1), pytest.approx(50.2))


def test_self_time_is_duration_less_the_children_by_step():
    per = sl.self_ms_by_name_per_step(sl.spans_of(make_log(3), 0.0, 1e9))
    for step in range(3):
        assert per["sched.assemble"][step] == pytest.approx(5.0)
        assert per["sched.keys"][step] == pytest.approx(10.0)
        assert per["worker.step"][step] == pytest.approx(0.0, abs=1e-6)
        assert per["sched.step"][step] == pytest.approx(1.0)
        assert sum(per[name][step] for name, *_ in ROUND) \
            == pytest.approx(1e3 * PERIOD)
    assert "req.decode" not in per  # no step: not a round's span


def test_a_planted_clock_offset_is_recovered():
    spans = [s for s in sl.spans_of(make_log(8), 0.0, 1e9)
             if s.step in (3, 4, 5)]
    jitter = [0.0, 3e-6, -2e-6]
    offset, spread = sl.clock_offset(make_trace((3, 4, 5), jitter=jitter),
                                     spans)
    assert offset == pytest.approx(OFFSET - 2e-6, abs=1e-6)
    assert spread == pytest.approx(5e-6, abs=1e-7)


@pytest.mark.parametrize("events,spans", [((2, 3, 4), (0, 1, 2, 3, 4, 5)),
                                          ((1, 2, 3, 4, 5), (2, 3, 4)),
                                          ((0, 1, 2), (0, 1, 2))])
def test_the_match_is_the_run_that_agrees_best(events, spans):
    """Periods that differ by milliseconds, as a server's do: an event
    more at either end of the trace, or spans beside the traced steps,
    and only the right run agrees to microseconds."""
    starts = [50.0, 50.11, 50.23, 50.33, 50.46, 50.57]
    records = [SpanRecord(k, None, "worker.step", ns(starts[k]),
                          ns(starts[k] + 0.07), k, None) for k in spans]
    trace = dt.Trace({}, {}, [
        (sl.WORKER_STEP_EVENT, starts[k] + OFFSET - 1e-6,
         starts[k] + OFFSET + 0.07) for k in events])
    offset, spread = sl.clock_offset(
        trace, sl.spans_of(FakeLog(records), 0.0, 1e9))
    assert offset == pytest.approx(OFFSET - 1e-6, abs=1e-7)
    assert spread < 1e-6


@pytest.mark.parametrize("case", ["no events", "no spans", "one span",
                                  "disagree"])
def test_no_tie_where_the_clocks_cannot_be_matched(case):
    spans = [s for s in sl.spans_of(make_log(6), 0.0, 1e9)
             if s.step in (2, 3, 4)]
    trace = make_trace((2, 3, 4))
    if case == "no events":
        trace = dt.Trace(trace.ops, {}, [])
    elif case == "no spans":
        spans = []
    elif case == "one span":
        spans = [s for s in spans if s.step == 3]
    else:  # another program's steps: 2 ms apart where these are 100
        trace = make_trace((2, 3, 4), jitter=[0.0, 2e-3, -2e-3])
    assert sl.clock_offset(trace, spans) is None


def test_a_planted_idle_gap_is_split_among_the_innermost_spans():
    spans = sl.spans_of(make_log(6), 0.0, 1e9)
    idle = sl.idle_by_span(make_trace((2, 3, 4)), spans, OFFSET)
    # two gaps, each from a step's last operation (0.085) to the next
    # step's first (0.130), crossing ten spans
    want = {"worker.wait": 0.005 + 0.006, "sched.emit": 0.005,
            "sched.observe": 0.004, "sched.step": 0.001,
            "sched.admit": 0.005, "sched.assemble": 0.005,
            "sched.keys": 0.010, "worker.put": 0.002,
            "worker.launch": 0.002}
    assert set(idle) == set(want)
    for name, s in want.items():
        assert idle[name] == pytest.approx(2 * s, abs=1e-9), name
    assert sum(idle.values()) == pytest.approx(2 * 0.045)
    # the harness's own reduction gives each whole gap to one name
    assert dict(dt.idle_gaps(make_trace((2, 3, 4)))) == {
        "outside any span": pytest.approx(2 * 0.045)}


def test_idle_named_falls_when_host_work_lies_outside_the_phases(
        monkeypatch):
    """sched.observe's span taken away: its 4 ms of every round become
    the root's own, and a fifth of the host's idle loses its name."""
    log = FakeLog([r for r in make_log(6).records()
                   if r.name != "sched.observe"])
    monkeypatch.setattr(sl, "program_log", lambda: log)
    got = harness.load_reader(REPO, "device.idle_named_pct").read(view())
    assert got == pytest.approx(100.0 * (0.034 - 0.005) / 0.034)


def test_idle_no_span_covers_is_outside_any_span():
    spans = [s for s in sl.spans_of(make_log(6), 0.0, 1e9) if s.step != 3]
    idle = sl.idle_by_span(make_trace((2, 3, 4)), spans, OFFSET)
    # round 3 is gone: 0.015 of the first gap and 0.030 of the second
    assert idle[sl.OUTSIDE] == pytest.approx(0.045)
    assert sum(idle.values()) == pytest.approx(2 * 0.045)
    assert sl.idle_by_span(dt.Trace({}, {}, []), spans, OFFSET) == {}


# ---------- the ten readers ----------


@pytest.mark.parametrize("metric", sorted(SPAN_METRICS))
def test_a_phase_reader_gives_the_median_self_time(planted, metric):
    assert harness.load_reader(REPO, metric).read(view()) \
        == pytest.approx(SPAN_METRICS[metric])


@pytest.mark.parametrize("metric", sorted(COUNTER_METRICS))
def test_a_counter_reader_gives_the_share(planted, metric):
    assert harness.load_reader(REPO, metric).read(view()) \
        == pytest.approx(COUNTER_METRICS[metric])


def test_idle_named_is_the_named_share_of_the_idle_the_host_causes(
        planted):
    run = view()
    got = harness.load_reader(REPO, "device.idle_named_pct").read(run)
    # a gap's 0.011 inside worker.wait is the device's own; of the
    # other 0.034 the root's 0.001 has no phase's name
    assert got == pytest.approx(100.0 * (0.034 - 0.001) / 0.034)
    said = "\n".join(run.lines)
    assert "disagree by 0.0us" in said and "sched.keys 0.0200s" in said


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_log_or_the_counters_reads_as_nothing(
        monkeypatch, metric):
    monkeypatch.setattr(sl, "program_log", lambda: None)
    run = view(counters={"serve_steps": 7})
    assert harness.load_reader(REPO, metric).read(run) is None


@pytest.mark.parametrize("metric", sorted(SPAN_METRICS)
                         + ["device.idle_named_pct"])
def test_an_empty_or_misaligned_log_reads_as_nothing(monkeypatch, metric):
    reader = harness.load_reader(REPO, metric)
    monkeypatch.setattr(sl, "program_log", lambda: FakeLog([]))
    assert reader.read(view()) is None
    # a log from another stretch of the clock than the window's
    late = make_log(6, t_first=500.0)
    monkeypatch.setattr(sl, "program_log", lambda: late)
    assert reader.read(view()) is None


def test_idle_named_says_nothing_without_a_trace_or_a_device_plane(planted):
    reader = harness.load_reader(REPO, "device.idle_named_pct")
    assert reader.read(view(trace=False)) is None
    run = view()
    run.trace = dt.Trace({}, {}, run.trace.host)  # a CPU's trace
    assert reader.read(run) is None


# ---------- BENCHMARK.json and the tiny cell ----------


def test_the_ten_entries_list_every_cell_and_move_the_gap():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-10:] == [
        "sched.admit_p50_ms", "sched.assemble_p50_ms", "sched.keys_p50_ms",
        "sched.emit_p50_ms", "sched.observe_p50_ms",
        "worker.dispatch_p50_ms", "worker.wait_p50_ms",
        "device.idle_named_pct", "sched.prefill_rows_pct",
        "kv.gather_live_pct"]
    for name in NEW:
        m = entries[name]
        assert m["moves"] == "itl_p95_ms" and m["workloads"] == cells
        assert m["unit"] == ("ms" if name.endswith("_ms") else "%")
    assert entries["kv.gather_live_pct"]["layer"] == "KV pool"
    assert entries["device.idle_named_pct"]["source"] == "device_trace"


def test_tiny_traced_rehearsal_reports_the_nine_without_a_device_plane(
        tmp_path):
    root, bench, cell = tiny.make_root(tmp_path)
    traced, lines = tiny.rehearse(root, bench, cell, trace=True)
    got = traced["metrics"]
    assert traced["correct"]
    for name in sorted(SPAN_METRICS) + sorted(COUNTER_METRICS):
        assert got[name]["value"] > 0, name
    # the CPU has no device plane: nothing to split, and never 0
    assert "device.idle_named_pct" not in got
    assert any(line.startswith("span log:") for line in lines)
    for name in COUNTER_METRICS:
        assert 0 < got[name]["value"] < 100
    # the inside lies within the outside: in every step put, launch and
    # wait nest between the history's stamps, so neither median passes
    # the wall's (an order, not a timing: a loaded machine keeps it;
    # tests/test_spans.py holds each step's span to its history entry)
    wall = got["step.wall_p50_ms"]["value"]
    assert got["worker.wait_p50_ms"]["value"] <= wall
    assert got["worker.dispatch_p50_ms"]["value"] <= wall
