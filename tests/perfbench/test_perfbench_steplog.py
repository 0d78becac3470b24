"""The readers of what the program's `worker.step` records COUNT
(ISSUE 40): `step.wide_wall_p50_ms`, `step.narrow_wall_p50_ms` and
`step.wide_rows_valid_pct` over hand-made logs (two widths, one width,
records without counts), their entries (which wait as data in
`perfbench/fixtures/per_layer.steplog.json`: ROADMAP C11) appended to
`BENCHMARK.json` under the contract, the `jit.*` line, and the tiny
cell's traced rehearsal through the three files."""

import importlib.util
import json
import os

import pytest

import perfbench_tiny as tiny
from perfbench import contract, harness
from perfbench.sources import program_spanlog as sl
from perfbench.sources import program_steplog as steplog
from test_perfbench_spanlog import FakeLog, ns
from triton_dist_tpu.obs.spans import SpanRecord

REPO = tiny.REPO
WIDE, NARROW, ROWS = ("step.wide_wall_p50_ms", "step.narrow_wall_p50_ms",
                      "step.wide_rows_valid_pct")
SLOTS, CHUNK = 8, 128
T0 = 50.0
# (width, rows, milliseconds) a step, back to back from T0 with 3 ms
# between them: three narrow steps to each wide one, as in chat
CHAT = [(1, 8, 20.0), (1, 7, 19.0), (1, 8, 21.0), (128, 140, 60.0)] * 3
DOC = [(128, 400, 65.0), (128, 360, 66.0), (128, 380, 67.0)]


def make_log(steps, counted=True, extra=()):
    records, t = [], T0 + 0.001
    for k, (width, rows, ms) in enumerate(steps):
        fields = (k, None, "worker.step", ns(t), ns(t + ms / 1e3), k, None)
        if counted:
            fields += ({"width": width, "rows": rows},)
        records.append(SpanRecord(*fields))
        t += ms / 1e3 + 0.003
    return FakeLog(records + list(extra)), t


def view(t1, lines=None):
    lines = [] if lines is None else lines
    return harness.RunView(t0=T0, t1=t1, slots=SLOTS, chunk=CHUNK,
                           say=lines.append, lines=lines)


def read(metric, log, t1, monkeypatch, lines=None):
    monkeypatch.setattr(sl, "program_log", lambda: log)
    return harness.load_reader(REPO, metric).read(view(t1, lines))


# ---------- the three readers over hand-made logs ----------


def test_two_widths_read_apart(monkeypatch):
    log, t1 = make_log(CHAT)
    assert read(WIDE, log, t1, monkeypatch) == pytest.approx(60.0)
    assert read(NARROW, log, t1, monkeypatch) == pytest.approx(20.0)
    # 140 of 8 x 128 rows in each wide step; the narrow steps' rows
    # are in neither the sum nor the divisor
    assert read(ROWS, log, t1, monkeypatch) == pytest.approx(
        100.0 * 140 / (SLOTS * CHUNK))


def test_one_width_is_the_wide_one_and_nothing_is_narrow(monkeypatch):
    log, t1 = make_log(DOC)
    assert read(WIDE, log, t1, monkeypatch) == pytest.approx(66.0)
    assert read(NARROW, log, t1, monkeypatch) is None
    assert read(ROWS, log, t1, monkeypatch) == pytest.approx(
        100.0 * (400 + 360 + 380) / (3 * SLOTS * CHUNK))


@pytest.mark.parametrize("metric", [WIDE, NARROW, ROWS])
def test_records_without_counts_read_as_nothing(metric, monkeypatch):
    """The parent under these files: seven fields a record."""
    log, t1 = make_log(CHAT, counted=False)
    assert not hasattr(log.records()[0], "counts") \
        or log.records()[0].counts is None
    assert read(metric, log, t1, monkeypatch) is None


@pytest.mark.parametrize("metric", [WIDE, NARROW, ROWS])
def test_no_log_and_an_empty_log_read_as_nothing(metric, monkeypatch):
    assert read(metric, None, T0 + 1.0, monkeypatch) is None
    assert read(metric, FakeLog([]), T0 + 1.0, monkeypatch) is None


def test_only_the_window_s_records_count(monkeypatch):
    """A step that closes after the window, and one before it, are
    left out, as `program_spanlog.spans_of` leaves them."""
    log, t1 = make_log(CHAT)
    last = log.records()[-1]
    cut = last.t0_ns / 1e9 + 1e-4  # inside the last (wide) step
    steps = steplog.step_records(log, T0, cut)
    assert len(steps) == len(CHAT) - 1
    assert steplog.step_records(log, T0 + 0.002, cut)[0].step == 1


def test_the_widest_is_what_the_window_shows_not_the_chunk(monkeypatch):
    log, t1 = make_log([(1, 8, 20.0), (1, 6, 22.0)])
    assert read(WIDE, log, t1, monkeypatch) == pytest.approx(21.0)
    assert read(ROWS, log, t1, monkeypatch) == pytest.approx(
        100.0 * 14 / (2 * SLOTS))


# ---------- the jit line ----------


def jit(name, t, seconds, fun="jit(step)"):
    return SpanRecord(10_000 + int(t * 1e3), None, name, ns(t - seconds),
                      ns(t), None, None, {"fun": fun})


def test_the_jit_line_counts_before_and_inside_the_window(monkeypatch):
    extra = [jit("jit.trace", T0 - 20.0, 0.5),
             jit("jit.trace", T0 - 19.0, 1.5),
             jit("jit.lower", T0 - 18.0, 0.25),
             jit("jit.cache_load", T0 - 17.0, 2.0),
             jit("jit.compile", T0 + 0.01, 9.0),
             jit("jit.trace", T0 + 0.02, 0.001, fun="_where"),
             jit("jit.trace", T0 + 0.03, 0.002, fun="_where")]
    log, t1 = make_log(CHAT, extra=extra)
    lines = []
    assert read(WIDE, log, t1, monkeypatch, lines) is not None
    said = [line for line in lines if line.startswith("jit records")]
    assert said == [
        "jit records: before the window trace 2 in 2.00s, lower 1 in "
        "0.25s, compile 0 in 0.00s, cache_load 1 in 2.00s; inside it 3 "
        "(trace 2 in 0.00s of _where; compile 1 in 9.00s of jit(step))"]


def test_the_jit_line_says_what_each_width_s_first_call_held(monkeypatch):
    """A `worker.launch` of the warm-up with `jit.*` records inside it
    is a width's first call: its seconds beside theirs by kind."""
    def warm(k, step, name, t, seconds, counts=None):
        return SpanRecord(20_000 + k, None, name, ns(t - seconds), ns(t),
                          step, None, counts)

    extra = [
        warm(0, 0, "worker.step", T0 - 10.0, 8.0, {"width": 128, "rows": 9}),
        warm(1, 0, "worker.launch", T0 - 10.5, 7.4),
        jit("jit.trace", T0 - 17.3, 0.5),
        jit("jit.lower", T0 - 17.0, 0.25),
        jit("jit.compile", T0 - 10.6, 6.25),
        warm(2, 1, "worker.step", T0 - 5.0, 1.0, {"width": 1, "rows": 2}),
        warm(3, 1, "worker.launch", T0 - 5.5, 0.45),
        jit("jit.trace", T0 - 5.7, 0.125),
        jit("jit.cache_load", T0 - 5.55, 0.0625),
        # a later launch of the warm-up holds none and is not told
        warm(4, 2, "worker.launch", T0 - 2.0, 0.002),
        # one that is no step's: before every launch
        jit("jit.compile", T0 - 30.0, 1.0, fun="jit(init)"),
    ]
    log, t1 = make_log(CHAT, extra=extra)
    lines = []
    assert read(WIDE, log, t1, monkeypatch, lines) is not None
    assert lines == [
        "jit records: before the window trace 2 in 0.62s, lower 1 in "
        "0.25s, compile 2 in 7.25s, cache_load 1 in 0.06s; inside it 0"
        "; step 0 (width 128) launch 7.40s holds trace 0.50s + lower "
        "0.25s + compile 6.25s"
        "; step 1 (width 1) launch 0.45s holds trace 0.12s + cache_load "
        "0.06s"]


def test_the_jit_line_is_said_once_a_run_and_not_without_records(
        monkeypatch):
    log, t1 = make_log(CHAT, extra=[jit("jit.trace", T0 - 1.0, 0.5)])
    monkeypatch.setattr(sl, "program_log", lambda: log)
    lines = []
    run = view(t1, lines)
    for metric in (WIDE, NARROW, ROWS):
        harness.load_reader(REPO, metric).read(run)
    assert len(lines) == 1 and "inside it 0" in lines[0]
    quiet, t1 = make_log(CHAT)
    lines = []
    assert read(WIDE, quiet, t1, monkeypatch, lines) is not None
    assert lines == []


# ---------- the entries ----------

FIXTURE = os.path.join(REPO, "perfbench", "fixtures",
                       "per_layer.steplog.json")
_spec = importlib.util.spec_from_file_location(
    "run_waiting", os.path.join(REPO, "scripts", "run_waiting.py"))
run_waiting = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(run_waiting)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def with_the_waiting_entries(bench, also=()):
    """`bench` with the three entries APPENDED: where a `benchmark` PR
    puts them once `test_perfbench_narrow_steps.py:40` and
    `test_perfbench_exaone_moe.py:72` find `sched.narrow_steps_pct` by
    name and not at `per_layer[-1]` (ROADMAP C11). The driver refuses an
    entry INSERTED before the last as a change to it, and one appended
    fails those two pins, whose files a PR that adds may not edit: so
    they wait as data."""
    waiting = run_waiting.entries_of(harness.load_json(FIXTURE))
    for m in waiting:
        m["workloads"] += list(also)
    return run_waiting.appended(bench, waiting), waiting


def test_the_benchmark_is_as_it_was_and_the_three_wait_as_data(bench):
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-1] == "sched.narrow_steps_pct"
    assert not {WIDE, NARROW, ROWS} & set(names)
    _, waiting = with_the_waiting_entries(bench)
    assert [m["name"] for m in waiting] == [WIDE, NARROW, ROWS]
    for m in waiting:  # each has its reader beside the accepted ones
        assert os.path.isfile(os.path.join(
            REPO, "perfbench", "metrics", m["name"] + ".py"))


@pytest.mark.parametrize("name, unit, better, moves, cells", [
    (WIDE, "ms", "lower", "itl_p95_ms",
     ["q8b-1chip.chat-closed", "q8b-1chip.doc-closed",
      "q8b-tp4.chat-closed"]),
    (NARROW, "ms", "lower", "output_tokens_per_s",
     ["q8b-1chip.chat-closed", "q8b-tp4.chat-closed"]),
    (ROWS, "%", "higher", "itl_p95_ms",
     ["q8b-1chip.chat-closed", "q8b-1chip.doc-closed",
      "q8b-tp4.chat-closed"])])
def test_an_entry_lists_cells_that_report_what_it_moves(
        bench, name, unit, better, moves, cells):
    full, _ = with_the_waiting_entries(bench)
    entry = next(m for m in full["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": "program_span", "layer": "worker / step",
                     "moves": moves, "workloads": cells}
    moved = next(m for m in bench["end_to_end"] if m["name"] == moves)
    all_cells = [w["name"] for w in bench["workloads"]]
    assert set(cells) <= set(moved.get("workloads", all_cells))


def test_the_contract_passes_with_the_entries_appended(bench):
    assert contract.violations(bench, REPO) == []
    full, _ = with_the_waiting_entries(bench)
    assert contract.violations(full, REPO) == []


def test_the_wrapper_hands_the_run_the_benchmark_with_them_appended(
        bench, monkeypatch):
    """`scripts/run_waiting.py`: what a builder runs on the chip until
    the entries are in `BENCHMARK.json`. `perfbench/run.py` gets its own
    arguments and reads the benchmark with the entries at its end."""
    from perfbench import run
    seen = {}

    def main(argv):
        seen["argv"] = argv
        seen["bench"] = harness.load_json(
            os.path.join(REPO, "BENCHMARK.json"))
        seen["other"] = harness.load_json(FIXTURE)
        return 0

    monkeypatch.setattr(run, "main", main)
    monkeypatch.setattr(harness, "load_json", harness.load_json)
    rest = ["--workload", "q8b-1chip.chat-closed", "--trace", "1"]
    assert run_waiting.main(["--entries", FIXTURE] + rest) == 0
    assert seen["argv"] == rest
    assert seen["bench"]["per_layer"][:-3] == bench["per_layer"]
    assert [m["name"] for m in seen["bench"]["per_layer"][-3:]] \
        == [WIDE, NARROW, ROWS]
    assert set(seen["other"]) == {"what", "per_layer"}


# ---------- the tiny cell through the three files ----------


def test_tiny_traced_rehearsal_reports_the_three(tmp_path):
    """The tiny cell's prefills run wide and its decode tails narrow:
    with the waiting entries appended all three are on the line (which
    of the two steps is the shorter is the chip's to say: at this size
    on the CPU both are overhead, 2.4 ms each), and the run's log has
    the `jit.*` line with the warm-up's compiles before the window."""
    root, bench, cell = tiny.make_root(tmp_path)
    bench, _ = with_the_waiting_entries(bench, also=[cell["name"]])
    tiny.write_json(root, "BENCHMARK.json", bench)
    traced, lines = tiny.rehearse(root, bench, cell, trace=True)
    assert traced["correct"]
    said = [line for line in lines if line.startswith("jit records")]
    # the warm-up traced the step before the window (whether it was
    # then compiled or loaded is the cache's to say)
    assert len(said) == 1 and "before the window trace" in said[0]
    assert "before the window trace 0 in" not in said[0]
    m = traced["metrics"]
    assert m[NARROW]["value"] > 0 and m[WIDE]["value"] > 0
    assert 0 < m[ROWS]["value"] <= 100
    assert m[WIDE]["unit"] == "ms" and m[ROWS]["unit"] == "%"


def test_tiny_traced_rehearsal_without_the_entries_is_the_accepted_line(
        tmp_path):
    """`BENCHMARK.json` as it stands names none of the three: no reader
    of this PR's runs, the line is the accepted one."""
    root, bench, cell = tiny.make_root(tmp_path)
    traced, lines = tiny.rehearse(root, bench, cell, trace=True)
    assert traced["correct"]
    assert not {WIDE, NARROW, ROWS} & set(traced["metrics"])
    assert not [line for line in lines if line.startswith("jit records")]
