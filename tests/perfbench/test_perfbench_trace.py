"""The trace-to-metrics reduction: interval arithmetic on hand-made
events, then every reduction on the small recorded chip trace kept as
a fixture (made by `run.py --trace 1 --dump-trace`, cut to a few
steps)."""

import os

import pytest

from perfbench.sources import device_trace as dt

FIXTURE = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "perfbench", "fixtures",
    "trace_chat_closed_1chip.json")


def test_union_merges_overlaps_and_nesting():
    evs = [("a", 0.0, 1.0), ("b", 0.5, 1.5), ("c", 3.0, 4.0),
           ("inner", 3.2, 3.4)]
    assert dt.union(evs) == [(0.0, 1.5), (3.0, 4.0)]
    assert dt.busy_seconds(evs) == pytest.approx(2.5)


def test_own_time_is_duration_less_the_children():
    evs = [("while", 0.0, 10.0), ("dot", 1.0, 4.0), ("fusion", 4.0, 6.0),
           ("dot", 7.0, 8.0), ("copy", 11.0, 12.0)]
    own = dt.self_times(evs)
    assert own["while"] == pytest.approx(4.0)
    assert own["dot"] == pytest.approx(4.0)
    assert own["fusion"] == pytest.approx(2.0)
    assert own["copy"] == pytest.approx(1.0)
    assert sum(own.values()) == pytest.approx(dt.busy_seconds(evs))


def test_matching_seconds_by_pattern():
    evs = [("_fp_local_kernel.3", 0.0, 1.0), ("fusion.7", 1.0, 3.0),
           ("all-reduce.1", 3.0, 3.5)]
    assert dt.matching_seconds(evs, ["*_fp_local_kernel*"]) == 1.0
    assert dt.matching_seconds(evs, ["all-reduce*", "all-gather*"]) == 0.5
    assert dt.matching_seconds(evs, ["_gemm_rs*"]) == 0.0


def test_idle_gaps_are_named_by_the_host_span_that_covers_them():
    tr = dt.Trace(
        ops={"/device:TPU:0": [("a", 0.0, 1.0), ("b", 1.5, 2.0),
                               ("c", 4.0, 5.0)]},
        modules={"/device:TPU:0": [("jit_step", 0.0, 2.0),
                                   ("jit_step", 4.0, 5.0)]},
        host=[("perfbench.scheduler_step", 0.0, 2.2),
              ("perfbench.worker_step", 0.1, 2.1),
              ("perfbench.scheduler_step", 3.9, 5.1)])
    gaps = dict(dt.idle_gaps(tr))
    assert gaps["worker_step"] == pytest.approx(0.5)
    assert gaps["outside any span"] == pytest.approx(2.0)
    busy, window = dt.busy_and_window(tr)
    assert (busy, window) == (pytest.approx(2.5), pytest.approx(5.0))
    assert dt.module_runs(tr) == 2
    assert dt.Trace.from_json(tr.to_json()) == tr


def test_an_empty_trace_reads_as_nothing():
    tr = dt.Trace({}, {}, [])
    assert dt.busy_and_window(tr) == (0.0, 0.0)
    assert dt.top_ops(tr) == [] and dt.idle_gaps(tr) == []
    assert dt.module_runs(tr) == 0


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        return dt.Trace.from_json(f.read())


def test_fixture_reduces_to_busy_idle_and_named_operations(recorded):
    busy, window = dt.busy_and_window(recorded)
    assert 0 < busy <= window
    runs = dt.module_runs(recorded)
    assert runs >= 2
    ops = dt.top_ops(recorded)
    assert 1 <= len(ops) <= 10
    assert ops == sorted(ops, key=lambda o: -o[1])
    evs = next(iter(recorded.ops.values()))
    assert sum(dt.self_times(evs).values()) == pytest.approx(
        dt.busy_seconds(evs), rel=1e-6)
    # the flash-prefill kernel is found by the name pattern its
    # implementation file registers
    from perfbench import work

    repo = os.path.dirname(os.path.dirname(os.path.dirname(FIXTURE)))
    spec = work.load(repo, "flash_prefill")
    fp = dt.per_device_matching(recorded, work.patterns(spec))
    assert 0 < max(fp) < busy
    gaps = dt.idle_gaps(recorded)
    assert gaps and all(s > 0 for _n, s in gaps)
    assert any(n in ("worker_step", "scheduler_step") for n, _s in gaps)
