"""`sched.narrow_steps_pct` (ISSUE 31), which came after the ten
readers of `test_perfbench_spanlog.py`: it reads the step counter's two
shapes, moves the chat cells' throughput and lists those cells alone."""

import pytest

import by_name as entries
import perfbench_tiny as tiny
from perfbench import harness
from test_perfbench_spanlog import view

REPO = tiny.REPO
NARROW = "sched.narrow_steps_pct"
SHAPES = {"serve_steps{shape=narrow}": 30, "serve_steps{shape=wide}": 10}


def test_narrow_steps_is_the_narrow_share_of_the_steps():
    read = harness.load_reader(REPO, NARROW).read
    assert read(view(counters=SHAPES)) == pytest.approx(75.0)
    assert read(view(counters={**SHAPES, "serve_steps{shape=wide}": 0})) \
        == pytest.approx(100.0)


@pytest.mark.parametrize("counters", [
    {}, {"serve_steps": 7}, {"serve_steps{shape=wide}": 12},
    {"serve_rows{state=decode}": 90}],
    ids=["none", "unlabelled", "wide-alone", "the-parent-s"])
def test_narrow_steps_reads_as_nothing_where_no_step_ran_narrow(counters):
    """The parent counts no `serve_steps`; a family held to the one
    width counts `wide` alone: the line leaves the metric out."""
    assert harness.load_reader(REPO, NARROW).read(
        view(counters=counters)) is None


def test_narrow_steps_entry_lists_the_chat_cells_and_moves_their_rate():
    bench = entries.load()
    entry = entries.metric(bench, NARROW)
    assert dict(entry, workloads=None) == {
        "name": NARROW, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "output_tokens_per_s", "workloads": None}
    assert {"q8b-1chip.chat-closed", "q8b-tp4.chat-closed"} <= set(
        entry["workloads"])
    moved = next(m for m in bench["end_to_end"]
                 if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(moved["workloads"])


def test_tiny_traced_rehearsal_reports_the_narrow_share(tmp_path):
    """The tiny cell's decode tail runs narrow and its prefills wide, so
    the share lies strictly between; its line is otherwise the ten's."""
    root, bench, cell = tiny.make_root(tmp_path)
    traced, _ = tiny.rehearse(root, bench, cell, trace=True)
    assert traced["correct"]
    assert 0 < traced["metrics"][NARROW]["value"] < 100
