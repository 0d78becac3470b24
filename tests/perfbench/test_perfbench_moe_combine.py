"""`moe.combine_rows_read_pct` in the benchmark: the entry as the
contract wants it, in the four expert cells, and its reader on planted
counters (nothing to read on a program without them, 100 where the jnp
combine read every pair, the held pairs' share where the kernel ran)."""

import pytest

import by_name as entries
from perfbench import contract, harness

REPO = entries.REPO
NEW = "moe.combine_rows_read_pct"
EXPERT_CELLS = ("q3n-1chip.longdoc-closed", "kl48b-1chip.longdoc-closed",
                "kx236b-1chip.longdoc-closed", "g4hs-1chip.longdoc-closed")


def test_the_entry_reads_the_expert_layer_in_the_four_expert_cells():
    bench = entries.load()
    assert contract.violations(bench, REPO) == []
    entry = dict(entries.metric(bench, NEW))
    # a later cell may be appended to the list
    assert set(EXPERT_CELLS) <= set(entry.pop("workloads"))
    assert entry == {
        "name": NEW, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "expert layer",
        "moves": "itl_p95_ms"}
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"].startswith("moe.")}
    assert layers == {"expert layer"}


def _view(**counters):
    return harness.RunView(root=REPO, counters=counters, trace=None,
                           trace_steps=[], say=lambda m: None)


@pytest.mark.parametrize("counters, want", [
    ({}, None),  # a program without the counters
    ({"moe_combine_rows": 0, "moe_combine_pairs": 0}, None),
    # the jnp combine un-sorts every pair
    ({"moe_combine_rows": 10_240, "moe_combine_pairs": 10_240}, 100.0),
    # the kernel read the held pairs' rows alone
    ({"moe_combine_rows": 1_792, "moe_combine_pairs": 10_240}, 17.5),
])
def test_the_reader_on_planted_counters(counters, want):
    read = harness.load_reader(REPO, NEW).read
    assert read(_view(**counters)) == want
