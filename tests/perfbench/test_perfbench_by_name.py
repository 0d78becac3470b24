"""A later PR appends to BENCHMARK.json: a configuration, its cell and
their per-layer entries at the ends of the lists, and the cell to the
`workloads` of accepted entries that read something in it. Two guards
for that: no file here finds an entry by its place or counts the
entries that list a cell (`pins`, which the planted cases below hold
to what it has to find), and such an appendage keeps the contract."""

import ast
import json
import os
import shutil

import pytest

import by_name as entries
import perfbench_tiny as tiny
from perfbench import contract

HERE = os.path.dirname(os.path.abspath(__file__))
LISTS = ("configs", "workloads", "per_layer")


def _is_list(node) -> bool:
    """`x["configs"]`, `x["workloads"]` or `x["per_layer"]`."""
    return (isinstance(node, ast.Subscript)
            and isinstance(node.slice, ast.Constant)
            and node.slice.value in LISTS)


def _is_place(node) -> bool:
    """A whole number, negative or not, or a slice."""
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        node = node.operand
    return isinstance(node, ast.Slice) or (
        isinstance(node, ast.Constant) and type(node.value) is int)


def pins(source: str) -> list:
    """What in `source` finds a benchmark entry by its place, or pins
    how many entries there are: an index or a slice of one of the
    three lists, or of a name bound to an expression over one; `len` or
    `sum` over such an expression held equal to a number; and two such
    names held equal. Each as `line: text`."""
    tree = ast.parse(source)
    lines = source.splitlines()

    def over_a_list(node) -> bool:
        return any(_is_list(n) for n in ast.walk(node))

    derived = {t.id for n in ast.walk(tree) if isinstance(n, ast.Assign)
               and over_a_list(n.value)
               for t in n.targets if isinstance(t, ast.Name)}

    def bench_valued(node) -> bool:
        return _is_list(node) or (isinstance(node, ast.Name)
                                  and node.id in derived)

    def counted(node) -> bool:
        return (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id in ("len", "sum")
                and any(over_a_list(a) or any(
                    isinstance(n, ast.Name) and n.id in derived
                    for n in ast.walk(a)) for a in node.args))

    found = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Subscript) and bench_valued(node.value) \
                and _is_place(node.slice):
            found.append(node.lineno)
        elif isinstance(node, ast.Compare) and all(
                isinstance(op, (ast.Eq, ast.NotEq)) for op in node.ops):
            sides = [node.left] + node.comparators
            number = any(isinstance(s, ast.Constant)
                         and type(s.value) is int for s in sides)
            if (number and any(counted(s) for s in sides)) or all(
                    isinstance(s, ast.Name) and s.id in derived
                    for s in sides):
                found.append(node.lineno)
    return [f"{n}: {lines[n - 1].strip()}" for n in sorted(set(found))]


FILES = sorted(f for f in os.listdir(HERE) if f.endswith(".py"))


@pytest.mark.parametrize("name", FILES)
def test_no_file_here_finds_an_entry_by_its_place(name):
    with open(os.path.join(HERE, name)) as f:
        assert pins(f.read()) == [], name


@pytest.mark.parametrize("planted", [
    'bench["workloads"][-1]["name"]',
    'bench["configs"][-1]["reduced"] = []',
    'spaced["per_layer"][0]["unit"] = "x"',
    'json.load(f)["workloads"][0]["name"]',
    'seen["bench"]["per_layer"][:-3]',
    'names = [m["name"] for m in bench["per_layer"]]\nnames[-1]',
    'lists = {m["name"] for m in bench["per_layer"] if c in m["workloads"]}'
    '\nassert len(lists) == 15',
    'assert sum(m.get("workloads") == [c] for m in bench["per_layer"]) == 4',
    'assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1',
    'a = {m["name"] for m in bench["per_layer"]}\n'
    'b = {m["name"] for m in bench["per_layer"] if 1}\nassert a == b',
], ids=["last-cell", "last-config", "first-entry", "first-cell-read",
        "a-slice", "a-name-s-place", "count-of-a-listing",
        "count-of-entries", "count-of-cells", "two-listings-equal"])
def test_a_planted_pin_is_found(planted):
    assert len(pins(planted)) == 1, planted


@pytest.mark.parametrize("fine", [
    'harness.find(bench["per_layer"], "step.mfu_pct", "metric")',
    'entries.metric(bench, NAME)["workloads"]',
    'lists = entries.listing(bench, CELL)\nassert set(GENERIC) <= lists',
    'recorded = listing.args[0]\nassert recorded[4].shape == (2,)',
    'assert len(widths) == 15',
    '[m["name"] for m in bench["per_layer"] if m["name"] in TEN] == TEN',
], ids=["find", "by-name", "inclusion", "no-benchmark-list",
        "a-count-of-no-list", "an-order-of-named-entries"])
def test_a_lookup_by_name_is_no_pin(fine):
    assert pins(fine) == []


def _appended(root: str, bench: dict, like: dict) -> dict:
    """`bench` as a PR that adds a configuration would leave it, in
    `root`: a copy of the file of the configuration `like` names under
    a new name, a cell of it under `like`'s mix and chips, listed
    wherever `like` is listed (the accepted lists extended), and one
    per-layer entry of its own with its reader, each at the end."""
    src = entries.config(bench, like["config"])
    name = src["name"] + ".copy"
    new = dict(src, name=name, file=f"perfbench/configs/{name}.json")
    shutil.copy(os.path.join(root, src["file"]),
                os.path.join(root, new["file"]))
    bench["configs"].append(new)
    cell = dict(like, name=f"{name}.{like['traffic']}", config=name)
    bench["workloads"].append(cell)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like["name"] in m.get("workloads", []):
            m["workloads"].append(cell["name"])
    with open(os.path.join(root, "perfbench", "metrics",
                           "tests.appended_count.py"), "w") as f:
        f.write(tiny.STEPS_READER)
    bench["per_layer"].append({
        "name": "tests.appended_count", "unit": "count",
        "better": "higher", "source": "program_span", "layer": "scheduler",
        "moves": "itl_p95_ms", "workloads": [cell["name"]]})
    tiny.write_json(root, "BENCHMARK.json", bench)
    return cell


@pytest.mark.parametrize("like",
                         [w["name"] for w in entries.load()["workloads"]])
def test_a_configuration_a_cell_and_an_entry_append_under_the_contract(
        tmp_path, like):
    root = str(tmp_path)
    shutil.copytree(os.path.join(entries.REPO, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = entries.load()
    before = json.loads(json.dumps(bench))
    cell = _appended(root, bench, entries.cell(bench, like))
    assert contract.violations(bench, root) == []
    # the added cell is read by what reads its model, and by its own
    assert entries.listing(bench, cell["name"]) == entries.listing(
        before, like) | {"tests.appended_count"}
    # every accepted entry is found where a test looks for it
    for group, find in (("configs", entries.config),
                        ("workloads", entries.cell),
                        ("per_layer", entries.metric)):
        for e in before[group]:
            got = find(bench, e["name"])
            assert {k: v for k, v in got.items() if k != "workloads"} == {
                k: v for k, v in e.items() if k != "workloads"}
