"""BENCHMARK.json against the contract's shape, and against the files
the harness finds by name."""

import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "perfbench/run.py"]
    assert bench["paths"] == ["perfbench", "tests/perfbench"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 65536
    # a full check with 24 cells has to fit 43200 s
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


def test_names_units_and_whys(bench):
    entries = (bench["configs"] + bench["workloads"] + bench["end_to_end"]
               + bench["per_layer"])
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] \
                    and "\t" not in e[key]
    for group in ("configs", "workloads"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES


def test_entries_have_just_the_contract_s_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


def test_cells_configs_and_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    used = set()
    pairs = set()
    for w in bench["workloads"]:
        assert w["config"] in configs
        used.add(w["config"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert os.path.exists(os.path.join(
            REPO, "perfbench", "traffic", w["traffic"] + ".json"))
        with open(os.path.join(REPO, configs[w["config"]]["file"])) as f:
            assert json.load(f)["serve"]["chips"] == w["chips"]
    assert used == set(configs)
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 4)
    for c in bench["configs"]:
        assert c["file"].startswith("perfbench/configs/")
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        # no width is ever cut: the published Qwen3-8B sizes
        assert (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"], cfg["vocab_size"]) == (
            4096, 12288, 32, 8, 128, 151936)
        assert (cfg["num_hidden_layers"] != 36) == (
            "num_hidden_layers" in c["reduced"])
        assert isinstance(cfg["check"]["gap_limit"], float)


def test_every_cell_reports_setup_another_end_to_end_and_a_layer(bench):
    def cells_of(m):
        return set(m.get("workloads",
                         [w["name"] for w in bench["workloads"]]))

    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in bench["end_to_end"])
    e2e = {m["name"]: cells_of(m) for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        mine = [n for n, cells in e2e.items() if w["name"] in cells]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(w["name"] in cells_of(m) for m in bench["per_layer"])
    known = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert "workloads" in m and set(m["workloads"]) <= known
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert set(m["workloads"]) <= e2e[m["moves"]], m["name"]
        assert os.path.exists(os.path.join(
            REPO, "perfbench", "metrics", m["name"] + ".py")), m["name"]


def test_a_roofline_moves_with_a_whole_step_mfu_beside_it(bench):
    for m in bench["per_layer"]:
        if m["name"].endswith("roofline_pct"):
            assert m["unit"] == "%"
            beside = [o for o in bench["per_layer"]
                      if "mfu" in o["name"] and o["moves"] == m["moves"]
                      and set(m["workloads"]) <= set(o["workloads"])]
            assert beside, m["name"]


def test_nothing_in_perfbench_imports_the_old_bench(bench):
    for path in bench["paths"]:
        for base, _dirs, files in os.walk(os.path.join(REPO, path)):
            for f in files:
                if f.endswith(".py") and f != os.path.basename(__file__):
                    text = open(os.path.join(base, f)).read()
                    assert "import bench" not in text, f
                    assert "chip_smoke" not in text or path.startswith(
                        "tests"), f


def test_the_reference_imports_nothing_of_the_program():
    ref = os.path.join(REPO, "perfbench", "reference")
    for f in os.listdir(ref):
        if f.endswith(".py"):
            assert "triton_dist_tpu" not in "".join(
                line for line in open(os.path.join(ref, f))
                if line.lstrip().startswith(("import ", "from "))), f
