"""A throw-away tiny cell for the CPU rehearsals: a temporary copy of
`perfbench/` and `BENCHMARK.json` with a configuration, a traffic mix
and a cell ADDED as new files and entries — no file that was there is
edited, which is what a later PR is held to."""

import json
import os
import shutil
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_CONFIG = {
    "vocab_size": 256, "hidden_size": 128, "intermediate_size": 256,
    "num_hidden_layers": 2, "num_attention_heads": 8,
    "num_key_value_heads": 4, "head_dim": 32, "rope_theta": 1000000,
    "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "torch_dtype": "float32",
    "stands_for": "a test-scale model on the CPU",
    "serve": {"chips": 1, "tp": 1, "slots": 4, "max_len": 64},
    "family": "qwen3_dense", "reference": "qwen3_dense",
    "check": {"control": "bf16", "gap_limit": 0.001},
}

TINY_MIX = {
    "loop": "closed", "clients": 4, "pool": 8,
    "prompt": {"dist": "lognormal", "median": 20, "sigma": 0.5,
               "min": 4, "max": 40},
    "output": {"dist": "uniform", "min": 2, "max": 8},
    "check_requests": 3,
}


# The cell the control is read in: bfloat16 with the fp8 control, as on
# the chip, and enough served tokens (about 140) for the readings to
# separate. On the CPU over 12 seeds the program's widest gap read
# 0.0032 at most and the control's 0.0225 at least; the limit sits
# between, 3x over the lower.
CONTROL_CONFIG = dict(TINY_CONFIG, torch_dtype="bfloat16",
                      check={"control": "fp8", "gap_limit": 0.01})
CONTROL_MIX = dict(
    TINY_MIX,
    prompt={"dist": "lognormal", "median": 16, "sigma": 0.5,
            "min": 4, "max": 32},
    output={"dist": "uniform", "min": 12, "max": 24}, check_requests=8)


def make_root(tmp_path, mix=None, config=None, extra_metric=None):
    """Temporary checkout: (root, bench, cell)."""
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copytree(os.path.join(REPO, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(root, "perfbench", "configs",
                           "tiny-test.json"), "w") as f:
        json.dump(config or TINY_CONFIG, f)
    with open(os.path.join(root, "perfbench", "traffic",
                           "tiny-test.json"), "w") as f:
        json.dump(mix or TINY_MIX, f)
    bench["configs"].append({
        "name": "tiny-test", "source": "tests/perfbench",
        "file": "perfbench/configs/tiny-test.json", "reduced": [],
        "why": "test"})
    cell = {"name": "tiny-test.tiny-test", "config": "tiny-test",
            "traffic": "tiny-test", "chips": 1, "why": "test"}
    bench["workloads"].append(cell)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(cell["name"])
    if extra_metric is not None:
        name, source = extra_metric
        with open(os.path.join(root, "perfbench", "metrics",
                               name + ".py"), "w") as f:
            f.write(source)
        bench["per_layer"].append({
            "name": name, "unit": "count", "better": "higher",
            "source": "program_span", "layer": "scheduler",
            "moves": "itl_p95_ms", "workloads": [cell["name"]]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root, bench, cell


def rehearse(root, bench, cell, seed=2**31 + 11, seconds=1.5, trace=False,
             **kw):
    from perfbench import harness

    lines = []
    result = harness.run_cell(root, bench, cell, seed, seconds, trace,
                              time.perf_counter(), say=lines.append,
                              device_kind="TPU v5 lite", **kw)
    return result, lines
