"""The rest of a run with the look for a chip skipped: a tiny cell on
the CPU through `run_cell` — data-driven (new files only), `correct`
true on the sound path and false with the timed path broken
underneath — and the command's refusals."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import perfbench_tiny as tiny

REPO = tiny.REPO
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    """One sound rehearsal, end-to-end and traced, shared by the tests
    that only read it."""
    root, bench, cell = tiny.make_root(tmp_path_factory.mktemp("sound"))
    plain, lines = tiny.rehearse(root, bench, cell)
    traced, _ = tiny.rehearse(root, bench, cell, trace=True)
    return bench, cell, plain, traced, lines


def test_result_line_has_the_contract_keys(sound):
    bench, cell, plain, traced, _ = sound
    for r in (plain, traced):
        assert RESULT_KEYS <= set(r)
        assert list(r)[-1] == "checks"  # the numbers compared come last
        assert set(r["device"]) >= {"platform", "kind", "count",
                                    "memory_peak_bytes"}
        json.loads(json.dumps(r))
    assert {"busy_s", "window_s"} <= set(traced["device"])
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}


def test_trace_0_reports_end_to_end_and_trace_1_per_layer(sound):
    bench, cell, plain, traced, _ = sound
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or cell["name"] in m["workloads"]}
    assert set(plain["metrics"]) == e2e and "setup_s" in e2e
    assert all(v["value"] > 0 for v in plain["metrics"].values())
    layer = {m["name"] for m in bench["per_layer"]}
    assert set(traced["metrics"]) <= layer
    # the CPU has no device plane: device_trace readers say nothing,
    # and never 0
    assert "device.idle_pct" not in traced["metrics"]
    assert "step.wall_p50_ms" in traced["metrics"]
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    for r in (plain, traced):
        for name, v in r["metrics"].items():
            assert v["unit"] == units[name]


def test_sound_path_is_correct_and_says_each_number_with_its_limit(sound):
    _, _, plain, traced, _ = sound
    for r in (plain, traced):
        assert r["correct"] is True and r["failed"] == 0
        assert r["attempted"] > 10
        checks = r["checks"]
        assert checks["failed_requests"] == {"value": 0, "limit": 0}
        assert checks["stream_mismatches"] == {"value": 0, "limit": 0}
        gap = checks["served_logit_gap_max"]
        assert 0 <= gap["value"] <= gap["limit"] == 0.001


def test_earlier_lines_carry_the_run_s_provenance(sound):
    lines = "\n".join(sound[4])
    for needle in ("device: platform=", "jax=", "depth 2", "chunk=",
                   "page=", "plan_id=", "applied tune configs",
                   "Pallas kernels in the compiled program",
                   "hits", "misses", "requests: sent", "finished in it",
                   "lateness", "ttft median", "set-up:"):
        assert needle in lines, needle


def test_an_altered_token_makes_the_run_not_correct(tmp_path):
    """A token altered where it is produced: the worker's step returns
    every slot's token shifted by one."""
    root, bench, cell = tiny.make_root(tmp_path)

    def tamper(sch):
        inner = sch.worker.step

        def step(*a, **kw):
            return (np.asarray(inner(*a, **kw)) + 1) % 256

        sch.worker.step = step

    result, _ = tiny.rehearse(root, bench, cell, tamper=tamper)
    assert result["correct"] is False
    gap = result["checks"]["served_logit_gap_max"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("seed", [2**31 + 21, 22, 23])
def test_the_control_in_the_program_s_place_reads_not_correct(
        tmp_path, seed):
    """The control (the reference one precision down, in the program's
    place on the run's own sample) goes through the harness's own
    comparison against the configuration's limit and comes out not
    correct, in the same run whose program reads correct."""
    root, bench, cell = tiny.make_root(tmp_path, mix=tiny.CONTROL_MIX,
                                       config=tiny.CONTROL_CONFIG)
    result, lines = tiny.rehearse(root, bench, cell, seed=seed,
                                  control=True, seconds=2.0)
    own = result["checks"]["served_logit_gap_max"]
    assert result["correct"] is True and own["value"] <= own["limit"]
    ctrl = result["control"]
    gap = ctrl["checks"]["served_logit_gap_max"]
    assert ctrl["correct"] is False
    assert gap["limit"] == own["limit"] and gap["value"] > gap["limit"]
    assert list(result)[-1] == "checks"
    assert any("in the program's place" in line and "not correct" in line
               for line in lines)
    # and limits.py, which reads the limits on the chip, sees it so
    from perfbench import limits

    row = limits.reading(seed, result)
    assert row["correct"] is True and row["control_correct"] is False
    assert limits.judge([row])["holds"] is True


def test_a_run_without_the_control_carries_no_control_key(sound):
    from perfbench import limits

    assert "control" not in sound[2] and "control" not in sound[3]
    assert limits.reading(1, sound[2])["control_correct"] is None


def reading(seed, correct, gap, control_correct=None, control_gap=None):
    return {"seed": seed, "correct": correct, "gap": gap, "limit": 0.6,
            "control_correct": control_correct, "control_gap": control_gap}


@pytest.mark.parametrize("rows, holds", [
    ([reading(1, True, 0.2, False, 2.0), reading(2, True, 0.1)], True),
    ([reading(1, True, 0.2, True, 0.5)], False),   # the control passed
    ([reading(1, False, 0.7, False, 2.0)], False),  # the program failed
    ([], False),
])
def test_limits_hold_only_where_the_comparison_separates_the_readings(
        rows, holds):
    from perfbench import limits

    verdict = limits.judge(rows)
    assert verdict["holds"] is holds
    if rows:
        assert verdict["limit"] == 0.6


def test_a_token_dropped_from_the_stream_makes_the_run_not_correct(
        tmp_path):
    """An answer altered on its way out: every fifth token never
    reaches the client's stream."""
    root, bench, cell = tiny.make_root(tmp_path)

    def tamper(sch):
        inner = sch._emit
        count = [0]

        def emit(req, tok):
            count[0] += 1
            stream = req.stream
            if count[0] % 5 == 0:
                req.stream = None
            try:
                inner(req, tok)
            finally:
                req.stream = stream
            if req.done and req.stream is not None and count[0] % 5 == 0:
                req.stream._close()

        sch._emit = emit

    result, _ = tiny.rehearse(root, bench, cell, tamper=tamper)
    assert result["correct"] is False
    assert result["checks"]["stream_mismatches"]["value"] > 0 \
        or result["checks"]["failed_requests"]["value"] > 0


def test_the_exchange_between_chips_left_out_makes_the_run_not_correct(
        tmp_path, monkeypatch):
    """tp=4 over four virtual devices, sound first (the second
    rehearsal of a four-chip call); then every row-parallel projection
    keeps its own chip's partial sum — the all-reduce is left out of
    the serve step the window drives."""
    import jax.numpy as jnp

    cfg = json.loads(json.dumps(tiny.TINY_CONFIG))
    cfg["serve"].update(chips=4, tp=4)
    mix = dict(tiny.TINY_MIX, pool=4,
               prompt={"dist": "uniform", "min": 4, "max": 8},
               output={"dist": "uniform", "min": 2, "max": 3})
    root, bench, cell = tiny.make_root(tmp_path, mix=mix, config=cfg)
    cell["chips"] = 4
    sound, _ = tiny.rehearse(root, bench, cell, seconds=8.0)
    assert sound["correct"] is True and sound["attempted"] >= 4

    def local_only(act, w, axis=None, config=None):
        return jnp.dot(act, w, preferred_element_type=jnp.float32).astype(
            act.dtype)

    from triton_dist_tpu.layers import tp_attn, tp_mlp

    monkeypatch.setattr(tp_mlp, "gemm_ar", local_only)
    monkeypatch.setattr(tp_attn, "gemm_ar", local_only)

    def tamper(sch):
        eng, pool = sch.worker.engine, sch.pool
        eng._serve_cache.clear()
        sch.worker._fn = eng.make_serve_step(pool.slots, sch.chunk,
                                             pool.page, pool.max_pages)

    result, _ = tiny.rehearse(root, bench, cell, seconds=8.0, tamper=tamper)
    assert result["correct"] is False
    gap = result["checks"]["served_logit_gap_max"]
    assert gap["value"] > gap["limit"]


def test_a_new_cell_is_new_files_only(tmp_path):
    """A throw-away configuration, mix, metric and cell, added as new
    files to a temporary copy; the harness finds each by its name in
    BENCHMARK.json and no file that was there is edited."""
    before = {}
    for base, _dirs, files in os.walk(os.path.join(REPO, "perfbench")):
        for f in files:
            if "__pycache__" not in base:
                p = os.path.join(base, f)
                before[os.path.relpath(p, REPO)] = open(p, "rb").read()
    reader = (
        '"""Steps the window ran (a count)."""\n\n\n'
        "def read(run):\n"
        "    return float(len(run.steps)) or None\n")
    mix = dict(tiny.TINY_MIX, loop="open", arrivals="poisson",
               rate_per_s=20.0)
    root, bench, cell = tiny.make_root(
        tmp_path, mix=mix, extra_metric=("sched.steps_in_window", reader))
    traced, lines = tiny.rehearse(root, bench, cell, trace=True)
    assert traced["correct"] is True
    assert traced["metrics"]["sched.steps_in_window"]["value"] >= 1
    assert traced["metrics"]["sched.steps_in_window"]["unit"] == "count"
    assert any("offered" in line and "requests/s" in line for line in lines)
    assert any(line.startswith("open loop: waiting") for line in lines)
    assert any("generator lateness" in line for line in lines)
    for rel, content in before.items():
        with open(os.path.join(root, rel), "rb") as f:
            assert f.read() == content, rel


def test_a_reader_that_finds_nothing_is_left_out(tmp_path):
    reader = "def read(run):\n    return None\n"
    root, bench, cell = tiny.make_root(
        tmp_path, extra_metric=("kernel.absent.roofline_pct", reader))
    traced, _ = tiny.rehearse(root, bench, cell, trace=True)
    assert "kernel.absent.roofline_pct" not in traced["metrics"]


def test_the_cross_chip_cell_fails_without_a_cross_chip_kernel(tmp_path):
    cfg = json.loads(json.dumps(tiny.TINY_CONFIG))
    cfg["serve"]["cross_chip_kernels"] = ["_gemm_rs_kernel",
                                          "_ring_ag_kernel"]
    root, bench, cell = tiny.make_root(tmp_path, config=cfg)
    with pytest.raises(RuntimeError, match="cross-chip Pallas"):
        tiny.rehearse(root, bench, cell)


def run_command(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "perfbench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_the_command_refuses_to_measure_without_a_tpu():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        name = json.load(f)["workloads"][0]["name"]
    out = run_command(REPO, "--workload", name, "--seed", "1",
                      "--seconds", "1", "--trace", "0")
    assert out.returncode not in (0, None)
    assert "TPU only" in out.stderr
    assert out.stdout.strip() == ""  # no result, nothing built


def test_the_command_refuses_where_the_program_is_absent(tmp_path):
    root, bench, cell = tiny.make_root(tmp_path)
    out = run_command(root, "--workload", cell["name"], "--seed", "1",
                      "--seconds", "1", "--trace", "0")
    assert out.returncode not in (0, None)
    assert out.stdout.strip() == ""
