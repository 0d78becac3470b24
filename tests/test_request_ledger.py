"""Request-scoped attribution tests (ISSUE 13).

The load-bearing properties: (1) the per-request ledger CLOSES — each
retired request's decomposed phase times sum to its submit->finish
wall time within the documented tolerance; (2) request tagging is
zero-cost-off — bit-identical tokens, unchanged pallas_call_count;
(3) the ledger/report tooling is strict (malformed input is loud).
"""

import importlib.util
import os

import numpy as np
import pytest

from triton_dist_tpu import obs
from triton_dist_tpu.lang.core import pallas_call_count
from triton_dist_tpu.models import Engine, ModelConfig
from triton_dist_tpu.runtime import make_mesh
from triton_dist_tpu.serve import Scheduler
from triton_dist_tpu.trace import events as tev
from triton_dist_tpu.trace.ledger import (
    attribute_branch_time,
    build_ledger,
    check_close,
    check_ledger,
    format_requests_table,
    load_ledger,
    write_ledger,
    write_request_trace,
)

GEO = dict(slots=3, chunk=4, page=8)


@pytest.fixture(scope="module")
def mesh1():
    return make_mesh(mesh_shape=(1,), axis_names=("tp",))


@pytest.fixture(scope="module")
def eng1(mesh1):
    cfg = ModelConfig.tiny(num_q_heads=4, num_kv_heads=2,
                           max_positions=64)
    return Engine(cfg, mesh1, decode_mode="ar", max_len=64,
                  donate_cache=False)


@pytest.fixture(scope="module")
def prompts(eng1):
    rng = np.random.default_rng(11)
    v = eng1.cfg.vocab_size
    return [list(map(int, rng.integers(0, v, n))) for n in (12, 9, 7)]


def _run(sch, prompts, gen=5):
    reqs = [sch.submit(p, max_new_tokens=gen) for p in prompts]
    sch.run()
    return reqs


def test_ledger_closes_on_host_loop_run(eng1, prompts, tmp_path):
    sch = Scheduler(eng1, **GEO)
    reqs = _run(sch, prompts)
    led = sch.ledger()
    assert check_close(led) == [], format_requests_table(led)
    rows = {r["request_id"]: r for r in led["requests"]}
    for req in reqs:
        row = rows[req.request_id]
        assert row["state"] == "finished"
        assert row["tokens_out"] == 5
        chunks = -(-len(req.prompt) // sch.chunk)
        # the loop counts plan rows exactly: chunk steps + decodes
        assert row["prefill_chunks"] == chunks
        assert row["decode_steps"] == 5 - 1
        assert row["device_steps"] == chunks + 4
        assert row["device_share_us"] > 0
    # step history carries the slot->request map the ledger folded
    steps = [e for e in sch.history if e["kind"] == "step"]
    assert steps and all(e["slots"] for e in steps)
    # and the document round-trips through the strict loader
    path = write_ledger(led, str(tmp_path / "ledger.json"))
    assert load_ledger(path)["requests"] == led["requests"]


# ---------- zero-cost-off ----------


def test_request_tagging_zero_cost_off_host_loop(eng1, prompts):
    """Tagging (history + phase accumulation) never touches
    the device: two tagged runs replay the same executable with zero
    new pallas calls after the first, and tokens are bitwise."""
    sch = Scheduler(eng1, **GEO)
    ref = [r.out_tokens for r in _run(sch, prompts)]
    c0 = pallas_call_count()
    sch2 = Scheduler(eng1, **GEO)
    again = [r.out_tokens for r in _run(sch2, prompts)]
    assert pallas_call_count() == c0
    assert again == ref
    assert len(sch2.history) > 0  # tagging was on the whole time


# ---------- ledger tooling strictness + render modes ----------


def _report_cli():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "_tdt_trace_report_ledger", os.path.join(repo, "scripts",
                                                 "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_report_requests_mode(eng1, prompts, tmp_path, capsys):
    cli = _report_cli()
    sch = Scheduler(eng1, **GEO)
    _run(sch, prompts[:2], gen=3)
    path = write_ledger(sch.ledger(), str(tmp_path / "led.json"))
    assert cli.main(["--requests", path]) == 0
    out = capsys.readouterr().out
    assert "request ledger" in out and "close" in out
    bad = tmp_path / "bad.json"
    bad.write_text('{"magic": "nope"}')
    assert cli.main(["--requests", str(bad)]) == 1
    # a close violation is as loud as a bad magic
    doc = load_ledger(path)
    doc["requests"][0]["close_frac"] = 0.5
    broke = tmp_path / "broke.json"
    import json

    broke.write_text(json.dumps(doc))
    assert cli.main(["--requests", str(broke)]) == 1


def test_check_ledger_rejects_torn_rows(tmp_path):
    with pytest.raises(ValueError, match="missing"):
        check_ledger({"magic": "tdt-req-ledger",
                      "requests": [{"request_id": 0}]})
    with pytest.raises(ValueError, match="not a request ledger"):
        load_ledger_path = tmp_path / "x.json"
        load_ledger_path.write_text("{}")
        load_ledger(str(load_ledger_path))


# ---------- per-request Perfetto tracks ----------


def test_request_perfetto_tracks(eng1, prompts, tmp_path):
    from triton_dist_tpu.trace.export import load_trace_json

    sch = Scheduler(eng1, **GEO)
    reqs = _run(sch, prompts, gen=3)
    path = write_request_trace(sch, str(tmp_path / "req.trace.json"))
    d = load_trace_json(path)  # strict loader accepts the format
    names = {e["args"]["name"] for e in d["traceEvents"]
             if e.get("ph") == "M"}
    for req in reqs:
        assert f"req{req.request_id}" in names  # one track per request
    assert "serve" in names
    phases = [e["name"] for e in d["traceEvents"] if e.get("ph") == "X"]
    assert "prefill" in phases and "decode" in phases


# ---------- branch-time attribution ----------


def test_branch_time_attribution_splits_proportionally():
    from triton_dist_tpu.trace.collect import Event, Span, Timeline

    rid = tev.REGIONS["mega.task"]
    spans = [Span("mega", 0, 0, rid, payload=b, aux=i, t0=0.0,
                  t1=10.0) for i, b in enumerate((0, 0, 1))]
    tl = Timeline(events=[Event("mega", 0, 0, rid, tev.KIND_BEGIN, 0,
                                0, 0, 0.0)],
                  spans=spans, drops={}, host_spans=[])
    ledger = {"magic": "tdt-req-ledger", "requests": [
        {"request_id": 7, "device_steps": 3},
        {"request_id": 9, "device_steps": 1},
    ]}
    out = attribute_branch_time(ledger, tl, branch_keys=["mm", "attn"])
    assert set(out) == {7, 9}
    assert out[7]["mm"] == pytest.approx(20.0 * 3 / 4)
    assert out[9]["attn"] == pytest.approx(10.0 * 1 / 4)
    # shares reassemble the bucket totals
    assert sum(d["mm"] for d in out.values()) == pytest.approx(20.0)


# ---------- decomposition histograms on the always-on plane ----------


def test_decomposition_histograms_stream_at_retirement(eng1, prompts):
    sch = Scheduler(eng1, **GEO)
    _run(sch, prompts, gen=3)
    for name in ("serve_req_queued_us", "serve_req_prefill_us",
                 "serve_req_decode_us"):
        assert sch.obs.hist_count(name) == len(prompts), name
    # and they ride the Prometheus exposition (the /metrics scrape)
    text = obs.to_prometheus(sch.obs)
    assert "serve_req_decode_us_count" in text
    assert "serve_req_prefill_us_bucket" in text


def test_ledger_build_is_pure(eng1, prompts):
    """build_ledger must not mutate scheduler or request state: two
    builds produce identical documents — including the ISSUE 14
    columns (spec_verify/prefix), exercised here on a spec+prefix
    scheduler so the extension rides the purity pin."""
    from triton_dist_tpu.spec import NgramDraft, SpecConfig

    sch = Scheduler(eng1, spec=SpecConfig(k=3, draft=NgramDraft()),
                    prefix_cache=True, prefix_block=8, **GEO)
    _run(sch, prompts, gen=3)
    a = build_ledger(sch)
    b = build_ledger(sch)
    assert a == b
    # the new columns are present on every row, the close contract is
    # untouched (spec_verify is a SUB-bucket of decode, never added to
    # the close sum — tol unchanged)
    for row in a["requests"]:
        assert {"spec_verify_us", "spec_steps",
                "prefix_hit_tokens"} <= set(row)
        assert row["spec_verify_us"] <= row["decode_us"] * 1.001 + 1
    assert check_close(a) == []
