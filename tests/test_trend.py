"""Perf-trend sentinel tests (ISSUE 13).

The detector corpus: synthetic artifact series with INJECTED
regressions / improvements / rig switches — every injected defect must
be flagged with its class, and the REAL r01–r06 series must produce
zero unacknowledged flags (the acceptance criterion: the sentinel run
that lands in the PR exits 0). Pure file I/O — no jax."""

import importlib.util
import json
import os

import pytest

from triton_dist_tpu.obs import trend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write_round(tmp_path, rnd, parsed, kind="BENCH"):
    doc = {"n": rnd, "rc": 0, "tail": "", "parsed": parsed}
    (tmp_path / f"{kind}_r{rnd:02d}.json").write_text(json.dumps(doc))


def _write_multichip(tmp_path, rnd, ok, rc=0, skipped=False):
    (tmp_path / f"MULTICHIP_r{rnd:02d}.json").write_text(json.dumps(
        {"n_devices": 8, "rc": rc, "ok": ok, "skipped": skipped}))


# ---------- synthetic corpus: every injected defect flagged ----------


def test_detector_flags_injected_regressions(tmp_path):
    _write_round(tmp_path, 1, {"foo_ms": 10.0, "qux_ms": 9.0,
                               "baz_us": 50.0})
    _write_round(tmp_path, 2, {"foo_ms": 10.5, "qux_ms": 8.0,
                               "baz_us": 52.0,
                               "bar_tokens_per_s": 100.0})
    _write_round(tmp_path, 3, {
        "foo_ms": 16.5,             # +57% over best -> watermark_break
        "qux_ms": 5.0,              # improvement (note, never a flag)
        "bar_tokens_per_s": 70.0,   # throughput -43% -> trend flag
        # baz_us ABSENT -> missing_family
    })
    rep = trend.analyze(repo=str(tmp_path))
    kinds = {(f["key"], f["kind"]) for f in rep["flags"]}
    assert ("foo_ms", "watermark_break") in kinds
    assert ("baz_us", "missing_family") in kinds
    assert any(k == "bar_tokens_per_s" and kind in
               ("trend_regression", "watermark_break")
               for k, kind in kinds)
    # the improvement landed as a NOTE, not a flag
    assert not any(f["key"] == "qux_ms" for f in rep["flags"])
    assert any(n["key"] == "qux_ms" and n["kind"] == "improvement"
               for n in rep["notes"])
    # nothing here is acknowledged -> the gate fails
    assert len(trend.unacknowledged(rep)) == len(rep["flags"]) >= 3


def test_detector_trend_vs_watermark_thresholds(tmp_path):
    # a +30% drift over the median crosses trend_tol (25%) but not
    # watermark_tol (50%): exactly one class fires
    _write_round(tmp_path, 1, {"foo_ms": 10.0})
    _write_round(tmp_path, 2, {"foo_ms": 10.2})
    _write_round(tmp_path, 3, {"foo_ms": 13.2})
    rep = trend.analyze(repo=str(tmp_path))
    kinds = [f["kind"] for f in rep["flags"]]
    assert kinds == ["trend_regression"]


def test_rig_switch_never_compares_across_rigs(tmp_path):
    """A new rig's wildly different absolutes are a NEW series, not a
    regression (the r06 cpu-world1 situation) — and quarantined keys
    are tracked but never flagged."""
    _write_round(tmp_path, 1, {"foo_ms": 10.0})
    _write_round(tmp_path, 2, {"foo_ms": 10.1})
    _write_round(tmp_path, 3, {
        "rig": "cpu-x", "foo_ms": 4000.0,
        "cpu_incomparable": {"foo_ms": 9999.0},
    })
    rep = trend.analyze(repo=str(tmp_path))
    assert rep["flags"] == []
    assert "foo_ms [cpu-x]" in rep["series"]
    assert "foo_ms [cpu-x-quarantine]" in rep["series"]
    # the default-rig series simply has no newer artifact — r02 IS the
    # default rig's newest, so nothing is "missing"
    assert rep["newest"]["default"].endswith("r02.json")


def test_stable_series_and_neutral_keys_are_clean(tmp_path):
    _write_round(tmp_path, 1, {"foo_ms": 10.0, "ep_moe_chunks": 1})
    _write_round(tmp_path, 2, {"foo_ms": 10.4, "ep_moe_chunks": 4})
    rep = trend.analyze(repo=str(tmp_path))
    assert rep["flags"] == []
    # the only notes a clean corpus may carry are the stale_ack
    # bookkeeping rows: every repo-level ACKNOWLEDGED entry matches no
    # flag HERE, and the sentinel says so rather than silently
    # accreting mutes (one row per ledger entry)
    assert ([n["kind"] for n in rep["notes"]]
            == ["stale_ack"] * len(trend.ACKNOWLEDGED))


def test_acknowledgement_is_kind_scoped(tmp_path):
    """An ack mutes exactly its (key, kind): a WATERMARK break on the
    acknowledged key still fails the gate (the overbroad-mute class)."""
    key, kind = next(k for k in trend.ACKNOWLEDGED
                     if k[1] == "trend_regression")
    _write_round(tmp_path, 1, {key: 10.0})
    _write_round(tmp_path, 2, {key: 10.2})
    _write_round(tmp_path, 3, {key: 99.0})  # way past watermark_tol
    rep = trend.analyze(repo=str(tmp_path))
    kinds = {f["kind"]: f for f in rep["flags"] if f["key"] == key}
    assert "watermark_break" in kinds
    assert not kinds["watermark_break"]["acknowledged"]
    assert kind not in kinds or kinds[kind]["acknowledged"]
    assert trend.unacknowledged(rep)


def test_multichip_state_going_backwards_is_flagged(tmp_path):
    _write_round(tmp_path, 1, {"foo_ms": 10.0})
    _write_multichip(tmp_path, 1, ok=True)
    _write_multichip(tmp_path, 2, ok=False, rc=1)
    rep = trend.analyze(repo=str(tmp_path))
    kinds = [f["kind"] for f in rep["flags"]]
    assert kinds.count("multichip_regression") == 2  # rc!=0 AND ok lost


def test_strict_mode_raises_on_unreadable_artifact(tmp_path):
    (tmp_path / "BENCH_r01.json").write_text("{not json")
    with pytest.raises(ValueError, match="unreadable"):
        trend.analyze(repo=str(tmp_path), strict=True)
    # non-strict skips it (the claims-lint compatibility behavior)
    assert trend.analyze(repo=str(tmp_path))["series"] == {}


# ---------- the real series (acceptance criterion) ----------


def test_real_series_has_zero_unacknowledged_flags():
    """The sentinel on the committed artifacts (r01 and the cpu-world1
    r06-r09; the r02-r05 chip records were deleted in PR 24): zero
    FALSE positives — every flag carries an ACKNOWLEDGED reason
    (today: the two rig-local absolute arms), so the CI gate exits 0. A
    new unexplained flag here means either a real regression (fix it)
    or a detector bug (fix that) — never 'loosen the test'."""
    rep = trend.analyze(repo=REPO, strict=True)
    unack = trend.unacknowledged(rep)
    assert unack == [], unack
    acked = {(f["key"], f["kind"]) for f in rep["flags"]
             if f["acknowledged"]}
    assert acked == set(trend.ACKNOWLEDGED), acked
    # every ACKNOWLEDGED entry still earns its keep on the real series
    assert not any(n["kind"] == "stale_ack" for n in rep["notes"])
    # what remains is one rig: no default-rig (chip) series is left to
    # mix with, and the cpu rig's multi-point series all survived
    assert not any(k.endswith("[default]") for k in rep["series"])
    assert len(rep["series"]["serve_tokens_per_s [cpu-world1]"]) == 4


def test_report_document_roundtrip_and_strictness(tmp_path):
    rep = trend.analyze(repo=REPO)
    trend.check_report(rep)
    with pytest.raises(ValueError, match="not a perf-trend report"):
        trend.check_report({"magic": "nope"})
    with pytest.raises(ValueError, match="missing"):
        trend.check_report({"magic": trend.TREND_MAGIC, "series": {},
                            "flags": [], "notes": []})
    md = trend.render_markdown(rep)
    assert "Perf-trend sentinel report" in md
    assert "plan_decode_ms" in md


# ---------- the CLI (the CI gate's exact entry point) ----------


def _cli():
    spec = importlib.util.spec_from_file_location(
        "_tdt_perf_trend", os.path.join(REPO, "scripts",
                                        "perf_trend.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_perf_trend_cli_green_on_real_series(tmp_path):
    cli = _cli()
    out = str(tmp_path / "rep")
    assert cli.main(["--out", out, "-q"]) == 0
    assert os.path.isfile(os.path.join(out, "report.md"))
    doc = json.loads(open(os.path.join(out, "report.json")).read())
    trend.check_report(doc)


def test_perf_trend_cli_red_on_unacknowledged_regression(tmp_path):
    corpus = tmp_path / "corpus"
    corpus.mkdir()
    _write_round(corpus, 1, {"foo_ms": 10.0})
    _write_round(corpus, 2, {"foo_ms": 99.0})
    cli = _cli()
    assert cli.main(["--repo", str(corpus),
                     "--out", str(tmp_path / "rep"), "-q"]) == 1


def test_perf_trend_cli_usage_error_on_malformed_artifact(tmp_path):
    corpus = tmp_path / "corpus"
    corpus.mkdir()
    (corpus / "BENCH_r01.json").write_text("{torn")
    cli = _cli()
    assert cli.main(["--repo", str(corpus),
                     "--out", str(tmp_path / "rep"), "-q"]) == 2
