"""Perf-trend regression sentinel — the artifact series grown teeth
(ISSUE 13, ROADMAP item 1's measurement debt made standing).

`scripts/check_perf_claims.py` guards each claim against the NEWEST
artifact carrying its key — a point check against a hand-maintained
band. This module reads the FULL BENCH_r*.json / MULTICHIP_r*.json
series (through check_perf_claims' own reader, `artifact_series` —
reused, not re-implemented, so the two tools can never disagree about
what an artifact says) and flags what a band cannot see:

  trend_regression   the newest point of a (key, rig) series is worse
                     than the MEDIAN of its prior points by more than
                     `trend_tol` (default 25%) — a drift no band edge
                     has been crossed by yet.
  watermark_break    the newest point is worse than the series'
                     BEST-EVER point by more than `watermark_tol`
                     (default 50%) — a capability the repo once
                     demonstrated and lost.
  band_violation     the newest point contradicts a `[perf:...]` claim
                     band (check_perf_claims' contradiction, restated
                     per-series so the report is one document).
  missing_family     a key a rig measured in an earlier round is absent
                     from that rig's newest artifact — an arm that
                     silently errored out of the schema.
  multichip_*        the MULTICHIP series' ok/rc/skipped state went
                     backwards.

plus non-fatal NOTES: `band_drift` (inside the band but within
`drift_margin` of the adverse edge) and `improvement` (newest beats the
best prior point).

RIG-AWARENESS is the load-bearing part: BENCH_r06 comes from the
reduced cpu-world1 rig (docs/performance.md "Rigs") and its values are
incomparable with the r02-r05 TPU points, so every series is keyed
(key, rig) — per-key newest-wins within a rig, never across. Keys an
artifact quarantines under `parsed.cpu_incomparable` land in a
`<rig>-quarantine` series that is tracked but NEVER flagged.

Direction: most keys are latency/ratio shaped (lower is better);
throughput keys (`*tokens_per_s*`, the serving speedup ratios) invert.
Neutral keys (config echoes like window steps, the model-derived HBM
floors) are tracked, never flagged.

Acknowledgement: a flagged regression that is UNDERSTOOD gets an entry
in ACKNOWLEDGED ((key, flag kind) -> reason — kind-scoped, so muting a
known trend drift never mutes a future watermark break on the same
key). Acknowledged flags stay in the report (with their reason inline)
but do not fail the CI gate (`scripts/perf_trend.py` exit 1 is
UNacknowledged flags only) — the PENDING_FIRST_ARTIFACT pattern: the
bookkeeping lives next to the rule, and an ack whose flag no longer
fires is reported as a stale_ack note so the ledger shrinks back.
"""

from __future__ import annotations

import importlib.util
import json
import os
import statistics
from typing import Dict, List, Optional, Tuple

TREND_MAGIC = "tdt-perf-trend"

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# keys that mirror the per-round headline metric (whose NAME changes
# round to round) — their cross-round series compares different
# quantities, so they are skipped entirely
SKIP_KEYS = {"value", "vs_baseline"}

# tracked in the report, never flagged: config echoes, model-derived
# constants, pressure stats whose "direction" is workload-shaped
NEUTRAL_KEYS = {
    "serve_resident_window_steps",
    "serve_resident_ring_depth_max", "serve_resident_ring_depth_mean",
    "ep_moe_chunks", "ep_moe_drop_frac",
    "mega_8b_hbm_floor_ms", "mega_32b_hbm_floor_ms",
    "faults_guard_trips", "obs_stat_events",
    # planner parity ratios sit at ~1.0 by construction (bit-identical
    # programs; tests/test_plan.py) — movement is host-timer noise,
    # not a regression direction
    "plan_vs_hand_prefill", "plan_vs_hand_decode",
}

# throughput-shaped keys: HIGHER is better (everything else numeric
# defaults to lower-is-better — latency, time ratios, overhead fracs)
HIGHER_IS_BETTER_SUFFIXES = ("tokens_per_s",)
HIGHER_IS_BETTER = {
    "serve_vs_seq_tokens",        # batched/sequential throughput ratio
    "serve_resident_vs_hostloop",  # resident/host-loop throughput ratio
    "spec_vs_plain_tokens",       # spec/plain-decode throughput ratio
    "spec_accept_rate",           # accepted/proposed draft tokens
    "plan_recover_misroute_ratio",  # misrouted/planned — the
                                    # regression the planner removes
}

# (key, flag kind) -> reason. The scope is deliberately NARROW: an ack
# mutes exactly one flag class on one key — a future watermark_break or
# band_violation on the same key still fails the gate. An acknowledged
# flag reports WITH its reason; an ack that matched NO flag is itself
# reported as a stale_ack note (the series recovered — delete the
# entry).
ACKNOWLEDGED = {
    # the ("a2a_dispatch_us", "trend_regression") ack left in PR 24
    # with the r02-r05 records whose +39% move it explained: with the
    # series gone it matched no flag (a stale_ack)
    ("allreduce_wire_native_us", "watermark_break"): (
        "2-core rig-local absolute arm, not a codec change: r08 read "
        "the native ring at 1221us vs the 798-819us of r06/r07 while "
        "the fp8/int8 ABSOLUTE arms stayed flat (~31ms/~16ms — their "
        "vs_native ratios moved inversely, 39->26 and 19->12, exactly "
        "as a slow native denominator predicts). The cpu-world1 rig "
        "only claims ratios (docs/performance.md 'Rigs'); the "
        "watermark re-arms on the next artifact inside tolerance."),
    # the round-7 ("sp_prefill_vs_ring", "trend_regression") ack was
    # deleted in round 8: r08 measured the arm back inside tolerance,
    # turning the entry into a stale_ack note (the series recovered)
    ("plan_decode_ms", "trend_regression"): (
        "2-core rig-local absolute arm, not a planner change: r09 "
        "read the planned decode step at 11.4ms vs the 7.5-8.8ms of "
        "r07/r08 while the SAME-RUN hand-routed denominator moved "
        "with it (plan_vs_hand_decode 0.79, the best ratio of the "
        "series — a slow machine, not a slow plan; the r09 routing "
        "is byte-identical to r08's committed PLAN_TABLE.json, "
        "plan_report --diff 0 flips). The cpu-world1 rig only claims "
        "ratios (docs/performance.md 'Rigs'); the trend re-arms on "
        "the next artifact inside tolerance."),
}


def higher_is_better(key: str) -> bool:
    return key in HIGHER_IS_BETTER or any(
        s in key for s in HIGHER_IS_BETTER_SUFFIXES)


_CLAIMS_MOD_CACHE: Dict[str, object] = {}


def _claims_mod(repo: str):
    """Load scripts/check_perf_claims.py by path — ITS parsing is the
    one artifact-reading definition (see module doc); the package must
    not fork it, and the script deliberately is not a package. The
    script is taken from the analyzed repo when it ships one, else
    from THIS package's repo (so a synthetic artifact corpus in a bare
    tmp dir still reads through the shared parser). Cached per path:
    one analyze() must read ONE on-disk version of the script."""
    path = os.path.join(repo, "scripts", "check_perf_claims.py")
    if not os.path.isfile(path):
        path = os.path.join(_REPO, "scripts", "check_perf_claims.py")
    mod = _CLAIMS_MOD_CACHE.get(path)
    if mod is None:
        spec = importlib.util.spec_from_file_location(
            "_tdt_check_perf_claims", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _CLAIMS_MOD_CACHE[path] = mod
    return mod


def bench_series(repo: str = _REPO, strict: bool = False
                 ) -> Dict[Tuple[str, str], List[dict]]:
    """(key, rig) -> [{round, label, value}] oldest-first over every
    BENCH_r*.json, via check_perf_claims.artifact_series. Quarantined
    keys (`parsed.cpu_incomparable`) ride under rig
    `<rig>-quarantine`."""
    mod = _claims_mod(repo)
    series: Dict[Tuple[str, str], List[dict]] = {}

    def add(key, rig, rnd, label, value):
        if key in SKIP_KEYS:
            return
        if isinstance(value, bool) or not isinstance(value, (int, float)):
            return
        series.setdefault((key, rig), []).append(
            {"round": rnd, "label": label, "value": float(value)})

    for label, rnd, parsed in mod.artifact_series(repo, strict=strict):
        rig = parsed.get("rig", "default")
        for k, v in parsed.items():
            if k == "cpu_incomparable" and isinstance(v, dict):
                for qk, qv in v.items():
                    add(qk, f"{rig}-quarantine", rnd, label, qv)
                continue
            add(k, rig, rnd, label, v)
    return series


def multichip_series(repo: str = _REPO, strict: bool = False
                     ) -> List[dict]:
    """[{label, round, ok, rc, skipped, n_devices}] oldest-first over
    MULTICHIP_r*.json. strict raises on unreadable/structurally
    malformed artifacts."""
    import glob
    import re

    out = []
    for path in sorted(glob.glob(os.path.join(repo,
                                              "MULTICHIP_r*.json"))):
        label = os.path.basename(path)
        m = re.search(r"MULTICHIP_r(\d+)", label)
        rnd = int(m.group(1)) if m else 0
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            if strict:
                raise ValueError(f"{label}: unreadable artifact: {e}")
            continue
        if not isinstance(doc, dict) or "ok" not in doc \
                or "rc" not in doc:
            if strict:
                raise ValueError(f"{label}: not a MULTICHIP artifact "
                                 "(ok/rc missing)")
            continue
        out.append({
            "label": label, "round": rnd, "ok": bool(doc["ok"]),
            "rc": int(doc["rc"]), "skipped": bool(doc.get("skipped")),
            "n_devices": doc.get("n_devices"),
        })
    return out


def _claim_bands(repo: str) -> Dict[str, Tuple[float, float]]:
    """key -> tightest claimed (lo, hi) over every [perf:...] bracket
    (check_perf_claims.collect_claims reused)."""
    mod = _claims_mod(repo)
    bands: Dict[str, Tuple[float, float]] = {}
    for _rel, key, lo, hi in mod.collect_claims(repo):
        cur = bands.get(key)
        bands[key] = (max(lo, cur[0]) if cur else lo,
                      min(hi, cur[1]) if cur else hi)
    return bands


def _worse_by(newest: float, ref: float, key: str) -> Optional[float]:
    """Relative amount `newest` is WORSE than `ref` (None when the
    comparison is degenerate — a zero reference)."""
    if higher_is_better(key):
        if newest <= 0:
            return None
        return ref / newest - 1.0
    if ref <= 0:
        return None
    return newest / ref - 1.0


def _flag(key, rig, kind, detail) -> dict:
    ack = ACKNOWLEDGED.get((key, kind))
    return {"key": key, "rig": rig, "kind": kind, "detail": detail,
            "acknowledged": ack is not None, "ack": ack}


def analyze(repo: str = _REPO, trend_tol: float = 0.25,
            watermark_tol: float = 0.50, drift_margin: float = 0.05,
            strict: bool = False) -> dict:
    """The sentinel: build the rig-aware series, apply the flag rules
    (module doc), return the report document (magic tdt-perf-trend).
    Deterministic: same artifacts -> same report."""
    series = bench_series(repo, strict=strict)
    bands = _claim_bands(repo)
    flags: List[dict] = []
    notes: List[dict] = []

    # newest round per rig (missing-family needs it)
    newest_round: Dict[str, int] = {}
    newest_label: Dict[str, str] = {}
    for (key, rig), pts in series.items():
        last = pts[-1]
        if last["round"] >= newest_round.get(rig, -1):
            newest_round[rig] = last["round"]
            newest_label[rig] = last["label"]

    for (key, rig), pts in sorted(series.items()):
        if rig.endswith("-quarantine") or key in NEUTRAL_KEYS:
            continue
        newest = pts[-1]["value"]
        at_newest = pts[-1]["round"] == newest_round.get(rig)

        # missing-family: measured before, absent from the rig's
        # newest artifact
        if not at_newest:
            flags.append(_flag(
                key, rig, "missing_family",
                f"last measured in {pts[-1]['label']} "
                f"({pts[-1]['value']}); absent from the {rig} rig's "
                f"newest artifact {newest_label.get(rig)} — the arm "
                "silently dropped out of the schema"))
            continue

        prior = [p["value"] for p in pts[:-1]]
        if prior:
            med = statistics.median(prior)
            best = (max(prior) if higher_is_better(key)
                    else min(prior))
            w_med = _worse_by(newest, med, key)
            w_best = _worse_by(newest, best, key)
            if w_best is not None and w_best > watermark_tol:
                flags.append(_flag(
                    key, rig, "watermark_break",
                    f"newest {newest} is {w_best:+.0%} worse than the "
                    f"best-ever {best} (tol {watermark_tol:.0%})"))
            elif w_med is not None and w_med > trend_tol:
                flags.append(_flag(
                    key, rig, "trend_regression",
                    f"newest {newest} is {w_med:+.0%} worse than the "
                    f"prior median {med} (tol {trend_tol:.0%})"))
            if w_best is not None and w_best < -trend_tol:
                notes.append({
                    "key": key, "rig": rig, "kind": "improvement",
                    "detail": f"newest {newest} beats the best prior "
                              f"{best} by {-w_best:.0%}"})

        band = bands.get(key)
        if band is not None:
            lo, hi = band
            if not (lo <= newest <= hi):
                flags.append(_flag(
                    key, rig, "band_violation",
                    f"newest {newest} outside the claimed band "
                    f"[{lo}, {hi}]"))
            else:
                edge = lo if higher_is_better(key) else hi
                rel = abs(newest - edge) / max(abs(edge), 1e-12)
                if rel < drift_margin:
                    notes.append({
                        "key": key, "rig": rig, "kind": "band_drift",
                        "detail": f"newest {newest} is within "
                                  f"{rel:.1%} of the adverse band edge "
                                  f"{edge} — the next wiggle "
                                  "contradicts the claim"})

    mseries = multichip_series(repo, strict=strict)
    if mseries:
        last = mseries[-1]
        prior_ok = any(m["ok"] for m in mseries[:-1])
        if last["rc"] != 0:
            flags.append(_flag("multichip", "multichip",
                               "multichip_regression",
                               f"{last['label']} exited rc="
                               f"{last['rc']}"))
        if not last["ok"] and prior_ok:
            flags.append(_flag(
                "multichip", "multichip", "multichip_regression",
                f"{last['label']} ok=false while an earlier round "
                "passed"))
        if last["skipped"] and any(not m["skipped"]
                                   for m in mseries[:-1]):
            flags.append(_flag(
                "multichip", "multichip", "multichip_regression",
                f"{last['label']} skipped while earlier rounds ran"))

    # stale acks: an ACKNOWLEDGED entry that matched no flag means the
    # series recovered (or the key/kind was typo'd) — surface it so the
    # ledger shrinks back instead of silently accreting mutes
    matched = {(f["key"], f["kind"]) for f in flags
               if f["acknowledged"]}
    for (key, kind) in sorted(ACKNOWLEDGED):
        if (key, kind) not in matched:
            notes.append({
                "key": key, "rig": "-", "kind": "stale_ack",
                "detail": f"ACKNOWLEDGED[({key!r}, {kind!r})] matched "
                          "no flag — the series recovered; delete the "
                          "entry"})

    unack = [f for f in flags if not f["acknowledged"]]
    return {
        "magic": TREND_MAGIC,
        "newest": newest_label,
        "series": {
            f"{key} [{rig}]": pts
            for (key, rig), pts in sorted(series.items())
        },
        "multichip": mseries,
        "flags": flags,
        "notes": notes,
        "summary": {
            "n_series": len(series),
            "n_flags": len(flags),
            "n_unacknowledged": len(unack),
            "n_notes": len(notes),
        },
    }


def unacknowledged(report: dict) -> List[dict]:
    return [f for f in report["flags"] if not f["acknowledged"]]


def check_report(doc: dict) -> dict:
    """Validate a sentinel report document (the trace_report --trend
    strictness contract); returns it. ValueError on malformed input."""
    if not isinstance(doc, dict) or doc.get("magic") != TREND_MAGIC:
        raise ValueError(
            f"not a perf-trend report (magic="
            f"{doc.get('magic') if isinstance(doc, dict) else None!r} "
            f"!= {TREND_MAGIC!r})")
    for sect in ("series", "flags", "notes", "summary"):
        if sect not in doc:
            raise ValueError(f"report section {sect!r} missing")
    if not isinstance(doc["flags"], list):
        raise ValueError("report 'flags' is not a list")
    for i, f in enumerate(doc["flags"]):
        if not isinstance(f, dict) or "key" not in f or "kind" not in f \
                or "acknowledged" not in f:
            raise ValueError(f"report flags[{i}] malformed")
    return doc


def render_markdown(report: dict) -> str:
    """The human half of the report — committed beside the artifacts
    (docs/perf_trend.md) and uploaded by CI."""
    lines = ["# Perf-trend sentinel report", ""]
    lines.append("Newest artifact per rig: "
                 + (", ".join(f"`{rig}` → {lbl}" for rig, lbl
                              in sorted(report["newest"].items()))
                    or "none"))
    s = report["summary"]
    lines.append("")
    lines.append(f"{s['n_series']} series · {s['n_flags']} flag(s) "
                 f"({s['n_unacknowledged']} unacknowledged) · "
                 f"{s['n_notes']} note(s)")
    lines.append("")
    if report["flags"]:
        lines.append("## Flags")
        lines.append("")
        lines.append("| key | rig | kind | detail | ack |")
        lines.append("|---|---|---|---|---|")
        for f in report["flags"]:
            ack = f["ack"] or ("yes" if f["acknowledged"] else "**NO**")
            lines.append(f"| `{f['key']}` | {f['rig']} | {f['kind']} | "
                         f"{f['detail']} | {ack} |")
        lines.append("")
    if report["notes"]:
        lines.append("## Notes (non-fatal)")
        lines.append("")
        lines.append("| key | rig | kind | detail |")
        lines.append("|---|---|---|---|")
        for n in report["notes"]:
            lines.append(f"| `{n['key']}` | {n['rig']} | {n['kind']} | "
                         f"{n['detail']} |")
        lines.append("")
    lines.append("## Multi-point series (tails)")
    lines.append("")
    lines.append("| series | points | values (oldest → newest) |")
    lines.append("|---|---|---|")
    for name, pts in sorted(report["series"].items()):
        if len(pts) < 2:
            continue
        vals = " → ".join(str(p["value"]) for p in pts)
        lines.append(f"| `{name}` | {len(pts)} | {vals} |")
    single = sum(1 for pts in report["series"].values()
                 if len(pts) < 2)
    lines.append("")
    lines.append(f"({single} single-point series omitted — they grow "
                 "teeth on their second artifact.)")
    if report["multichip"]:
        lines.append("")
        lines.append("## MULTICHIP series")
        lines.append("")
        lines.append("| round | ok | rc | skipped |")
        lines.append("|---|---|---|---|")
        for m in report["multichip"]:
            lines.append(f"| {m['label']} | {m['ok']} | {m['rc']} | "
                         f"{m['skipped']} |")
    lines.append("")
    return "\n".join(lines)
