#!/usr/bin/env python
"""Lint numeric perf claims against the artifact of record.

Three rounds running, the AG+GEMM docstring claimed "0.98-1.00x of XLA"
while the driver-captured `pallas_vs_xla` printed 1.10 — prose drifts,
the artifact does not. This linter makes such drift a nonzero exit:

1. Every perf claim in kernel docstrings / docs is written in the
   lintable bracket form `[perf:KEY=LO-HI]` (KEY a bench.py schema key,
   LO-HI the claimed inclusive band; `[perf:KEY=V]` claims the exact
   value within FLOAT_TOL). Freeform "0.98x of XLA" prose is decoration;
   the bracket is the claim.
2. Each claim KEY must exist in bench.py's result schema
   (_NUMERIC_KEYS) — a renamed or typo'd metric fails here, so a claim
   can never silently detach from the measurement.
3. Claims are checked against the measured value per key — the newest
   BENCH_r*.json carrying that key wins (so a round whose arm errored
   falls back to the last round that measured it), then
   BASELINE.json["published"]. Measured outside the claimed band =
   contradiction = exit 1. No artifact at all skips only this step.
4. REQUIRED_CLAIMS pins where the load-bearing claims must live:
   deleting the AG+GEMM parity sentence (instead of correcting it) is
   itself a failure, and so is a required claim no artifact backs.

Exit codes (CI contract; wired into __graft_entry__'s dryrun plane next
to verify_kernels.py):

  0  all claims present, schema-valid, and consistent with the artifact
  1  contradiction, unknown schema key, or missing required claim
  2  usage error

Pure file I/O + an ast read of bench.py's schema literal — no jax, no
package import; runs anywhere in milliseconds.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# [perf:key=lo-hi] or [perf:key=value]; a markdown/docstring-safe token
CLAIM_RE = re.compile(
    r"\[perf:([A-Za-z0-9_]+)=([0-9]*\.?[0-9]+)(?:-([0-9]*\.?[0-9]+))?\]"
)

# files scanned for claims (repo-relative globs)
SCAN_GLOBS = (
    "triton_dist_tpu/**/*.py",
    "docs/*.md",
    "bench.py",
)

# (key, repo-relative file) pairs that MUST carry a claim: the
# historically drifting ones, plus the serving plane's load-bearing
# batching claim (ISSUE 6). Removing the sentence is as loud as
# contradicting it.
REQUIRED_CLAIMS = (
    # (pallas_vs_xla / gemm_rs_vs_xla left this list in PR 24 with the
    # chip records that backed them: a claim no artifact measures is
    # removed, not required)
    ("serve_vs_seq_tokens", "docs/serving.md"),
    ("sp_prefill_vs_ring", "triton_dist_tpu/kernels/flash_prefill.py"),
    ("sp_prefill_vs_ring", "docs/performance.md"),
    ("sp_prefill_vs_xla", "docs/performance.md"),
    ("allreduce_wire_fp8_vs_native",
     "triton_dist_tpu/kernels/allreduce.py"),
    ("allreduce_wire_fp8_vs_native", "docs/performance.md"),
    ("ag_gemm_wire_fp8_vs_native", "docs/performance.md"),
    # spec decoding + radix prefix cache (ISSUE 14)
    ("spec_vs_plain_tokens", "docs/serving.md"),
    ("prefix_hit_ttft", "docs/serving.md"),
    # fusion planner (ISSUE 17): the parity audit and the recovered
    # misroute are the planner's load-bearing measurements
    ("plan_vs_hand_prefill", "docs/performance.md"),
    ("plan_recover_misroute_ratio", "docs/performance.md"),
    # disaggregated prefill/decode + 2-level collectives (ISSUE 18)
    ("xslice_disagg_vs_single_tokens", "docs/serving.md"),
    ("xslice_ag_vs_flat", "docs/performance.md"),
    # the tuning loop (ISSUE 20): the cache-winner launch must never
    # measure worse than the hard-coded default it overrides
    ("gemm_rs_tuned_vs_default", "docs/performance.md"),
    ("flash_prefill_tuned_vs_default", "docs/performance.md"),
)

# Keys whose claims are REQUIRED but whose first measurement is still
# in flight. Each entry names the bench ROUND whose artifact must carry
# the key: the grace holds only while the newest BENCH_r*.json predates
# that round, and the rule closes BY ITSELF the moment a
# round-N-or-later artifact exists — measured: the claim is checked;
# absent: the required claim is unbacked and FAILS (no manual
# bookkeeping left to forget). Emptied in round 6 (ISSUE 12):
# BENCH_r06.json — the first serving-era artifact, produced on the
# documented cpu-world1 rig (docs/performance.md "Rigs") — carried all
# five formerly-graced keys. ISSUE 14 re-arms the mechanism for the
# spec/prefix families: BENCH_r07.json (same rig) already measures
# both, so the grace below is normally inert — it only bites if a
# later round drops the arms, and it dies by itself at round 14.
PENDING_FIRST_ARTIFACT = {
    "spec_vs_plain_tokens": 14,
    "prefix_hit_ttft": 14,
    # ISSUE 17: BENCH_r08.json (cpu-world1 rig) measures the planner
    # family; as with the spec keys the grace is normally inert — it
    # bites only if a later round drops the arms, and dies at round 17
    "plan_vs_hand_prefill": 17,
    "plan_recover_misroute_ratio": 17,
    # ISSUE 18: the xslice families shipped before their first bench
    # round; BENCH_r09.json (cpu-world1 rig) measures both, so the
    # grace is retired to inert — it bites only if a later round drops
    # the arms, and dies by itself at round 19
    "xslice_disagg_vs_single_tokens": 19,
    "xslice_ag_vs_flat": 19,
    # ISSUE 20: the tuning-loop family lands measured in the same
    # round it ships (BENCH_r09.json), so this grace is inert from
    # birth — it bites only if a later round drops the sweep, and dies
    # by itself at round 20
    "gemm_rs_tuned_vs_default": 20,
    "flash_prefill_tuned_vs_default": 20,
}


def _artifact_round(label) -> int:
    """Round number of an artifact label ('BENCH_r06.json' -> 6);
    0 when unparsable (BASELINE.json: predates every round)."""
    m = re.search(r"BENCH_r(\d+)", label or "")
    return int(m.group(1)) if m else 0

FLOAT_TOL = 0.005  # slack for exact-value claims (rounding in the JSON)


def _bench_numeric_keys(repo: str):
    """The _NUMERIC_KEYS set literal, read via ast — importing bench.py
    would drag in jax + the whole package for a pure text lint (this
    CLI must run anywhere in milliseconds, like scripts/lint.py)."""
    import ast

    with open(os.path.join(repo, "bench.py"), encoding="utf-8") as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if (isinstance(node, ast.Assign)
                and any(isinstance(t, ast.Name) and t.id == "_NUMERIC_KEYS"
                        for t in node.targets)):
            return set(ast.literal_eval(node.value))
    return None  # caller reports: schema check impossible


def collect_claims(repo: str):
    """[(relpath, key, lo, hi)] over every scanned file."""
    out = []
    for pattern in SCAN_GLOBS:
        for path in sorted(glob.glob(os.path.join(repo, pattern),
                                     recursive=True)):
            rel = os.path.relpath(path, repo)
            try:
                with open(path, encoding="utf-8") as f:
                    text = f.read()
            except OSError:
                continue
            for m in CLAIM_RE.finditer(text):
                key, lo = m.group(1), float(m.group(2))
                hi = float(m.group(3)) if m.group(3) else None
                if hi is None:
                    lo, hi = lo - FLOAT_TOL, lo + FLOAT_TOL
                out.append((rel, key, lo, hi))
    return out


def artifact_series(repo: str, strict: bool = False):
    """Every BENCH_r*.json in round order (oldest first) as
    (label, round, parsed) triples — THE artifact reader, shared
    between the claims lint below and the perf-trend sentinel
    (triton_dist_tpu/obs/trend.py + scripts/perf_trend.py), so the two
    tools can never disagree about what an artifact says. Artifacts
    without a parsed dict (round 1 predates the schema) are skipped;
    unreadable JSON is skipped here and a ValueError under `strict`
    (the sentinel's malformed-input contract)."""
    out = []
    for path in sorted(glob.glob(os.path.join(repo, "BENCH_r*.json"))):
        label = os.path.basename(path)
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            if strict:
                raise ValueError(f"{label}: unreadable artifact: {e}")
            continue
        parsed = doc.get("parsed") if isinstance(doc, dict) else None
        if isinstance(parsed, dict):
            out.append((label, _artifact_round(label), parsed))
    return out


def latest_measured(repo: str):
    """(label, {key: (value, source_label)}) over BENCH_r*.json newest
    first, then BASELINE.json["published"]. Per KEY the newest artifact
    carrying it wins — a round whose arm errored (key absent) falls
    back to the last round that measured it, so a claim never silently
    detaches from measurement just because the newest run dropped the
    field. Returns (None, {}) when no artifact exists at all."""
    sources = [(label, parsed)
               for label, _rnd, parsed in reversed(artifact_series(repo))]
    base = os.path.join(repo, "BASELINE.json")
    try:
        with open(base) as f:
            pub = json.load(f).get("published", {})
        if isinstance(pub, dict) and pub:
            sources.append(("BASELINE.json", pub))
    except (OSError, ValueError):
        pass
    measured = {}
    for label, flat in sources:
        for k, v in flat.items():
            if (k not in measured and isinstance(v, (int, float))
                    and not isinstance(v, bool)):
                measured[k] = (v, label)
    return (sources[0][0] if sources else None), measured


def check(repo: str = _REPO, verbose: bool = False) -> int:
    claims = collect_claims(repo)
    schema = _bench_numeric_keys(repo)
    problems = []

    if schema is None:
        problems.append("bench.py: could not locate the _NUMERIC_KEYS "
                        "set literal — schema check impossible")
        schema = set()

    for key, rel in REQUIRED_CLAIMS:
        if not any(c[0] == rel and c[1] == key for c in claims):
            problems.append(
                f"{rel}: required [perf:{key}=...] claim is MISSING "
                "(correct the claim, don't delete it)")

    for rel, key, lo, hi in claims:
        if key not in schema:
            problems.append(
                f"{rel}: claim key {key!r} is not in bench.py's result "
                "schema (_NUMERIC_KEYS) — typo or stale rename")

    label, measured = latest_measured(repo)
    required_keys = {k for k, _ in REQUIRED_CLAIMS}
    if label is None:
        print("check_perf_claims: no BENCH_r*.json / published baseline "
              "— schema + presence checks only", file=sys.stderr)
    for rel, key, lo, hi in claims:
        got, src = measured.get(key, (None, None))
        status = "unmeasured"
        if got is not None:
            ok = lo <= got <= hi
            status = f"measured {got} [{src}] " \
                     f"({'ok' if ok else 'CONTRADICTED'})"
            if not ok:
                problems.append(
                    f"{rel}: claims {key} in [{lo}, {hi}] but {src} "
                    f"measured {got}")
        elif label is not None and key in required_keys:
            first_round = PENDING_FIRST_ARTIFACT.get(key)
            if (first_round is not None
                    and _artifact_round(label) < first_round):
                print(f"check_perf_claims: {rel}: {key!r} awaits its "
                      f"first bench artifact (round >= {first_round}; "
                      f"newest is {label})", file=sys.stderr)
            else:
                # fail CLOSED: a load-bearing claim no artifact (current
                # or prior) backs is exactly the silent detachment this
                # tool exists to prevent
                problems.append(
                    f"{rel}: required claim {key!r} is not measured by "
                    "ANY bench artifact — the claim is unbacked")
        if verbose:
            print(f"{rel}: [perf:{key}={lo}-{hi}] {status}")

    for p in problems:
        print(f"check_perf_claims: {p}", file=sys.stderr)
    n = len(claims)
    print(f"check_perf_claims: {n} claim(s) vs {label or '<none>'}, "
          f"{len(problems)} problem(s)")
    return 1 if problems else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args(argv)
    return check(verbose=args.verbose)


if __name__ == "__main__":
    sys.exit(main())
