#!/usr/bin/env python
"""trace_report — per-region attribution + predicted-stall diff from an
exported trace JSON, with render modes for the other observability
artifacts: --metrics (registry snapshots / flight dumps), --requests
(per-request ledgers), --trend (perf-trend sentinel reports).

Usage:
    python scripts/trace_report.py TRACE.json [TRACE2.json ...]
    python scripts/trace_report.py --metrics SNAP_OR_DUMP.json [...]
    python scripts/trace_report.py --requests LEDGER.json [...]
    python scripts/trace_report.py --trend REPORT.json [...]

Default mode reads Perfetto/Chrome-trace JSONs written by
`trace.write_trace` (examples/12_trace_overlap.py, `bench.py --trace`),
and prints:

  * per-stream attribution: compute / sem_wait / dma_wait fractions of
    the traced span time (from the events' `cat` classification);
  * a per-region table (total span time + span/instant counts);
  * for megakernel traces that embedded an `attribution.
    compare_predicted` report (otherData["compare_predicted"]), the
    measured-vs-predicted scoreboard-stall diff per (rank, queue).

`--metrics` mode reads the always-on tier's artifacts — a metrics
registry snapshot (`obs.write_snapshot`, magic "tdt-metrics") or a
flight-recorder dump (`FlightRecorder.dump`, magic "tdt-flight") — and
renders them in the same table style: counters/gauges/histogram
quantiles for a snapshot; the per-step ring (metric deltas, scheduler
state, decoded guard rows) for a dump.

`--requests` renders a per-request attribution ledger
(`trace.write_ledger`, magic "tdt-req-ledger"; ISSUE 13): one row per
request — queued / inject-wait / prefill / decode decomposition, the
close fraction, device-step share. `--trend` renders a perf-trend
sentinel report (`scripts/perf_trend.py --out`'s report.json, magic
"tdt-perf-trend"): the flags/notes tables plus the multi-point series.

Exits non-zero on a malformed input in EVERY mode (missing magic tag,
torn histograms, dump snapshots without their guard-row lists) — the
bench.check_result strictness contract: a tool that silently renders a
clobbered artifact would hide exactly the bugs it exists to catch.
"""

from __future__ import annotations

import sys
from collections import defaultdict

# runnable from anywhere: the repo root is the package root
import os

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from triton_dist_tpu.trace.collect import MalformedTrace  # noqa: E402
from triton_dist_tpu.trace.export import load_trace_json  # noqa: E402

CLASSES = ("compute", "sem_wait", "dma_wait")


def report(path: str) -> None:
    d = load_trace_json(path)
    events = d["traceEvents"]
    pname = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pname[e["pid"]] = e["args"]["name"]

    by_stream = defaultdict(lambda: defaultdict(float))
    by_region = defaultdict(lambda: [0.0, 0, 0])  # time, spans, instants
    for e in events:
        stream = pname.get(e.get("pid"), str(e.get("pid")))
        region = str(e.get("name", "?")).split(" ")[0]
        if e.get("ph") == "X":
            cat = e.get("cat", "trace")
            dur = float(e.get("dur", 0.0))
            if cat in CLASSES:
                by_stream[stream][cat] += dur
            by_stream[stream]["total"] += dur
            r = by_region[(stream, region)]
            r[0] += dur
            r[1] += 1
        elif e.get("ph") == "i":
            by_region[(stream, region)][2] += 1

    print(f"== {path} ({d['otherData'].get('label', '?')}, "
          f"clock={d['otherData'].get('clock', '?')}) ==")
    drops = d["otherData"].get("drops") or {}
    if any(drops.values()):
        print(f"  WARNING: dropped records: {drops}")
    print(f"{'stream':<20} {'compute':>9} {'sem_wait':>9} "
          f"{'dma_wait':>9}")
    for stream in sorted(by_stream):
        tot = max(by_stream[stream]["total"], 1e-9)
        print(f"{stream:<20} " + " ".join(
            f"{by_stream[stream][c] / tot:>8.1%}" for c in CLASSES))
    print()
    print(f"{'stream/region':<28} {'time_us':>10} {'spans':>7} "
          f"{'instants':>9}")
    for (stream, region), (t, ns, ni) in sorted(by_region.items()):
        print(f"{stream + '/' + region:<28} {t:>10.1f} {ns:>7} {ni:>9}")

    rep = d["otherData"].get("compare_predicted")
    if rep:
        print()
        print("measured vs predicted scoreboard stall "
              "(mega/scheduler.predicted_stalls):")
        print(f"{'rank':>4} {'queue':>5} {'tasks':>6} "
              f"{'measured_frac':>14} {'predicted_frac':>15} {'ok':>3}")
        for row in rep:
            m = row["measured_stall_frac"]
            p = row["predicted_stall_frac"]
            ok = (p is not None and abs(m - p) <= 0.1
                  and row["n_tasks_traced"] == row["n_tasks_scheduled"]
                  and row["order_ok"])
            print(f"{str(row.get('rank')):>4} {row['queue']:>5} "
                  f"{row['n_tasks_traced']:>6} {m:>14.3f} "
                  f"{p if p is None else round(p, 3)!s:>15} "
                  f"{'ok' if ok else 'NO':>3}")
            if not ok:
                raise MalformedTrace(
                    f"{path}: rank {row.get('rank')} queue "
                    f"{row['queue']} disagrees with the schedule")
    print()


def _metrics_table(snap: dict, indent: str = "") -> None:
    """Counters / gauges / histogram quantiles of one snapshot dict."""
    for key in sorted(snap.get("counters", {})):
        print(f"{indent}{key:<44} {snap['counters'][key]:>12}")
    for key in sorted(snap.get("gauges", {})):
        print(f"{indent}{key:<44} {snap['gauges'][key]:>12.4g}")
    hists = snap.get("histograms", {})
    if hists:
        print(f"{indent}{'histogram':<32} {'count':>8} {'p50':>10} "
              f"{'p99':>10} {'max':>10}")
    for key in sorted(hists):
        from triton_dist_tpu.obs.registry import Histogram

        h = Histogram.from_state(hists[key])
        print(f"{indent}{key:<32} {h.total:>8} {h.quantile(0.5):>10.1f} "
              f"{h.quantile(0.99):>10.1f} "
              f"{0.0 if h.total == 0 else h.max:>10.1f}")


def report_metrics(path: str) -> None:
    """Render one always-on-tier artifact: a registry snapshot or a
    flight-recorder dump (dispatch on the magic tag). ValueError on
    malformed input -> exit 1 in main."""
    import json

    from triton_dist_tpu.obs.recorder import FLIGHT_MAGIC, check_dump
    from triton_dist_tpu.obs.registry import SNAPSHOT_MAGIC, Registry

    with open(path, encoding="utf-8") as f:
        try:
            doc = json.load(f)
        except json.JSONDecodeError as e:
            raise ValueError(f"{path}: not JSON: {e}") from e
    magic = doc.get("magic") if isinstance(doc, dict) else None
    if magic == SNAPSHOT_MAGIC:
        Registry.check_snapshot(doc)
        print(f"== {path} (metrics snapshot) ==")
        _metrics_table(doc)
    elif magic == FLIGHT_MAGIC:
        check_dump(doc)
        snaps = doc["snapshots"]
        print(f"== {path} (flight recorder: {len(snaps)} snapshots, "
              f"reason: {doc.get('reason', '?')}) ==")
        for s in snaps:
            sched = s.get("scheduler", {})
            head = (f"step {s['step']:>5}  active={len(sched.get('active', {}))} "
                    f"queue={sched.get('queue_depth', '?')} "
                    f"retries={sched.get('step_retries', '?')}")
            if s.get("error"):
                head += f"  ERROR: {s['error'][:80]}"
            print(head)
            delta = s.get("metrics_delta") or {}
            for key in sorted(delta.get("counters", {})):
                print(f"    +{key:<42} {delta['counters'][key]:>8}")
            for r in s["guard_rows"]:
                print(f"    guard row: rank {r['rank']} "
                      f"{r.get('site_label', r['site'])} slot={r['slot']} "
                      f"expected>={r['expected']} observed={r['observed']}")
    else:
        raise ValueError(
            f"{path}: magic {magic!r} is neither a metrics snapshot "
            f"({SNAPSHOT_MAGIC!r}) nor a flight dump ({FLIGHT_MAGIC!r})")
    print()


def report_requests(path: str) -> None:
    """Render one per-request ledger document (ISSUE 13; written by
    trace.write_ledger / Scheduler.ledger). ValueError on malformed
    input -> exit 1 in main."""
    from triton_dist_tpu.trace.ledger import (
        check_close,
        format_requests_table,
        load_ledger,
    )

    doc = load_ledger(path)
    print(f"== {path} (request ledger: {len(doc['requests'])} "
          f"request(s), chunk={doc.get('chunk', '?')}) ==")
    print(format_requests_table(doc))
    problems = check_close(doc)
    for p in problems:
        print(f"  CLOSE VIOLATION: {p}")
    if problems:
        raise ValueError(f"{path}: {len(problems)} request(s) fail the "
                         "ledger close contract")
    print()


def report_trend(path: str) -> None:
    """Render one perf-trend sentinel report (scripts/perf_trend.py
    --out report.json). ValueError on malformed input -> exit 1."""
    import json

    from triton_dist_tpu.obs.trend import check_report, render_markdown

    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise ValueError(f"{path}: {e}") from e
    check_report(doc)
    print(f"== {path} (perf-trend sentinel report) ==")
    print(render_markdown(doc))
    print()


_MODES = {
    "--metrics": report_metrics,
    "--requests": report_requests,
    "--trend": report_trend,
}


def main(argv) -> int:
    if not argv:
        print(__doc__, file=sys.stderr)
        return 2
    picked = [m for m in _MODES if m in argv]
    if len(picked) > 1:
        print(f"trace_report: pick one mode, got {picked}",
              file=sys.stderr)
        return 2
    render = _MODES[picked[0]] if picked else report
    paths = [a for a in argv if a not in _MODES]
    if not paths:
        print(__doc__, file=sys.stderr)
        return 2
    try:
        for path in paths:
            render(path)
    except MalformedTrace as e:
        print(f"trace_report: malformed trace: {e}", file=sys.stderr)
        return 1
    except ValueError as e:
        print(f"trace_report: malformed artifact: {e}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
