#!/usr/bin/env python
"""trace_report — per-region attribution + predicted-stall diff from an
exported trace JSON, with render modes for the other observability
artifacts: --metrics (registry snapshots / flight dumps), --requests
(per-request ledgers), --trend (perf-trend sentinel reports),
--device-parts (a jax.profiler profile of a served window).

Usage:
    python scripts/trace_report.py TRACE.json [TRACE2.json ...]
    python scripts/trace_report.py --metrics SNAP_OR_DUMP.json [...]
    python scripts/trace_report.py --requests LEDGER.json [...]
    python scripts/trace_report.py --trend REPORT.json [...]
    python scripts/trace_report.py --device-parts PROFILE.xplane.pb

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

`--device-parts` reads a profile (the `.xplane.pb` that
`jax.profiler.start_trace` / `stop_trace` around a served window
leave: docs/observability.md "Device parts") and prints, for each compiled program of the first device
plane, device milliseconds a run by PART of the step
(`triton_dist_tpu.layers.parts.PARTS`, the `jax.named_scope`s the
model opens): each operation's own time goes to the innermost part
named in its `op_name`, `unscoped` otherwise; a program's times are
divided by its runs on the profile's "XLA Modules" line.

Exits non-zero on a malformed input in EVERY mode (missing magic tag,
torn histograms, dump snapshots without their guard-row lists) — the
bench.check_result strictness contract: a tool that silently renders a
clobbered artifact would hide exactly the bugs it exists to catch.
"""

from __future__ import annotations

import bisect
import re
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


# -- device parts -------------------------------------------------------------

UNSCOPED = "unscoped"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_PART = re.compile(r"tdt\.([a-z_]+(?:\.[a-z_]+)*)")
_HLO = re.compile(r"^%?(?P<name>[^\s=]+)\s*=\s*(?P<rest>.*)$", re.S)
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")
_NUMBER = re.compile(r"\.\d+$")


def part_of(op_name: str, parts) -> str:
    """The innermost part named in an operation's `op_name` path
    (`jit(f)/while/body/tdt.attn.core/dot_general`), or `unscoped`."""
    named = [m for m in _PART.findall(op_name or "") if m in parts]
    return named[-1] if named else UNSCOPED


def short_op(text: str) -> str:
    """`fusion bf16[1024,12288]` of an event's name, which may be a
    whole HLO instruction: the instruction's name less its number, and
    the first shape it produces."""
    m = _HLO.match(text)
    name, rest = (m.group("name"), m.group("rest")) if m else (text, "")
    shape = _SHAPE.search(rest[:200])
    name = _NUMBER.sub("", name.split(" ")[0])[:48]
    return name + (" " + shape.group(0) if shape else "")


def own_times(events):
    """[(event, own seconds)]: an operation's duration less what the
    operations inside it cover (a `while` holds its body's), over
    (name, op_name, start, end) tuples."""
    out, stack = [], []  # stack: [event, end, own]

    def close():
        ev, _end, own = stack.pop()
        out.append((ev, max(own, 0.0)))

    for ev in sorted(events, key=lambda e: (e[2], -e[3])):
        _n, _o, a, b = ev
        while stack and stack[-1][1] <= a:
            close()
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([ev, b, b - a])
    while stack:
        close()
    return out


def reduce_device_parts(events, modules, parts):
    """The report's numbers, from tuples alone. `events`: the first
    device plane's operations (name, op_name, start_s, end_s);
    `modules`: its program runs (name, start_s, end_s). Returns
    {program: {"runs", "module_s", "busy_s", "total_s",
    "parts": {part: seconds}, "ops": {part: {short op: seconds}}}},
    every second a SUM over the program's runs; an operation belongs
    to the run its start lies in ("outside any program" otherwise)."""
    runs = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in runs]
    out = {}

    def entry(prog):
        return out.setdefault(prog, {
            "runs": 0, "module_s": 0.0, "busy_s": 0.0, "total_s": 0.0,
            "parts": {}, "ops": {}})

    for name, a, b in runs:
        e = entry(name)
        e["runs"] += 1
        e["module_s"] += b - a

    def program_at(t):
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t < runs[i][2]:
            return runs[i][0]
        return "outside any program"

    spans = {}
    for ev, own in own_times(events):
        name, op_name, a, b = ev
        prog = program_at(a)
        e = entry(prog)
        part = part_of(op_name, parts)
        e["total_s"] += own
        e["parts"][part] = e["parts"].get(part, 0.0) + own
        ops = e["ops"].setdefault(part, {})
        key = short_op(name)
        ops[key] = ops.get(key, 0.0) + own
        spans.setdefault(prog, []).append((a, b))
    for prog, ivs in spans.items():  # busy: the union of the intervals
        end = None
        for a, b in sorted(ivs):
            if end is None or a > end:
                out[prog]["busy_s"] += b - a
                end = b
            elif b > end:
                out[prog]["busy_s"] += b - end
                end = b
    return out


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a
    varint, a memoryview for a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            val, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
            if i > n:
                raise ValueError("a field runs past its message")
        elif kind in (1, 5):
            val = buf[i:i + (8 if kind == 1 else 4)]
            i += 8 if kind == 1 else 4
        else:
            raise ValueError(f"wire type {kind}")
        yield key >> 3, val


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _device_plane(plane):
    """One XPlane (tsl/profiler/protobuf/xplane.proto) as
    (name, {line name: [(event metadata id, start_s, end_s)]},
    {event metadata id: (name, op_name)}). An operation's `op_name`
    is its event METADATA's `tf_op` stat, a string or a reference to a
    stat metadata's name."""
    name, lines, stat_names, metas = "", [], {}, []
    for num, val in _fields(plane):
        if num == 2:
            name = _text(val)
        elif num == 3:
            lines.append(val)
        elif num == 4:  # map<int64, XEventMetadata>
            metas.extend(v for n, v in _fields(val) if n == 2)
        elif num == 5:  # map<int64, XStatMetadata>
            for n, v in _fields(val):
                if n == 2:
                    f = dict(_fields(v))
                    stat_names[f.get(1, 0)] = _text(f.get(2, b""))
    if not name.startswith("/device:"):
        return name, {}, {}
    ops = {}
    for meta in metas:
        mid, text, op_name = 0, "", ""
        for num, val in _fields(meta):
            if num == 1:
                mid = val
            elif num == 2:
                text = _text(val)
            elif num == 5:  # XStat
                f = dict(_fields(val))
                if stat_names.get(f.get(1)) == "tf_op":
                    op_name = (_text(f[5]) if 5 in f
                               else stat_names.get(f.get(7), ""))
        ops[mid] = (text, op_name)
    out = {}
    for line in lines:
        lname, t0, events = "", 0, []
        for num, val in _fields(line):
            if num == 2:
                lname = _text(val)
            elif num == 3:
                t0 = val
            elif num == 4:
                events.append(val)
        evs = []
        for ev in events:
            f = dict(_fields(ev))
            a = t0 * 1e-9 + f.get(2, 0) * 1e-12
            evs.append((f.get(1, 0), a, a + f.get(3, 0) * 1e-12))
        out[lname] = evs
    return name, out, ops


def load_device_ops(path: str):
    """(events, modules, plane name) of a profile's first device
    plane with an "XLA Ops" and an "XLA Modules" line (the runs a
    program's times are divided by): events (name, op_name, start_s,
    end_s), modules (name, start_s, end_s). The file is read as the
    XSpace protobuf it is, field by field: `jax.profiler.ProfileData`
    (JAX 0.9) hands out an event's own stats and not its metadata's,
    and the `op_name` is the metadata's. ValueError on a file that is
    no profile or holds no such plane."""
    try:
        with open(path, "rb") as f:
            space = memoryview(f.read())
        planes = [_device_plane(v) for n, v in _fields(space) if n == 1]
    except (OSError, IndexError, ValueError) as e:
        raise ValueError(f"{path}: not a profile: {e!r}") from e
    for name, lines, ops in sorted(planes, key=lambda p: p[0]):
        if not (lines.get(OPS_LINE) and lines.get(MODULES_LINE)):
            continue
        events = [(*ops.get(mid, (str(mid), "")), a, b)
                  for mid, a, b in lines[OPS_LINE]]
        modules = [(ops.get(mid, (str(mid), ""))[0], a, b)
                   for mid, a, b in lines[MODULES_LINE]]
        return events, modules, name
    raise ValueError(f"{path}: no device plane with an {OPS_LINE!r} and an "
                     f"{MODULES_LINE!r} line (a profile taken on the CPU "
                     "has none)")


def report_device_parts(path: str) -> None:
    """Render one profile by part. ValueError on malformed input ->
    exit 1 in main."""
    from triton_dist_tpu.layers.parts import PARTS

    events, modules, plane = load_device_ops(path)
    table = reduce_device_parts(events, modules, PARTS)
    whole = sum(e["total_s"] for e in table.values()) or 1.0
    print(f"== {path} (device parts: {plane}, {len(events)} operations, "
          f"{len(modules)} program runs) ==")
    for prog, e in sorted(table.items(), key=lambda kv: -kv[1]["total_s"]):
        runs = max(e["runs"], 1)
        print(f"{prog}: {e['runs']} runs, {100 * e['total_s'] / whole:.1f}% "
              f"of the device's time; a run: operations "
              f"{1e3 * e['total_s'] / runs:.3f} ms, busy "
              f"{1e3 * e['busy_s'] / runs:.3f} ms, program "
              f"{1e3 * e['module_s'] / runs:.3f} ms")
        if e["total_s"] < 0.01 * whole:
            continue
        print(f"  {'part':<14} {'ms a run':>9} {'share':>6}  "
              "largest operations (ms a run)")
        order = [p for p in PARTS if p in e["parts"]]
        order += [p for p in e["parts"] if p not in PARTS]
        for part in order:
            sec = e["parts"][part]
            top = sorted(e["ops"][part].items(), key=lambda kv: -kv[1])[:3]
            print(f"  {part:<14} {1e3 * sec / runs:>9.3f} "
                  f"{100 * sec / e['total_s']:>5.1f}%  " + "; ".join(
                      f"{n} {1e3 * s / runs:.3f}" for n, s in top))
        un = e["parts"].get(UNSCOPED, 0.0)
        print(f"  unscoped {100 * un / max(e['busy_s'], 1e-12):.2f}% of the "
              f"busy time; total {1e3 * e['total_s'] / runs:.3f} ms against "
              f"busy {1e3 * e['busy_s'] / runs:.3f} ms a run "
              f"({100 * (e['total_s'] / max(e['busy_s'], 1e-12) - 1):+.2f}%)")
    print()


_MODES = {
    "--metrics": report_metrics,
    "--requests": report_requests,
    "--trend": report_trend,
    "--device-parts": report_device_parts,
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
