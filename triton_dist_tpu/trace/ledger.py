"""Per-request attribution ledger — the request-scoped tier of the
observability story (ISSUE 13, docs/observability.md "Request-scoped
attribution").

Every earlier observability surface is kernel- or step-scoped: trace
spans name a region, obs stat rows name a kernel, the scheduler's
metrics name the fleet. This module folds them along the REQUEST axis —
the unit users experience latency in — using two sources the serve
plane already records:

  phase accumulators   serve.Request.phase_ns: wall time per lifecycle
                       phase (queued / prefill / decode), accumulated
                       by the scheduler at every phase close. Because
                       phases are contiguous from submit to finish,
                       their sum CLOSES against the request's
                       submit->finish wall time — `check_close` pins
                       |close_frac - 1| <= tol (default 0.05; the slack
                       is the handful of bookkeeping instructions
                       between a phase close and the next open).
  slot history         scheduler.history: one entry a device step,
                       carrying wall time and the slot->request map.
                       Device wall time splits across a step's
                       occupants equally.

Products: a JSON-able ledger document (magic "tdt-req-ledger",
rendered by `scripts/trace_report.py --requests`), a per-request
Perfetto export (`write_request_trace`: ONE process track per
request), and `attribute_branch_time` — the per-request split of
`trace.attribution.task_time_by_branch`'s per-branch buckets.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List

LEDGER_MAGIC = "tdt-req-ledger"

# phases whose accumulated spans must close against wall time.
# migrate/admit are the disaggregated prefill/decode legs (ISSUE 18,
# xslice/): 0 on a single-slice scheduler, and on the in-process
# DisaggPair the passenger Request accumulates all five across both
# schedulers, so the prefill-side ledger closes the full TTFT
# decomposition — prefill-slice time + migration + decode admission.
_PHASES = ("queued", "prefill", "migrate", "admit", "decode")


def _us(ns: int) -> float:
    return round(ns / 1e3, 2)


def build_ledger(sch, tol: float = 0.05) -> dict:
    """Assemble the ledger document from a serve.Scheduler. Covers
    every submitted request; `close_frac` (phase sum / wall) is
    computed for DONE requests — the tier-1 close pin applies to them
    (`check_close`)."""
    device_us = _device_time_by_request(sch)
    rows: List[dict] = []
    for req in sch.requests:
        phases = dict(req.phase_ns)
        if req.done and getattr(req, "_phase", None) is not None:
            # a request finished without a phase close (e.g. cancelled
            # while queued): credit the open phase up to finish time so
            # the ledger still closes
            name, t0 = req._phase
            phases[name] = phases.get(name, 0) + max(
                0, req.t_finish - t0)
        wall_ns = (req.t_finish - req.t_submit) if req.done else 0
        covered = sum(phases.get(p, 0) for p in _PHASES)
        close = (covered / wall_ns) if wall_ns > 0 else None
        rows.append({
            "request_id": req.request_id,
            "state": req.state.value,
            "reason": req.finish_reason,
            "wall_us": _us(wall_ns) if req.done else None,
            "ttft_us": (round(req.ttft_us(), 2)
                        if req.ttft_us() is not None else None),
            "tpot_us": (round(req.tpot_us(), 2)
                        if req.tpot_us() is not None else None),
            "queued_us": _us(phases.get("queued", 0)),
            "prefill_us": _us(phases.get("prefill", 0)),
            "migrate_us": _us(phases.get("migrate", 0)),
            "admit_us": _us(phases.get("admit", 0)),
            "decode_us": _us(phases.get("decode", 0)),
            # spec_verify is a SUB-BUCKET of decode (ISSUE 14): the
            # wall share of decode steps that ran a verify row. It is
            # NOT added to the close sum — the decode phase already
            # contains it, so the close-against-wall contract (and its
            # tol) is untouched. 0 on unspecced runs.
            "spec_verify_us": _us(req.spec_verify_ns),
            "spec_steps": req.n_spec_steps,
            # a prefix-cache hit skips [0, prefix_hit_tokens) of
            # prefill entirely: hit requests report prefill_us ~= 0
            # by construction (the phase only spans the residual
            # chunks), which is the TTFT collapse the cache buys
            "prefix_hit_tokens": req.prefix_len,
            "close_frac": (round(close, 4)
                           if close is not None else None),
            "tokens_out": len(req.out_tokens),
            "prefill_chunks": req.n_prefill_chunks,
            "decode_steps": max(
                0, req.n_device_steps - req.n_prefill_chunks),
            "device_steps": req.n_device_steps,
            "evictions": req.n_evictions,
            "device_share_us": round(
                device_us.get(req.request_id, 0.0), 2),
        })
    return {
        "magic": LEDGER_MAGIC,
        "chunk": sch.chunk,
        "tol": tol,
        "history_dropped": sch.history_dropped,
        "requests": rows,
    }


def _device_time_by_request(sch) -> Dict[int, float]:
    """Device wall time (us) per request from the slot history: each
    step's wall split equally across its occupants."""
    out: Dict[int, float] = {}
    for e in sch.history:
        dur_us = (e["t1"] - e["t0"]) / 1e3
        slots = e.get("slots") or {}
        if not slots:
            continue
        share = dur_us / len(slots)
        for rid, _phase, _n in slots.values():
            out[rid] = out.get(rid, 0.0) + share
    return out


def check_close(ledger: dict, states=("finished",)) -> List[str]:
    """The ledger close contract: for every request in one of `states`,
    |close_frac - 1| <= tol — the decomposed phase times sum to the
    submit->finish wall time. Returns problem strings (empty = closed);
    the tier-1 pin asserts empty on a served run."""
    tol = float(ledger.get("tol", 0.05))
    problems = []
    for row in ledger["requests"]:
        if row["state"] not in states:
            continue
        close = row.get("close_frac")
        if close is None:
            problems.append(
                f"req{row['request_id']}: no close_frac (phases never "
                "closed against wall time)")
        elif abs(close - 1.0) > tol:
            problems.append(
                f"req{row['request_id']}: phase sum closes at "
                f"{close:.4f} of wall (tol {tol})")
    return problems


def check_ledger(doc: dict) -> dict:
    """Validate a ledger document (the trace_report strictness
    contract); returns it. Raises ValueError on malformed input."""
    if not isinstance(doc, dict) or doc.get("magic") != LEDGER_MAGIC:
        raise ValueError(
            f"not a request ledger (magic="
            f"{doc.get('magic') if isinstance(doc, dict) else None!r} "
            f"!= {LEDGER_MAGIC!r})")
    reqs = doc.get("requests")
    if not isinstance(reqs, list):
        raise ValueError("ledger 'requests' missing or not a list")
    for i, row in enumerate(reqs):
        if not isinstance(row, dict):
            raise ValueError(f"ledger requests[{i}] is not an object")
        for key in ("request_id", "state", "queued_us", "prefill_us",
                    "decode_us", "device_steps"):
            if key not in row:
                raise ValueError(f"ledger requests[{i}] missing {key!r}")
    return doc


def write_ledger(ledger: dict, path: str) -> str:
    check_ledger(ledger)
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(ledger, f, indent=1)
    return path


def load_ledger(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise ValueError(f"{path}: {e}") from e
    return check_ledger(doc)


def format_requests_table(ledger: dict) -> str:
    """The per-request table `scripts/trace_report.py --requests`
    prints: one row per request, decomposition columns in ms."""
    cols = (f"{'req':>5} {'state':<10} {'wall_ms':>9} {'queued':>8} "
            f"{'prefill':>8} {'migrate':>8} "
            f"{'admit':>8} {'decode':>9} {'close':>6} "
            f"{'ttft_ms':>8} {'tok':>4} {'steps':>6} "
            f"{'dev_ms':>8}")
    lines = [cols]

    def ms(v):
        return "-" if v is None else f"{v / 1e3:.1f}"

    for row in ledger["requests"]:
        close = row.get("close_frac")
        lines.append(
            f"{row['request_id']:>5} {row['state']:<10} "
            f"{ms(row.get('wall_us')):>9} {ms(row['queued_us']):>8} "
            f"{ms(row['prefill_us']):>8} "
            f"{ms(row.get('migrate_us', 0)):>8} "
            f"{ms(row.get('admit_us', 0)):>8} "
            f"{ms(row['decode_us']):>9} "
            f"{'-' if close is None else format(close, '.3f'):>6} "
            f"{ms(row.get('ttft_us')):>8} {row.get('tokens_out', 0):>4} "
            f"{row['device_steps']:>6} "
            f"{ms(row.get('device_share_us', 0)):>8}")
    if ledger.get("history_dropped"):
        lines.append(f"(history truncated: {ledger['history_dropped']} "
                     "oldest entries dropped — device shares are lower "
                     "bounds)")
    return "\n".join(lines)


def attribute_branch_time(ledger: dict, tl, branch_keys=None,
                          stream: str = "mega") -> Dict[int, dict]:
    """Split `attribution.task_time_by_branch`'s per-branch buckets
    across requests, proportional to each request's device-step share
    — the per-request view of the world=1 branch ledger (a latency
    regression names its branch AND its victim). Returns
    {request_id: {branch_key: time}}; the proportional rule is the
    documented resolution (branch spans carry no request tag — the
    megakernel runs whole steps)."""
    from triton_dist_tpu.trace.attribution import task_time_by_branch

    buckets = task_time_by_branch(tl, branch_keys, stream=stream)
    steps = {row["request_id"]: row["device_steps"]
             for row in ledger["requests"]}
    total = sum(steps.values())
    if total == 0:
        return {}
    return {
        rid: {key: d["time"] * n / total for key, d in buckets.items()}
        for rid, n in steps.items() if n > 0
    }


def write_request_trace(sch, path: str) -> str:
    """Perfetto export with ONE PROCESS TRACK PER REQUEST: every
    req<N>/<phase> span of the scheduler's span log (obs/spans.py)
    lands in its request's own track (instants — evictions,
    quarantines — as 'i' events), with the scheduler-level spans (each
    round's phases, step retries) in a 'serve' track
    beside them. Loads at ui.perfetto.dev next to the in-kernel traces
    (same format tag)."""
    spans = sch.spans.triples()
    # a live export must not lose in-flight requests: each OPEN phase
    # (req._phase — a span reaches the log only when its phase closes)
    # is exported as a zero-length instant at its open stamp
    for req in sch.requests:
        ph = getattr(req, "_phase", None)
        if ph is not None:
            name, t0 = ph
            spans.append((f"req{req.request_id}/{name}", t0, t0))
    t_all = [t for _n, t0, t1 in spans for t in (t0, t1)]
    t_base = min(t_all) if t_all else 0
    req_ids = sorted({row.request_id for row in sch.requests})
    pid_of = {rid: i + 2 for i, rid in enumerate(req_ids)}
    events = [{"ph": "M", "pid": 1, "name": "process_name",
               "args": {"name": "serve"}}]
    for rid in req_ids:
        events.append({"ph": "M", "pid": pid_of[rid],
                       "name": "process_name",
                       "args": {"name": f"req{rid}"}})
    for name, t0, t1 in spans:
        pid, label = 1, name
        if name.startswith("req"):
            head, _, rest = name.partition("/")
            try:
                rid = int(head[3:])
            except ValueError:
                rid = None
            if rid in pid_of:
                pid, label = pid_of[rid], rest or name
        ts = (t0 - t_base) / 1e3
        if t1 > t0:
            events.append({"ph": "X", "pid": pid, "tid": 1,
                           "name": label, "cat": "request",
                           "ts": ts, "dur": (t1 - t0) / 1e3})
        else:
            events.append({"ph": "i", "s": "t", "pid": pid, "tid": 1,
                           "name": label, "ts": ts})
    doc = {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "label": "serve-requests",
            "clock": "host",
            "format": "triton_dist_tpu.trace v1",
        },
    }
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f)
    return path
