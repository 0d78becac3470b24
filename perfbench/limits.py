#!/usr/bin/env python3
"""perfbench/limits.py — the readings a cell's `correct` limit is set from.

  python3 perfbench/limits.py --workload <name> --seeds 101,102,... \\
      --control-seeds 101,102,103 --seconds 25 [--out FILE]

One process: for each seed a short window at the cell's own load
through the timed path, then the reference over the sample; for the
control seeds also the control precision in the program's place, at
the same positions, through the harness's own comparison. Exits 1
unless every program run reads `correct` true and every control
`correct` false under the limit in the configuration's file. Not run
by the benchmark's own runs. The readings go into PERF.md; the limit
goes into the configuration's file.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reading(seed: int, r: dict) -> dict:
    """One row of readings from a `run_cell` result."""
    ctrl = r.get("control")
    return {"seed": seed, "correct": r["correct"],
            "gap": r["checks"]["served_logit_gap_max"]["value"],
            "limit": r["checks"]["served_logit_gap_max"]["limit"],
            "control_correct": ctrl and ctrl["correct"],
            "control_gap": ctrl and
            ctrl["checks"]["served_logit_gap_max"]["value"],
            "failed": r["failed"], "attempted": r["attempted"],
            "checks": r["checks"]}


def judge(rows: list) -> dict:
    """Whether the limit separates the readings AS THE HARNESS COMPARES
    THEM: every program run correct, every control not correct."""
    ctrl = [r for r in rows if r["control_correct"] is not None]
    return {
        "limit": rows[0]["limit"] if rows else None,
        "program_gaps": sorted(round(r["gap"], 4) for r in rows),
        "program_not_correct": [r["seed"] for r in rows if not r["correct"]],
        "control_gaps": sorted(round(r["control_gap"], 4) for r in ctrl),
        "control_read_correct": [r["seed"] for r in ctrl
                                 if r["control_correct"]],
        "holds": bool(rows) and all(r["correct"] for r in rows)
        and not any(r["control_correct"] for r in ctrl),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from perfbench import harness

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    if jax.devices()[0].platform != "tpu":
        print("limits are read on the chip", file=sys.stderr)
        return 2
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = harness.find(bench["workloads"], args.workload, "workload")
    control = {int(s) for s in args.control_seeds.split(",") if s}
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        r = harness.run_cell(ROOT, bench, cell, seed, args.seconds, False,
                             t, say=lambda m: print(m, flush=True),
                             control=seed in control)
        row = reading(seed, r)
        rows.append(row)
        print("READING " + json.dumps(row), flush=True)
    verdict = judge(rows)
    print("SUMMARY " + json.dumps(verdict), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0 if verdict["holds"] else 1


if __name__ == "__main__":
    sys.exit(main())
