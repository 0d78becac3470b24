#!/usr/bin/env python3
"""perfbench/prove.py — the sets of runs a cell's bounds are set from.

  python3 perfbench/prove.py --workload <name> --seeds 1,2,3,4,5,6 \\
      [--sets 2] [--trace-seeds 7,8,9] [--seconds N] --out DIR

Each run is the benchmark's own command in a process of its own (this
script never touches JAX, so the chip is the child's). Two sets with
the same seeds, then the traced runs; every result line is kept in
DIR/<workload>.jsonl, and for each metric the spread of each set is
printed: the distance between the first and third quartile
(`statistics.quantiles(values, n=4)`) as a share of the median.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spread(values):
    if len(values) < 2:
        return None
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--seconds", type=int, default=None)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = args.seconds or bench["run_seconds"]
    os.makedirs(args.out, exist_ok=True)
    log = os.path.join(args.out, args.workload + ".jsonl")
    seeds = [int(s) for s in args.seeds.split(",") if s]
    plan = [(k, s, 0) for k in range(args.sets) for s in seeds]
    plan += [("trace", int(s), 1) for s in args.trace_seeds.split(",") if s]
    rows = []
    for label, seed, trace in plan:
        t = time.time()
        p = subprocess.run(
            bench["command"] + ["--workload", args.workload, "--seed",
                                str(seed), "--seconds", str(seconds),
                                "--trace", str(trace)],
            cwd=ROOT, capture_output=True, text=True)
        took = time.time() - t
        out = p.stdout.strip().splitlines()
        try:
            line = json.loads(out[-1])
        except (IndexError, ValueError):
            line = None
        row = {"set": label, "seed": seed, "trace": trace, "rc": p.returncode,
               "took_s": round(took, 1), "result": line}
        rows.append(row)
        with open(log, "a") as f:
            f.write(json.dumps(row) + "\n")
        with open(os.path.join(
                args.out, f"{args.workload}.{label}.{seed}.log"), "w") as f:
            f.write(p.stdout + "\n--- stderr ---\n" + p.stderr[-4000:])
        vals = {k: round(v["value"], 4)
                for k, v in (line or {}).get("metrics", {}).items()}
        print(f"RUN set={label} seed={seed} trace={trace} rc={p.returncode} "
              f"took={took:.0f}s correct={(line or {}).get('correct')} "
              f"checks={(line or {}).get('checks')} {vals}", flush=True)
        if line is None:
            print(p.stderr[-1500:], flush=True)
    for k in range(args.sets):
        mine = [r["result"] for r in rows if r["set"] == k and r["result"]]
        names = sorted({n for r in mine for n in r["metrics"]})
        for n in names:
            vals = [r["metrics"][n]["value"] for r in mine
                    if n in r["metrics"]]
            sp = spread(vals)
            print(f"SPREAD set={k} {n}: median "
                  f"{statistics.median(vals):.4f} spread "
                  f"{'n/a' if sp is None else format(sp, '.4%')} "
                  f"min {min(vals):.4f} max {max(vals):.4f} n={len(vals)}",
                  flush=True)
    bad = [r for r in rows if not (r["result"] or {}).get("correct")]
    print(f"DONE {len(rows)} runs, {len(bad)} not correct or without a "
          "result", flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
