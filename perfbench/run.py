#!/usr/bin/env python3
"""perfbench/run.py — one run of one benchmark cell.

  python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. Exits non-zero, and prints no result,
when JAX's first device is not a TPU, when there are fewer chips than
the cell asks for, or when the program under test is not there. The
last line of standard output is the result; the numbers compared for
`correct` are also the last lines of standard error.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def say(msg: str) -> None:
    print(msg, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", default=None,
                    help="with --trace 1: keep the reduced trace as JSON "
                         "at this path (how the tests' fixture was made)")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    from perfbench import harness

    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = harness.find(bench["workloads"], args.workload, "workload")
    seconds = args.seconds if args.seconds is not None \
        else bench["run_seconds"]

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    try:
        from triton_dist_tpu import lang
    except ImportError as e:
        print(f"the program under test is not in this checkout: {e}",
              file=sys.stderr)
        return 3
    devices = jax.devices()
    if devices[0].platform != "tpu" or lang.use_interpret():
        print("perfbench measures on a TPU only (JAX's first device is "
              f"{devices[0].platform!r}, interpret={lang.use_interpret()}): "
              "nothing was built", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"cell {cell['name']} needs {cell['chips']} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    result = harness.run_cell(ROOT, bench, cell, args.seed, seconds,
                              bool(args.trace), T_START, say=say,
                              dump_trace=args.dump_trace)
    sys.stdout.flush()
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
