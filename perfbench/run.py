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
    t_harness = time.perf_counter()

    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = harness.find(bench["workloads"], args.workload, "workload")
    seconds = args.seconds if args.seconds is not None \
        else bench["run_seconds"]

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # the TPU runtime pins a host buffer for transfers, 4 GiB unless told;
    # on hosts without transparent huge pages that pinning is most of the
    # device start-up (v5e: 6-11 s, swinging by seconds; 1.5-2.5 s at
    # 256 MiB, PERF.md). A run puts no more than a few MiB at a time
    os.environ.setdefault("TPU_PREMAPPED_BUFFER_SIZE", str(256 << 20))
    import jax
    t_jax = time.perf_counter()
    # every program goes into the compile cache, however quickly it
    # compiled, so that a run after the first compiles nothing (JAX keeps
    # by default only what took a second or more)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    try:
        from triton_dist_tpu import lang
    except ImportError as e:
        print(f"the program under test is not in this checkout: {e}",
              file=sys.stderr)
        return 3
    t_program = time.perf_counter()
    devices = jax.devices()
    t_devices = time.perf_counter()
    if devices[0].platform != "tpu" or lang.use_interpret():
        print("perfbench measures on a TPU only (JAX's first device is "
              f"{devices[0].platform!r}, interpret={lang.use_interpret()}): "
              "nothing was built", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"cell {cell['name']} needs {cell['chips']} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    say(f"start-up: benchmark {t_harness - T_START:.2f}s, jax "
        f"{t_jax - t_harness:.2f}s, program {t_program - t_jax:.2f}s, "
        f"devices {t_devices - t_program:.2f}s")
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
