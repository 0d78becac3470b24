#!/usr/bin/env python3
"""scripts/run_waiting.py — one run of one benchmark cell with per-layer
entries that WAIT as data appended to the checkout's BENCHMARK.json.

  python3 scripts/run_waiting.py --entries perfbench/fixtures/per_layer.steplog.json \\
      --workload q8b-1chip.chat-closed --seed <n> --seconds <s> --trace 1

The files under `perfbench/fixtures/per_layer.*.json` hold entries whose
readers are in the tree but which `BENCHMARK.json` cannot take until a
`benchmark` issue lifts the pin on `per_layer[-1]` (ROADMAP C11). This
is how a builder reads them on the chip meanwhile: everything after
`--entries` goes to `perfbench/run.py` unchanged, which sees the
benchmark with the entries appended and nothing else different.
"""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def appended(bench: dict, waiting: list) -> dict:
    """`bench` with the `waiting` entries at the end of `per_layer`."""
    return dict(bench, per_layer=bench["per_layer"] + list(waiting))


def entries_of(fixture) -> list:
    """A fixture is the list itself or a mapping with it under
    `per_layer`."""
    return fixture["per_layer"] if isinstance(fixture, dict) else fixture


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--entries", action="append", required=True,
                    help="a file of waiting entries; may be given twice")
    args, rest = ap.parse_known_args(argv)

    sys.path.insert(0, ROOT)
    from perfbench import harness, run

    waiting = [m for path in args.entries
               for m in entries_of(harness.load_json(path))]
    bench_path = os.path.join(ROOT, "BENCHMARK.json")
    load = harness.load_json

    def load_with_the_entries(path):
        obj = load(path)
        if os.path.abspath(path) == bench_path:
            obj = appended(obj, waiting)
        return obj

    harness.load_json = load_with_the_entries
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
