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
benchmark with the entries appended and nothing else different. A
fixture's `lists_to_take_the_cell` (accepted entries that find something
to read in a cell they cannot list yet) is applied too: the cell joins
those entries' `workloads` for this run.
"""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def appended(bench: dict, waiting: list, lists=()) -> dict:
    """`bench` with the `waiting` entries at the end of `per_layer`,
    and each of `lists` ({"cell": name, "entries": [metric, ...]}: a
    fixture's `lists_to_take_the_cell`) applied: the cell appended to
    the `workloads` of the accepted entries it names."""
    per_layer = [dict(m) for m in bench["per_layer"]] + list(waiting)
    for take in lists:
        for m in per_layer:
            if m["name"] in take["entries"] \
                    and take["cell"] not in m["workloads"]:
                m["workloads"] = m["workloads"] + [take["cell"]]
    return dict(bench, per_layer=per_layer)


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

    fixtures = [harness.load_json(path) for path in args.entries]
    waiting = [m for f in fixtures for m in entries_of(f)]
    lists = [f["lists_to_take_the_cell"] for f in fixtures
             if isinstance(f, dict) and "lists_to_take_the_cell" in f]
    bench_path = os.path.join(ROOT, "BENCHMARK.json")
    load = harness.load_json

    def load_with_the_entries(path):
        obj = load(path)
        if os.path.abspath(path) == bench_path:
            obj = appended(obj, waiting, lists)
        return obj

    harness.load_json = load_with_the_entries
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
