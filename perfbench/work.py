"""Operations and bytes a piece of WORK needs, from shapes.

A roofline names work, not a kernel: `perfbench/work/<work>/work.json`
holds the formulae (arithmetic expressions over the configuration's
sizes and a step's rows), and each implementation of that work is a
file of its own beside it (`impl-<name>.json`: the trace-event name
patterns). A PR that swaps a kernel adds an `impl-` file; the
operations and bytes it is held to stay the ones here.

Variables an expression may use — sizes (from the configuration's
family file, `perfbench/families/<family>.py` `size_vars`): L layers, H hidden, I
intermediate, V vocabulary, hq / hkv query / kv heads, d head size,
tp chips sharing a layer, b bytes per element. `per_row` expressions
are summed over a step's active rows with n (valid token rows of the
slot) and ctx (tokens already cached), `per_emit` once for each row
that samples a token; `per_step` expressions see rows
(the step's valid rows) and active (slots with any). Every quantity is
PER CHIP.
"""

from __future__ import annotations

import ast
import glob
import json
import os
from typing import Dict, List, Tuple

_ALLOWED = (ast.Expression, ast.BinOp, ast.UnaryOp, ast.Name, ast.Load,
            ast.Constant, ast.Add, ast.Sub, ast.Mult, ast.Div,
            ast.FloorDiv, ast.USub, ast.Call)
_FUNCS = {"max": max, "min": min}
RESOURCES = ("flops", "hbm_bytes", "ici_bytes")


def evaluate(expr: str, variables: dict) -> float:
    """A formula's value; only arithmetic, names, max and min."""
    tree = ast.parse(expr, mode="eval")
    for node in ast.walk(tree):
        if not isinstance(node, _ALLOWED):
            raise ValueError(f"not an arithmetic formula: {expr!r}")
        if isinstance(node, ast.Call) and not (
                isinstance(node.func, ast.Name) and node.func.id in _FUNCS):
            raise ValueError(f"only max and min may be called: {expr!r}")
    return float(eval(compile(tree, "<formula>", "eval"),
                      {"__builtins__": {}}, {**_FUNCS, **variables}))


def load(root: str, name: str) -> dict:
    base = os.path.join(root, "perfbench", "work", name)
    with open(os.path.join(base, "work.json")) as f:
        work = json.load(f)
    work["impls"] = []
    for path in sorted(glob.glob(os.path.join(base, "impl-*.json"))):
        with open(path) as f:
            work["impls"].append(json.load(f))
    return work


def patterns(work: dict) -> List[str]:
    return [p for impl in work["impls"] for p in impl["events"]]


def step_needs(work: dict, sizes: dict, rows: List[Tuple[int, int, bool]]
               ) -> Dict[str, float]:
    """{resource: amount} one step needs; rows = [(n, ctx, emits), ...]
    for the slots with valid rows. `per_emit` formulae count once for
    each row that samples a token."""
    need = {r: 0.0 for r in RESOURCES}
    for n, ctx, emits in rows:
        for r, expr in work.get("per_row", {}).items():
            need[r] += evaluate(expr, {**sizes, "n": n, "ctx": ctx})
        if emits:
            for r, expr in work.get("per_emit", {}).items():
                need[r] += evaluate(expr, sizes)
    step = {"rows": sum(r[0] for r in rows), "active": len(rows)}
    for r, expr in work.get("per_step", {}).items():
        need[r] += evaluate(expr, {**sizes, **step})
    return need


def least_seconds(need: Dict[str, float], peaks: dict) -> Tuple[float, str]:
    """The least time one chip could take for `need`, and which peak
    binds: the largest of operations over peak FLOP/s, HBM bytes over
    peak bytes/s and interconnect bytes over its peak."""
    times = {
        "flops": need["flops"] / peaks["bf16_flops_per_s"],
        "hbm_bytes": need["hbm_bytes"] / peaks["hbm_bytes_per_s"],
        "ici_bytes": need["ici_bytes"] / peaks["ici_bytes_per_s"],
    }
    bound = max(times, key=times.get)
    return times[bound], bound


def peaks_for(root: str, device_kind: str) -> dict:
    """One table of peaks, keyed by device kind; a kind that is not in
    it is an error, never a default."""
    with open(os.path.join(root, "perfbench", "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"perfbench/peaks.json (has {sorted(table)})")
    return table[device_kind]
