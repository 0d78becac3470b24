"""The package's import graph, pinned so that it can only shrink.

One case a unit of `triton_dist_tpu` (each subpackage, and the two
top-level modules others import: `perf_model`, `autotuner`): every
`import` / `from` node of every file of the unit, function-level ones
too, names only units the table allows. The table is the graph as it
stands (PR 32); it has cycles (`models <-> serve`, `mega -> models`,
`perf_model -> mega`, `lang -> faults/obs/trace/verify`, `kernels ->
layers`, `wire -> faults`: ROADMAP D13). An edge may be REMOVED from
the table when the code stops needing it. Adding one is a design
decision to argue in the PR that needs it, not a line to append here.
"""

import ast
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "triton_dist_tpu")

# unit -> the units it may import. Only ever shrinks (module docstring).
ALLOWED = {
    "autotuner": {"kernels", "lang", "perf_model", "runtime", "wire"},
    "faults": {"kernels", "obs", "runtime", "serve", "spec", "trace",
               "verify", "wire", "xslice"},
    "kernels": {"faults", "lang", "layers", "obs", "perf_model",
                "runtime", "trace", "verify", "wire"},
    "lang": {"faults", "obs", "trace", "verify"},
    "layers": {"kernels", "plan", "runtime", "trace"},
    "mega": {"lang", "layers", "models", "perf_model", "runtime",
             "trace", "verify"},
    # no `mega`, no `obs` (they went with the device-resident loop)
    "models": {"layers", "plan", "runtime", "serve", "trace"},
    "obs": {"faults", "trace"},
    "perf_model": {"lang", "mega", "wire"},
    "plan": {"autotuner", "kernels", "lang", "layers", "perf_model",
             "verify"},
    "runtime": {"perf_model", "trace"},
    "serve": {"faults", "kernels", "mega", "models", "obs",
              "perf_model", "spec", "trace", "xslice"},
    "spec": {"serve"},
    "tools": set(),
    "trace": {"faults", "obs", "runtime"},
    "verify": {"lang", "runtime", "wire"},
    "wire": {"faults"},
    "xslice": {"faults", "kernels", "lang", "runtime", "serve", "verify",
               "wire"},
}

# edges allowed at ONE place only: (unit, target) -> (file, function).
# `serve -> mega` is the megakernel's bridge (ROADMAP D4)
ONLY_IN = {
    ("serve", "mega"): ("serve/kv_pool.py", "as_mega_cache"),
}


def _units():
    out = []
    for name in sorted(os.listdir(PKG)):
        path = os.path.join(PKG, name)
        if os.path.isdir(path) and os.path.exists(
                os.path.join(path, "__init__.py")):
            out.append(name)
        elif name.endswith(".py") and name != "__init__.py":
            out.append(name[:-3])
    return out


def _files_of(unit):
    single = os.path.join(PKG, unit + ".py")
    if os.path.exists(single):
        return [single]
    return [os.path.join(d, f)
            for d, _dirs, fs in os.walk(os.path.join(PKG, unit))
            for f in fs if f.endswith(".py")]


def _imports(path):
    """(target unit, line, enclosing function or None) for every import
    of another `triton_dist_tpu` unit in `path`."""
    parts = os.path.relpath(path, REPO)[:-3].split(os.sep)
    package = parts[:-1]  # of a module and of an __init__ alike
    found = []

    def visit(node, fn):
        for child in ast.iter_child_nodes(node):
            inner = (child.name if isinstance(
                child, (ast.FunctionDef, ast.AsyncFunctionDef)) else fn)
            names = []
            if isinstance(child, ast.Import):
                names = [a.name for a in child.names]
            elif isinstance(child, ast.ImportFrom):
                if child.level:
                    base = package[:len(package) - (child.level - 1)]
                    mod = ".".join(base + ([child.module]
                                           if child.module else []))
                else:
                    mod = child.module
                names = ([f"{mod}.{a.name}" for a in child.names]
                         if mod == "triton_dist_tpu" else [mod])
            for name in names:
                if name.startswith("triton_dist_tpu."):
                    found.append((name.split(".")[1], child.lineno, fn))
            visit(child, inner)

    with open(path, encoding="utf-8") as f:
        visit(ast.parse(f.read()), None)
    return found


def test_the_table_names_every_unit():
    assert _units() == sorted(ALLOWED)


@pytest.mark.parametrize("unit", sorted(ALLOWED))
def test_a_package_imports_only_what_the_table_allows(unit):
    bad = []
    for path in _files_of(unit):
        rel = os.path.relpath(path, PKG)
        for target, line, fn in _imports(path):
            if target == unit:
                continue
            if target not in ALLOWED[unit]:
                bad.append(f"{rel}:{line} imports {target}")
            elif ONLY_IN.get((unit, target), (rel, fn)) != (rel, fn):
                bad.append(f"{rel}:{line} imports {target} outside "
                           f"{ONLY_IN[(unit, target)]}")
    assert not bad, f"{unit} grew an import edge:\n  " + "\n  ".join(bad)


@pytest.mark.parametrize("unit", ["serve", "models"])
def test_importing_it_loads_no_megakernel(unit):
    """What runs at import, as the table cannot see it: a fresh
    interpreter that imports the unit holds no `triton_dist_tpu.mega*`
    module."""
    code = (f"import sys, triton_dist_tpu.{unit}\n"
            "print(sorted(m for m in sys.modules "
            "if m.startswith('triton_dist_tpu.mega')))")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "[]"
