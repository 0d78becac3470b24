#!/usr/bin/env python
"""hlo_compare — does a change that should touch metadata only (a
`jax.named_scope`, a renamed function) leave the serve programs'
instructions alone? Two steps, no chip:

    python scripts/hlo_compare.py dump ROOT OUTDIR [CONFIG ...]
    python scripts/hlo_compare.py diff OUTDIR_A OUTDIR_B

`dump` imports the checkout at ROOT (this tree, or a `git archive` of
another commit), compiles the serve step of each benchmark
configuration (every width; all of `perfbench/configs/*.json` with a
`serve` block by default) for a DESCRIBED v5e from shapes alone, and
writes per program the optimised HLO (`.hlo.txt`), the same with
metadata, stack-frame tables, kernel bodies and backend configurations
masked (`.masked.txt`) and the kernel listing with the part names found
(`.json`). One ROOT a process: run it once for each side.

`diff` compares two such directories program by program at three
depths: the masked lines as they are; with the NUMBERS of instruction
names taken off (`%reshape.131` -> `%reshape`: one more scope shifts the
compiler's numbering, and `breakdown.device_ops` names with it); with
every instruction renamed by its order of appearance (same opcodes,
operands, shapes, layouts and schedule under whatever names). It also
compares the kernel listings. Exit 0 if every program agrees at the
last depth and lists the same kernels, 1 otherwise, 2 on bad usage.
The masks are functions (`masked`, `unnumbered`, `renamed`) that
tests/test_parts.py holds the tiny configurations to on the CPU.
"""

from __future__ import annotations

import json
import os
import re
import sys

_MASKS = [
    (re.compile(r",? metadata=\{[^}]*\}"), ""),
    (re.compile(r'"body":"[^"]*"'), '"body":"..."'),
    (re.compile(r'backend_config="[^"]*"'), 'backend_config="..."'),
    (re.compile(r"backend_config=\{.*\}$"), "backend_config={...}"),
]
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
_NAME = re.compile(r"%[A-Za-z_][\w\-]*(?:\.[\w\-]+)*")
_SIGNATURE_NAME = re.compile(r"\b([A-Za-z_][\w\-]*)((?:\.\d+)+)(?=: )")
_HEADER_PARAM = re.compile(r"([(] ?|, )([A-Za-z_][\w.\-]*)(: )")


def masked(text: str) -> str:
    """An optimised HLO module's text less what a scope may touch:
    `metadata={...}`, the stack-frame tables, Mosaic kernel bodies and
    backend configurations (which embed serialized locations)."""
    out, skip = [], False
    for line in text.splitlines():
        if line in _TABLES:
            skip = True
            continue
        if skip:
            if line.strip() == "" or re.match(r"^\d+ ", line):
                continue
            skip = False
        for rx, rep in _MASKS:
            line = rx.sub(rep, line)
        out.append(line)
    return "\n".join(out)


def unnumbered(text: str) -> str:
    """`%reshape.131` -> `%reshape`, `param_0.8:` -> `param_0:`: the
    numbers the compiler appends to a name, nothing else (a constant's
    `0.5` stays)."""
    def strip(m):
        head, *rest = m.group(0).split(".")
        return ".".join([head] + [p for p in rest if not p.isdigit()])

    return _SIGNATURE_NAME.sub(r"\1", _NAME.sub(strip, text))


def renamed(text: str) -> str:
    """Every instruction and computation name replaced by `%<order of
    first appearance>` (a computation header's `param_0.8:` is the
    body's `%param_0.8`): equal texts then hold the same instructions
    wired the same way, whatever the compiler called them."""
    order = {}

    def rename(m):
        return order.setdefault(m.group(0), f"%{len(order)}")

    out = []
    for line in text.splitlines():
        if line.endswith(" {"):  # a computation's header
            line = _HEADER_PARAM.sub(r"\1%\2\3", line)
        out.append(_NAME.sub(rename, line))
    return "\n".join(out)


def differing_lines(a: str, b: str):
    """(count, first few pairs) of the lines that differ, by position
    where the two have the same number of lines, by `difflib`
    otherwise."""
    la, lb = a.splitlines(), b.splitlines()
    if len(la) == len(lb):
        pairs = [(x, y) for x, y in zip(la, lb) if x != y]
        return len(pairs), pairs[:3]
    import difflib

    delta = [d for d in difflib.unified_diff(la, lb, lineterm="", n=0)
             if d[:1] in "+-" and d[:3] not in ("+++", "---")]
    return len(delta), [(d, "") for d in delta[:6]]


def compare_dirs(dir_a: str, dir_b: str) -> int:
    """Print one line a program; the count of programs that differ at
    the last depth or in their kernels (a program missing on one side
    counts)."""
    def programs(d):
        return {f[:-len(".masked.txt")] for f in os.listdir(d)
                if f.endswith(".masked.txt")}

    both = sorted(programs(dir_a) | programs(dir_b))
    if not both:
        raise ValueError(f"no *.masked.txt under {dir_a} or {dir_b}")
    bad = 0
    for prog in both:
        paths = [os.path.join(d, prog + ".masked.txt")
                 for d in (dir_a, dir_b)]
        if not all(os.path.exists(p) for p in paths):
            print(f"{prog}: on one side only")
            bad += 1
            continue
        a, b = (open(p).read() for p in paths)
        kern = [json.load(open(os.path.join(d, prog + ".json")))["kernels"]
                for d in (dir_a, dir_b)]
        raw, _ = differing_lines(a, b)
        nums, _ = differing_lines(unnumbered(a), unnumbered(b))
        last, shown = differing_lines(renamed(a), renamed(b))
        same = last == 0 and kern[0] == kern[1]
        bad += not same
        print(f"{prog}: {a.count(chr(10)) + 1} masked lines; differ {raw} "
              f"as they are, {nums} with names' numbers off, {last} with "
              f"names by order; kernels "
              + (f"{kern[0]} both" if kern[0] == kern[1]
                 else f"{kern[0]} | {kern[1]}")
              + ("" if same else "  <-- DIFFERS"))
        for x, y in shown:
            print(f"    a: {x[:200]}\n    b: {y[:200]}")
    return bad


# -- dump: compile for a described v5e from shapes ---------------------------


def dump(root: str, out: str, names) -> None:
    root, out = os.path.abspath(root), os.path.abspath(out)
    sys.path.insert(0, root)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import time

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    jax.config.update("jax_enable_compilation_cache", False)
    from perfbench import harness
    from triton_dist_tpu import perf_model
    from triton_dist_tpu.lang import core
    from triton_dist_tpu.models import Engine
    from triton_dist_tpu.models.dense import param_shapes, param_specs
    from triton_dist_tpu.perf_model import choose_chunk_for
    from triton_dist_tpu.runtime import make_mesh

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    core.backend_platform = lambda: "tpu"
    core.backend_device = lambda: topo.devices[0]
    perf_model.detect_chip.cache_clear()
    sds, bf16 = jax.ShapeDtypeStruct, jnp.bfloat16
    os.makedirs(out, exist_ok=True)
    configs = os.path.join(root, "perfbench", "configs")
    if not names:
        names = sorted(
            f[:-5] for f in os.listdir(configs) if f.endswith(".json")
            and "serve" in harness.load_json(os.path.join(configs, f)))

    for name in names:
        cfgj = harness.load_json(os.path.join(configs, name + ".json"))
        cfg = harness.load_family(root, cfgj["family"]).model_config(cfgj)
        serve = cfgj["serve"]
        tp, slots, max_len = serve["tp"], serve["slots"], serve["max_len"]
        mesh = make_mesh((tp,), ("tp",), devices=topo.devices)
        rep = NamedSharding(mesh, P())
        page = 64
        max_pages = max_len // page
        chunk = choose_chunk_for(cfg, tp, slots, max_len, "flash")
        if cfg.is_hybrid:
            from triton_dist_tpu.models import hybrid

            params = {n: sds(s, bf16, sharding=rep)
                      for n, s, _ in hybrid.leaves(cfg)}
            pools = tuple(
                sds((cfg.num_kv_layers, 1 + slots * max_pages, page, h, w),
                    bf16, sharding=rep) for h, w in cfg.page_arrays)
            cache = pools + tuple(
                sds(s, dt, sharding=rep) for s, dt in zip(
                    hybrid.state_shapes(cfg, slots), (jnp.float32, bf16))
            ) + tuple(sds(s, bf16, sharding=rep)
                      for s in hybrid.window_shapes(cfg, slots))
        else:
            params = jax.tree.map(
                lambda shape, spec: sds(shape, bf16,
                                        sharding=NamedSharding(mesh, spec)),
                param_shapes(cfg, tp), param_specs("tp"),
                is_leaf=lambda x: type(x) is tuple)
            pool = sds((cfg.num_layers, 1 + slots * max_pages, page,
                        cfg.num_kv_heads, cfg.head_dim), bf16,
                       sharding=NamedSharding(mesh,
                                              P(None, None, None, "tp")))
            cache = (pool, pool)
        eng = Engine(cfg, mesh, params=params, max_len=max_len)
        for width in eng.serve_widths(chunk):
            t = time.time()
            text = eng.make_serve_step(slots, width, page, max_pages).lower(
                eng.params, sds((slots, width), jnp.int32, sharding=rep),
                cache, sds((slots, max_pages), jnp.int32, sharding=rep),
                sds((slots,), jnp.int32, sharding=rep),
                sds((slots,), jnp.int32, sharding=rep),
                sds((slots,), jnp.float32, sharding=rep),
                sds((slots, 2), jnp.uint32, sharding=rep),
            ).compile().as_text()
            tag = os.path.join(out, f"{name}.w{width}")
            with open(tag + ".hlo.txt", "w") as f:
                f.write(text)
            with open(tag + ".masked.txt", "w") as f:
                f.write(masked(text))
            found = {"kernels": core.pallas_kernels_in(text),
                     "lines": text.count("\n"),
                     "parts": sorted(set(re.findall(r"tdt\.[a-z_.]+", text)))}
            with open(tag + ".json", "w") as f:
                json.dump(found, f)
            print(f"{name}.w{width}: {time.time() - t:.0f} s, "
                  f"{found['lines']} lines, kernels {found['kernels']}, "
                  f"{len(found['parts'])} part names", flush=True)


def main(argv) -> int:
    try:
        if len(argv) >= 3 and argv[0] == "dump":
            dump(argv[1], argv[2], argv[3:])
            return 0
        if len(argv) == 3 and argv[0] == "diff":
            return 1 if compare_dirs(argv[1], argv[2]) else 0
    except (OSError, ValueError, KeyError) as e:
        print(f"hlo_compare: {e!r}", file=sys.stderr)
        return 1
    print(__doc__, file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
