"""One run of one cell: build, warm, measure a window, check, reduce.

Driven by data: the cell is an entry of `BENCHMARK.json`'s `workloads`;
its configuration, traffic mix, per-layer readers and roofline work are
files found by name under `perfbench/`. `run.py` looks for the chip and
calls `run_cell`; the tests call `run_cell` on the CPU at a tiny size.

The entry the window drives is the one a user calls:
`models.Engine(cfg, mesh, max_len, seed, fast_init=True)` with its
default modes, `serve.Scheduler(engine, slots)` with every other
argument at its default, `start()`, `submit(..., stream=True)`, tokens
read off `Request.stream`. Greedy. One process.
"""

from __future__ import annotations

import gc
import glob
import importlib.util
import json
import os
import shutil
import sys
import threading
import time
from typing import Callable, List, Optional

import numpy as np

from perfbench import traffic, work
from perfbench.sources import (device_trace, host_clock, program_counter,
                               program_span)
from perfbench.sources.host_clock import Stamps

TRACE_DIR = ".perfbench_trace"
WARM_NEW_TOKENS = 3
FIRST_TOKEN_WAIT_S = 60.0


class CacheCounter:
    """Persistent-compilation-cache hits and misses of this process."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}")


def _load_module(modname: str, path: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod  # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def load_reader(root: str, metric: str):
    """The reader module of one per-layer metric, found by its name."""
    path = os.path.join(root, "perfbench", "metrics", metric + ".py")
    return _load_module(
        "perfbench_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)


def load_reference(root: str, name: str):
    path = os.path.join(root, "perfbench", "reference", name + ".py")
    return _load_module("perfbench_reference_" + name, path)


def load_family(root: str, name: str):
    """What one model family needs between its configuration file and
    the program: `model_config(cfg)`, the program's config object, and
    `size_vars(cfg)`, the sizes the work formulae may name. A file of
    its own, found by the configuration's `family`."""
    path = os.path.join(root, "perfbench", "families", name + ".py")
    return _load_module("perfbench_family_" + name, path)


# -- the load generator -------------------------------------------------------


class Load:
    """Sends the planned requests through `Scheduler.submit` and reads
    each stream in a thread of its own, stamping with its own clock."""

    def __init__(self, scheduler, planned: List[traffic.Planned], mix: dict):
        self.sch = scheduler
        self.planned = planned
        self.mix = mix
        self.stamps: List[Stamps] = []
        self.requests: list = []
        self._lock = threading.Lock()
        self._next = 0
        self._closed = threading.Event()
        self._threads: List[threading.Thread] = []

    def _send(self, p: traffic.Planned, due: float):
        s = Stamps(index=p.index, due=due, sent=time.perf_counter(),
                   prompt_len=len(p.prompt), max_new=p.max_new_tokens)
        req = self.sch.submit(p.prompt, max_new_tokens=p.max_new_tokens,
                              stream=True)
        with self._lock:
            self.stamps.append(s)
            self.requests.append(req)
        return s, req

    @staticmethod
    def _read(s: Stamps, req) -> None:
        for _tok, _piece in req.stream:
            s.tokens.append(time.perf_counter())
        s.ended = time.perf_counter()

    def _take(self) -> Optional[traffic.Planned]:
        with self._lock:
            if self._closed.is_set() or self._next >= len(self.planned):
                return None
            p = self.planned[self._next]
            self._next += 1
            return p

    def _client(self) -> None:
        """Closed loop: the next request goes when the last one ended."""
        while True:
            p = self._take()
            if p is None:
                return
            s, req = self._send(p, due=time.perf_counter())
            self._read(s, req)

    def _arrivals(self, t_open: float) -> None:
        """Open loop: each request goes at its due time, whatever the
        earlier ones are doing."""
        while True:
            p = self._take()
            if p is None:
                return
            due = t_open + p.due_s
            while True:
                left = due - time.perf_counter()
                if left <= 0 or self._closed.is_set():
                    break
                time.sleep(min(left, 0.05))
            if self._closed.is_set():
                return
            s, req = self._send(p, due=due)
            t = threading.Thread(target=self._read, args=(s, req),
                                 daemon=True)
            t.start()
            self._threads.append(t)

    def open(self) -> float:
        """Start sending; returns the time the window opened."""
        t_open = time.perf_counter()
        if self.mix["loop"] == "closed":
            targets = [(self._client, ())] * int(self.mix["clients"])
        else:
            targets = [(self._arrivals, (t_open,))]
        for fn, a in targets:
            t = threading.Thread(target=fn, args=a, daemon=True)
            t.start()
            self._threads.append(t)
        return t_open

    def close(self) -> None:
        """Send nothing more."""
        self._closed.set()

    def drain(self) -> None:
        """After the close: wait (a minute at most) until every request
        sent has its first token or has ended, then cancel what is
        still running and wait for the readers."""
        deadline = time.perf_counter() + FIRST_TOKEN_WAIT_S
        while time.perf_counter() < deadline:
            with self._lock:
                waiting = [s for s in self.stamps
                           if not s.tokens and s.ended is None]
            if not waiting:
                break
            time.sleep(0.02)
        with self._lock:
            pairs = list(zip(self.stamps, self.requests))
        for s, req in pairs:
            if not req.done:
                s.cancelled = True
                self.sch.cancel(req)
        for t in list(self._threads):
            t.join(timeout=60)
        for s, req in pairs:
            if not s.cancelled and len(s.tokens) < s.max_new:
                s.failed = True


# -- tracing ------------------------------------------------------------------


def annotate(obj, method: str, label: str) -> None:
    """Wrap obj.method in a profiler TraceAnnotation (traced run only:
    the span at a layer boundary, recorded from the benchmark's files)."""
    import jax

    inner = getattr(obj, method)

    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(device_trace.HOST_PREFIX + label):
            return inner(*a, **kw)

    setattr(obj, method, wrapped)


class Tracer:
    """Traces `seconds` of the steady window, starting `after` seconds
    into it, from a thread of its own."""

    def __init__(self, root: str, after: float, seconds: float):
        self.dir = os.path.join(root, TRACE_DIR)
        self.after, self.seconds = after, seconds
        self.t0 = self.t1 = None
        self._thread = threading.Thread(target=self._run, daemon=True)

    def start(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
        self._thread.start()

    def _run(self) -> None:
        import jax

        time.sleep(self.after)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = time.perf_counter()
        time.sleep(self.seconds)
        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()

    def finish(self) -> Optional[device_trace.Trace]:
        self._thread.join(timeout=300)
        files = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not files:
            return None
        return device_trace.load_xplane(files[0])

    def discard(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


# -- what a per-layer reader sees ---------------------------------------------


class RunView:
    def __init__(self, **kw):
        self.__dict__.update(kw)


# -- correctness --------------------------------------------------------------


def choose_sample(stamps: List[Stamps], requests: list, t1: float, k: int,
                  seed: int) -> List[int]:
    """Indices of up to k requests that finished in the window, the
    longest among them, the others drawn from the seed."""
    done = [i for i, (s, r) in enumerate(zip(stamps, requests))
            if not s.failed and not s.cancelled and s.ended is not None
            and s.ended <= t1 and len(s.tokens) == s.max_new]
    if not done:
        return []
    longest = max(done, key=lambda i: stamps[i].prompt_len
                  + stamps[i].max_new)
    rest = [i for i in done if i != longest]
    rng = np.random.default_rng(seed)
    picked = [rest[j] for j in rng.permutation(len(rest))[:max(k - 1, 0)]]
    return [longest] + picked


def score_sample(ref, sizes, weights, sample, rows: int, devices, tp: int,
                 quant: Optional[str] = None):
    """For each (prompt, served tokens) of `sample`: the gaps by which
    each served token's logit lies under the reference's best, one
    causal pass over [prompt + served] padded to the serving horizon.
    With `quant`, the tokens scored are the ones the CONTROL precision
    puts first at the same positions."""
    import jax.numpy as jnp

    width = sizes.max_len
    gap_fn = ref.make_gap_scorer(sizes, width, rows)
    top_fn = ref.make_top_scorer(sizes, width, rows, quant) if quant \
        else None
    out = []
    for prompt, served in sample:
        seq = np.zeros((width,), np.int32)
        n = len(prompt) + len(served)
        seq[:n] = np.concatenate([np.asarray(prompt, np.int32),
                                  np.asarray(served, np.int32)])
        first = len(prompt) - 1
        start = min(first, width - rows)
        off = first - start
        toks = ref.replicated(jnp.asarray(seq), devices, tp)
        if top_fn is not None:
            scored = np.asarray(top_fn(weights, toks, start))
        else:
            scored = np.zeros((rows,), np.int32)
            scored[off:off + len(served)] = served
        gaps = np.asarray(gap_fn(weights, toks, start,
                                 ref.replicated(jnp.asarray(scored),
                                                devices, tp)))
        out.append(gaps[off:off + len(served)])
    return out


# -- the run ------------------------------------------------------------------


def run_cell(root: str, bench: dict, cell: dict, seed: int, seconds: float,
             trace: bool, t_start: float,
             say: Callable[[str], None] = print,
             device_kind: Optional[str] = None,
             tamper: Optional[Callable] = None,
             control: bool = False,
             dump_trace: Optional[str] = None) -> dict:
    """Everything of a run after the look for a chip. `device_kind`
    names the peaks row for a rehearsal on another device; `tamper`
    (tests only) gets the scheduler before it starts, to break the
    timed path underneath; `control` (limits.py) also puts the control
    precision, in the program's place on the same sample, through the
    same comparison, under the result's `control` key; `dump_trace`
    keeps the reduced trace as JSON at that path (how the fixture was
    made)."""
    import jax

    from triton_dist_tpu.lang.core import pallas_kernels_in
    from triton_dist_tpu.models import Engine
    from triton_dist_tpu.runtime import enable_compile_cache, make_mesh
    from triton_dist_tpu.serve import Scheduler

    centry = find(bench["configs"], cell["config"], "configuration")
    cfg = load_json(os.path.join(root, centry["file"]))
    mix = traffic.load_mix(root, cell["traffic"])
    serve = cfg["serve"]
    chips, tp = int(cell["chips"]), int(serve["tp"])
    if chips != serve["chips"]:
        raise ValueError(f"cell {cell['name']} asks for {chips} chips, its "
                         f"configuration for {serve['chips']}")
    devices = jax.devices()[:chips]
    kind = device_kind or devices[0].device_kind
    peaks = work.peaks_for(root, kind)
    family = load_family(root, cfg["family"])
    sizes = family.size_vars(cfg)
    say(f"device: platform={devices[0].platform} kind="
        f"{devices[0].device_kind} count={len(jax.devices())} used={chips} "
        f"jax={jax.__version__}")
    say(f"cell: {cell['name']} config={cell['config']} traffic="
        f"{cell['traffic']} seed={seed} seconds={seconds} trace={int(trace)}")
    say(f"model: {centry['source']} depth {cfg['num_hidden_layers']}"
        + (f" of {cfg['published']['num_hidden_layers']}"
           if "published" in cfg else "")
        + f"; reduced: {centry['reduced'] or 'nothing'}; {cfg['stands_for']}")

    cache = CacheCounter()
    say(f"compile cache: {enable_compile_cache()}")

    planned = traffic.plan(mix, seed, cfg["vocab_size"], serve["max_len"],
                           horizon_s=seconds)
    mesh = make_mesh((tp,), ("tp",), devices=devices)
    t_build = time.perf_counter()
    engine = Engine(family.model_config(cfg), mesh, max_len=serve["max_len"],
                    seed=seed, fast_init=True)
    jax.block_until_ready(engine.params)
    t_params = time.perf_counter()
    sch = Scheduler(engine, slots=serve["slots"])
    sch.history_cap = 1 << 22  # keep every step of the window
    pool, w = sch.pool, sch.worker
    plan_obj = sch.plan
    say(f"serve: Scheduler(slots={serve['slots']}) chose chunk={sch.chunk} "
        f"page={pool.page}; engine modes prefill={engine.prefill_mode} "
        f"decode={engine.decode_mode}; plan_id="
        f"{getattr(plan_obj, 'plan_id', None)} applied tune configs="
        f"{plan_obj.applied_configs() if plan_obj is not None else None}")

    import jax.numpy as jnp

    K = serve["slots"]
    compiled = w._fn.lower(
        engine.params, jnp.zeros((K, sch.chunk), jnp.int32), pool.k, pool.v,
        jnp.asarray(pool.table), jnp.asarray(pool.lengths),
        jnp.zeros((K,), jnp.int32), jnp.zeros((K,), jnp.float32),
        jnp.zeros((K, 2), jnp.uint32)).compile()
    kernels = pallas_kernels_in(compiled.as_text())
    del compiled
    t_compiled = time.perf_counter()
    say(f"serve step: {K} x {sch.chunk} rows, Pallas kernels in the "
        f"compiled program: {kernels or 'none'}")
    wanted = serve.get("cross_chip_kernels")
    if wanted and not any(k.startswith(tuple(wanted)) for k in kernels):
        raise RuntimeError(
            f"the compiled serve step holds none of the cross-chip Pallas "
            f"kernels {wanted} (found {kernels or 'none'}): the route gave "
            "way to XLA, and that must not pass for this cell")

    if trace:
        annotate(sch, "step", "scheduler_step")
        annotate(w, "step", "worker_step")
    if tamper is not None:
        tamper(sch)
    sch.start()
    try:
        # warm the one program the window uses, through the window's own
        # calls: a prompt of two chunks, then decode steps
        rng = np.random.default_rng(seed)
        warm = [sch.submit(rng.integers(0, cfg["vocab_size"],
                                        min(sch.chunk + 1,
                                            serve["max_len"] - 8)).tolist(),
                           max_new_tokens=WARM_NEW_TOKENS, stream=True)
                for _ in range(2)]
        for r in warm:
            for _ in r.stream:
                pass
        n_warm_steps = len(sch.history)
        load = Load(sch, planned, mix)
        tracer = None
        if trace:
            span = min(4.0, max(seconds / 3.0, 0.2))
            tracer = Tracer(root, after=0.4 * seconds, seconds=span)
            tracer.start()
        t0 = load.open()
        setup_s = t0 - t_start
        say(f"set-up: {setup_s:.2f}s to the first timed request (imports "
            f"and device start-up {t_build - t_start:.2f}s, "
            f"parameters {t_params - t_build:.2f}s, pool and compile "
            f"{t_compiled - t_params:.2f}s, warm-up "
            f"{t0 - t_compiled:.2f}s, {n_warm_steps} warm steps); compile "
            f"cache {cache.hits} hits {cache.misses} misses so far")
        time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
        load.close()
        misses_in_window = cache.misses
        load.drain()
    finally:
        sch.stop()
    t1 = t0 + seconds
    stamps, requests = load.stamps, load.requests
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)

    # -- end-to-end metrics, from the benchmark's own stamps ----------------
    due = [s for s in stamps if t0 <= s.due < t1]
    failed = [s for s in due if s.failed]
    ttft = host_clock.ttfts(stamps, t0, t1)
    gaps = host_clock.gaps(stamps, t0, t1)
    out_tok = host_clock.output_tokens(stamps, t0, t1)
    prompt_tok = host_clock.prompt_tokens(stamps, t0, t1)
    late = host_clock.lateness(stamps)
    values = {
        "setup_s": setup_s,
        "ttft_p95_ms": 1e3 * (host_clock.percentile(ttft, 95) or 0.0),
        "itl_p95_ms": 1e3 * (host_clock.percentile(gaps, 95) or 0.0),
        "tokens_per_s": (prompt_tok + out_tok) / seconds,
        "output_tokens_per_s": out_tok / seconds,
    }
    finished = [s for s in stamps if s.ended is not None and s.ended <= t1
                and len(s.tokens) == s.max_new]
    say(f"requests: sent {len(stamps)} due in the window {len(due)} "
        f"finished in it {len(finished)} failed {len(failed)}; generator "
        f"lateness median {1e3 * float(np.median(late)):.2f}ms max "
        f"{1e3 * max(late):.2f}ms")
    say(f"window: ttft median "
        f"{1e3 * (host_clock.percentile(ttft, 50) or 0):.1f}ms p95 "
        f"{values['ttft_p95_ms']:.1f}ms over {len(ttft)}; gap median "
        f"{1e3 * (host_clock.percentile(gaps, 50) or 0):.2f}ms p95 "
        f"{values['itl_p95_ms']:.2f}ms over {len(gaps)}; tokens/s prompt "
        f"{prompt_tok / seconds:.1f} + output {out_tok / seconds:.1f}"
        + (f"; offered {sum(s.prompt_len + s.max_new for s in due) / seconds:.1f}"
           f" tokens/s at {mix['rate_per_s']} requests/s"
           if mix["loop"] == "open" else ""))
    say(f"compile cache: {cache.hits} hits {cache.misses} misses; "
        f"{cache.misses - misses_in_window} after the window closed")
    if mix["loop"] == "open":
        def waiting(t):
            return sum(1 for s in stamps if s.due <= t
                       and not (s.tokens and s.tokens[0] <= t))
        depth_half, depth_end = waiting(t0 + seconds / 2), waiting(t1)
        early = [s for s in due if s.due < t0 + seconds / 2]
        done_idx = {s.index for s in finished}
        early_done = sum(1 for s in early if s.index in done_idx)
        say(f"open loop: waiting for a first token at the window's half "
            f"{depth_half}, at its end {depth_end}; finished "
            f"{len(finished)} of {len(due)} due, {early_done} of the "
            f"{len(early)} due in its first half")

    # -- what the program recorded, for the per-layer readers ---------------
    prompt_lens = {r.request_id: len(r.prompt) for r in requests + warm}
    steps_all = program_span.steps_of(sch.history, prompt_lens)
    view = RunView(
        root=root, cfg=cfg, mix=mix, sizes=sizes, peaks=peaks, chips=chips,
        slots=K, chunk=sch.chunk, t0=t0, t1=t1, stamps=stamps,
        requests=requests, steps=program_span.in_window(steps_all, t0, t1),
        trace=None, trace_steps=[], say=say,
        counters=program_counter.counters_of(sch))
    served = [(list(r.prompt), list(r.out_tokens)) for r in requests]
    mismatched = sum(
        1 for s, r in zip(stamps, requests)
        if not s.cancelled and not s.failed
        and (len(r.out_tokens) != s.max_new
             or len(s.tokens) != len(r.out_tokens)))

    # -- free the program's state, then the reference -----------------------
    sample_idx = choose_sample(stamps, requests, t1,
                               int(mix.get("check_requests", 4)), seed)
    sample = [served[i] for i in sample_idx]
    del sch, pool, w, engine, load, warm, requests, plan_obj
    view.requests = [RunView(phase_ns=dict(r.phase_ns))
                     for r in view.requests]
    gc.collect()
    checks, control_checks = run_checks(
        root, cfg, sample, seed, devices, tp, mix, say,
        mismatched=mismatched, failed=len(failed), control=control)

    result = {
        "correct": all(c["ok"] for c in checks.values()),
        "attempted": len(due), "failed": len(failed),
    }
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    if trace:
        tr = tracer.finish()
        tracer.discard()
        if tr is not None:
            if dump_trace:
                with open(dump_trace, "w") as f:
                    f.write(tr.to_json())
            view.trace = tr
            view.trace_steps = program_span.in_window(
                steps_all, tracer.t0, tracer.t1)
            busy, window = device_trace.busy_and_window(tr)
            device["busy_s"], device["window_s"] = busy, window
            result["breakdown"] = {
                "device_ops": device_trace.top_ops(tr),
                "idle_gaps": device_trace.idle_gaps(tr)}
        metrics = {}
        for m in bench["per_layer"]:
            if "workloads" in m and cell["name"] not in m["workloads"]:
                continue
            val = load_reader(root, m["name"]).read(view)
            if val is not None:
                metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
    else:
        metrics = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in bench["end_to_end"]
            if "workloads" not in m or cell["name"] in m["workloads"]}
    result["metrics"] = metrics
    result["device"] = device
    if "breakdown" in result:  # keep the contract's key order
        result["breakdown"] = result.pop("breakdown")
    if control_checks is not None:
        result["control"] = {
            "correct": all(c["ok"] for c in control_checks.values()),
            "checks": _value_and_limit(control_checks)}
    result["checks"] = _value_and_limit(checks)
    return result


def _value_and_limit(checks: dict) -> dict:
    return {k: {"value": c["value"], "limit": c["limit"]}
            for k, c in checks.items()}


def gap_check(gaps: list, limit) -> dict:
    """The one comparison of `served_logit_gap_max`: the widest gap of
    the tokens scored against the configuration's limit. Nothing scored
    is not correct."""
    tokens = int(sum(len(g) for g in gaps))
    widest = float(max(g.max() for g in gaps)) if tokens else 1e9
    return {"value": widest, "limit": limit, "tokens": tokens,
            "ties": int(sum((g > 0).sum() for g in gaps)),
            "ok": limit is not None and tokens > 0 and widest <= limit}


def run_checks(root, cfg, sample, seed, devices, tp, mix, say,
               mismatched: int, failed: int, control: bool = False):
    """Each number compared, beside its limit: (the program's checks,
    the control's or None). The control is the reference in the
    precision under the configuration's, put in the program's place:
    the tokens IT puts first at the sample's positions go through the
    same comparison, and have to come out not correct."""
    import jax

    ref = load_reference(root, cfg["reference"])
    sizes = ref.Sizes.from_config(cfg)
    limit = cfg["check"]["gap_limit"]
    exact = {
        "failed_requests": {"value": failed, "limit": 0, "ok": failed == 0},
        "stream_mismatches": {"value": mismatched, "limit": 0,
                              "ok": mismatched == 0},
    }
    t = time.perf_counter()
    gaps, cgaps = [], None
    if sample:
        weights = ref.draw_weights(sizes, tp, seed, devices)
        rows = int(mix["output"].get("max", mix["output"].get("value", 1)))
        rows = min(rows, sizes.max_len)
        gaps = score_sample(ref, sizes, weights, sample, rows, devices, tp)
        if control:
            cgaps = score_sample(ref, sizes, weights, sample, rows, devices,
                                 tp, quant=cfg["check"]["control"])
        jax.block_until_ready(weights)
        del weights
    own = gap_check(gaps, limit)
    say(f"reference: {len(sample)} finished requests (the longest among "
        f"them), {own['tokens']} served tokens scored in "
        f"{time.perf_counter() - t:.1f}s; {own['ties']} not the "
        f"reference's first choice, widest gap {own['value']:.4f}")
    checks = {**exact, "served_logit_gap_max": own}
    if not control:
        return checks, None
    ctrl = gap_check(cgaps or [], limit)
    say(f"control ({cfg['check']['control']} in the program's place): "
        f"widest gap of its first choices {ctrl['value']:.4f} against the "
        f"limit {limit}, {ctrl['ties']} of {ctrl['tokens']} not the "
        f"reference's: {'correct' if ctrl['ok'] else 'not correct'}")
    return checks, {"served_logit_gap_max": ctrl}
