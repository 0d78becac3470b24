#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system starts on the chip.

Drives the main path once through the entry points a user calls, on a
TPU v5e, at Qwen3-8B's published widths (hidden 4096, intermediate
12288, 32 q / 8 kv heads, head_dim 128, vocab 151 936, bf16, untied
head; weights random from --seed):

  one chip (default)  `models.Engine` + `serve.Scheduler` answer four
      requests (prompts of 128/256/512/512 tokens, 32 new tokens each,
      greedy, max_len 2048), then one `mega.MegaQwen3` decode step.
      The only cut is DEPTH, to what one 16 GB chip holds. Before
      them, the hybrid family's latent attention alone at
      Kimi-Linear's widths: 8 slots x 128 columns over 8,192 cached
      rows of 640 on the planner's route, against the expanded form
      in float32, with cached rows broken on purpose (`latent_phase`);
      then the hybrid family's window and global grouped-query blocks
      at K-EXAONE's widths through a per-slot tail and a paged view,
      against plain float32 attention, each broken four ways
      (`window_phase`); then the held experts' grouped matmul, both
      products of an expert block at the three hybrid configurations'
      widths and the cells' group sizes, against the loop over
      experts, with one group's offset shifted by a row, and their
      combine at granite-4.0-h-small's widths against the jnp forms,
      with two held pairs swapped (`experts_phase`); then the hybrid
      family's state-space mixer at
      granite-4.0-h-micro's widths over three steps with the state
      carried, against the recurrence position by position in float32,
      the carry broken three ways (`ssd_phase`), and its attention
      block with heads of 64 kept 128 wide over a paged view against
      the plain reference's attention, the scale and the cached pages
      broken (`nope_phase`).
  --chips 4           the cross-chip path and nothing else: full depth
      (36 layers), tp=4 over the four chips, the same four requests.

What comes out is checked against a plain reference on the same
weights — the `xla` formulation of `models.dense.forward` with XLA
attention (no Pallas kernel in it), teacher-forced on the tokens that
were served — and every phase says which Pallas kernels are in the
program it ran, by name, so a route that quietly gave way to XLA
cannot pass for a kernel run. Any phase that raises exits non-zero.

One process; it starts no other. Exits non-zero before building
anything when JAX's first device is not a TPU. Last line of stdout:
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}
"""

import argparse
import functools
import json
import os
import statistics
import sys
import time

import numpy as np

PROMPT_LENS = (128, 256, 512, 512)
NEW_TOKENS = 32
MAX_LEN = 2048
SLOTS = 4
# The bf16 tolerance on a logit, as a multiple of
#   2**-8 * sqrt(depth) * std(reference logits).
# Every layer re-rounds the residual stream to bf16 (relative step
# 2**-8) and two correct formulations round differently, so their
# logits differ by about that product RMS: on the v5e at depth 21 the
# SAME Pallas-free XLA program at two paddings differs by 1.03x it RMS
# and 5.5x it at the largest of 4.9M entries; prefill-then-decode
# through the cache against one full pass, both Pallas-free, by 1.9x
# RMS and 10.3x-11.6x at the largest (PERF.md, PR 24). 16 leaves a
# factor 1.4 over the largest difference seen between two formulations
# that hold no kernel of ours; an fp8 computation (2**-4) lands 16x out.
TOL_ULPS = 16
# HBM kept free on one chip beyond weights and caches: the megakernel's
# per-layer fusion transient (0.4 GB), the serve step's logits block,
# compiled programs, allocator fragmentation.
RESERVE_BYTES = 3 << 29


def say(msg: str) -> None:
    print(msg, flush=True)


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


def layer_bytes(cfg, keys=("w_qkv", "w_o", "w_gate", "w_up", "w_down")):
    """bf16 bytes of one layer's weights (all of them, or `keys`)."""
    from triton_dist_tpu.models.dense import param_shapes

    shapes = param_shapes(cfg, 1).layers
    return sum(2 * int(np.prod(getattr(shapes, k)[1:])) for k in keys)


def depth_for_one_chip(cfg, bytes_limit: int) -> int:
    """The largest layer count one chip holds through every phase. The
    binding phase is the megakernel's: MegaQwen3 streams a tile-major
    fused gate|up copy, which lives BESIDE the Engine's split gate and
    up (the two phases share every other weight)."""
    fixed = 2 * 2 * cfg.vocab_size * cfg.hidden_size  # embed + head
    kv_row = 2 * 2 * cfg.num_kv_heads * MAX_LEN * cfg.head_dim
    per_layer = (layer_bytes(cfg) + layer_bytes(cfg, ("w_gate", "w_up"))
                 + kv_row)
    depth = (bytes_limit - fixed - RESERVE_BYTES) // per_layer
    say(f"depth: bytes_limit={bytes_limit / 1e9:.2f}GB - embed+head "
        f"{fixed / 1e9:.2f}GB - reserve {RESERVE_BYTES / 1e9:.2f}GB over "
        f"{per_layer / 1e9:.4f}GB/layer (weights "
        f"{layer_bytes(cfg) / 1e9:.4f} + megakernel gate|up copy "
        f"{layer_bytes(cfg, ('w_gate', 'w_up')) / 1e9:.4f} + kv) "
        f"-> {depth}")
    if depth < 1:
        raise RuntimeError("not one layer fits this device")
    return int(min(depth, cfg.num_layers))


def compile_and_name(jitted, *args):
    """AOT-compile `jitted` for `args`: (kernel names in the compiled
    program, compile seconds). The entry point's own first call then
    finds the program in the compile cache."""
    from triton_dist_tpu.lang.core import pallas_kernels_in

    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    return pallas_kernels_in(compiled.as_text()), time.perf_counter() - t0


def require(kernels: dict, wanted, where: str) -> None:
    """Fail unless a kernel of each wanted name prefix is in `kernels`."""
    missing = [w for w in wanted
               if not any(name.startswith(w) for name in kernels)]
    if missing:
        raise RuntimeError(
            f"{where}: expected Pallas kernel(s) {missing} are not in "
            f"the compiled program (found {kernels or 'none'}) — the "
            "route gave way to XLA")


def check_memory_spread(mesh, model_bytes: int) -> None:
    """After init on several chips: all hold about the same, none holds
    the model."""
    used = [d.memory_stats()["bytes_in_use"] for d in mesh.devices.flat]
    say("memory after init, GB per device: "
        + " ".join(f"{u / 1e9:.2f}" for u in used)
        + f" (model {model_bytes / 1e9:.2f})")
    if max(used) > 1.1 * min(used) or max(used) > 0.6 * model_bytes:
        raise RuntimeError("parameters are not spread evenly over the "
                           f"chips: {used}")


def check_ring(mesh) -> None:
    ring = list(mesh.devices.flat)
    coords = [tuple(d.coords) for d in ring]
    say("tp ring (device id @ chip coords): "
        + " -> ".join(f"{d.id}@{c}" for d, c in zip(ring, coords)))
    for a, b in zip(coords, coords[1:] + coords[:1]):
        if sum(abs(x - y) for x, y in zip(a, b)) != 1:
            raise RuntimeError(f"tp neighbours {a} and {b} are not ICI "
                               "neighbours")


# -- phases -------------------------------------------------------------------


def serve_phase(eng, prompts, want_kernels):
    """Four requests through serve.Scheduler (background thread,
    streamed), the calls examples/11_model_server.py makes."""
    import jax.numpy as jnp

    from triton_dist_tpu.lang.core import pallas_call_count
    from triton_dist_tpu.serve import Scheduler

    calls0 = pallas_call_count()
    sch = Scheduler(eng, slots=SLOTS)
    pool, w = sch.pool, sch.worker
    kernels, t_compile = compile_and_name(
        w._fn, eng.params, jnp.zeros((SLOTS, sch.chunk), jnp.int32),
        pool.state, jnp.asarray(pool.table), jnp.asarray(pool.lengths),
        jnp.zeros((SLOTS,), jnp.int32), jnp.zeros((SLOTS,), jnp.float32),
        jnp.zeros((SLOTS, 2), jnp.uint32))
    say(f"serve: Scheduler(slots={SLOTS}, chunk={sch.chunk}, "
        f"page={pool.page}) step compiled in {t_compile:.1f}s; kernels in "
        f"it: {kernels or 'none'}; pallas_call_count +"
        f"{pallas_call_count() - calls0}")
    require(kernels, want_kernels, "serve step")

    sch.start()
    t0 = time.perf_counter()
    reqs = [sch.submit(p, max_new_tokens=NEW_TOKENS, stream=True)
            for p in prompts]
    streamed = [[tok for tok, _piece in r.stream] for r in reqs]
    wall = time.perf_counter() - t0
    sch.stop()  # re-raises what killed the serving thread, if anything
    for i, (r, s) in enumerate(zip(reqs, streamed)):
        if not (r.done and r.finish_reason == "length"
                and len(r.out_tokens) == NEW_TOKENS and s == r.out_tokens):
            raise RuntimeError(
                f"request {i}: state={r.state} reason={r.finish_reason} "
                f"returned {len(r.out_tokens)} streamed {len(s)} tokens")
    steps = [(h["t1"] - h["t0"]) / 1e9 for h in sch.history
             if h["kind"] == "step"]
    say(f"serve: 4/4 requests finished, {4 * NEW_TOKENS} tokens streamed "
        f"== returned, {len(steps)} steps in {wall:.2f}s (set-up "
        f"information: first step {steps[0]:.3f}s, median step "
        f"{statistics.median(steps):.4f}s)")
    served = [list(r.out_tokens) for r in reqs]

    # finding, not a gate: docs/serving.md promises each request's
    # tokens are bitwise those of the same Engine decoding it alone AT
    # THE SAME STEP WIDTHS; alone a request decodes through the narrow
    # step, batched it may ride a wide one beside a prefilling slot,
    # and then the streams are equal to rounding only
    del sch, pool, w, reqs
    alone_sch = Scheduler(eng, slots=SLOTS)
    alone = []
    for p in prompts:
        r = alone_sch.submit(p, max_new_tokens=NEW_TOKENS)
        alone_sch.run()
        alone.append(list(r.out_tokens))
    same = [a == s for a, s in zip(alone, served)]
    say(f"serve finding: batched streams bitwise equal to each request "
        f"decoded alone: {same} ({'holds' if all(same) else 'DOES NOT HOLD'}"
        " on this device)")
    return served


def reference_logits(eng, prompts, served):
    """The plain reference: dense.forward's `xla` formulation with XLA
    attention over [prompt + served tokens] (teacher forcing), one
    request at a time, padded to one width (causal: the padding behind
    a row cannot reach it) so all four share one program; row j of
    request i scores served token j."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from triton_dist_tpu.models.dense import (
        ALL_COLS,
        cache_specs,
        forward,
        param_specs,
    )

    cfg, axis = eng.cfg, eng.axis
    n = int(eng.mesh.shape[axis])
    # one width for all four; the xla formulation shards the rows by tp
    width = -(-(max(PROMPT_LENS) + NEW_TOKENS) // n) * n

    def per_rank(params, tokens, cache, first):
        logits, _ = forward(cfg, params, tokens, cache, mode="xla",
                            axis=axis, head_cols=ALL_COLS,
                            attn_impl="xla")
        return jax.lax.dynamic_slice_in_dim(logits[0], first, NEW_TOKENS)

    fn = jax.jit(jax.shard_map(
        per_rank, mesh=eng.mesh,
        in_specs=(param_specs(axis), P(), cache_specs(axis), P()),
        out_specs=P(), check_vma=False))

    def args_of(p, s):
        seq = np.zeros((1, width), np.int32)
        seq[0, :len(p) + len(s)] = p + s
        return (eng.params, jnp.asarray(seq), eng.new_cache(1),
                jnp.asarray(len(p) - 1, jnp.int32))

    kernels, t_compile = compile_and_name(fn, *args_of(prompts[0],
                                                       served[0]))
    if kernels:
        raise RuntimeError(f"the reference holds Pallas kernels {kernels}")
    t0 = time.perf_counter()
    ref = np.stack([np.asarray(fn(*args_of(p, s)))
                    for p, s in zip(prompts, served)])  # (4, NEW, V) f32
    say(f"reference: xla formulation, XLA attention, no Pallas kernel; "
        f"compiled in {t_compile:.1f}s, four requests ran in "
        f"{time.perf_counter() - t0:.2f}s")
    if not np.isfinite(ref).all():
        raise RuntimeError("reference logits are not finite")
    return ref


def check_served_tokens(ref, served, tol: float) -> None:
    ties = 0
    worst = 0.0
    for i, toks in enumerate(served):
        for j, tok in enumerate(toks):
            gap = float(ref[i, j].max() - ref[i, j, tok])
            if gap > 0.0:  # served token is not the reference's argmax
                ties += 1
                worst = max(worst, gap)
                if gap >= tol:
                    raise RuntimeError(
                        f"request {i} token {j}: served {tok} scores "
                        f"{gap:.4f} under the reference's best "
                        f"{int(ref[i, j].argmax())} — outside the "
                        f"tolerance {tol:.4f}")
    say(f"served tokens vs reference argmax: {len(served) * NEW_TOKENS - ties}"
        f"/{len(served) * NEW_TOKENS} equal, {ties} near-ties (largest "
        f"gap {worst:.4f} < tolerance {tol:.4f})")


def engine_phase(eng, prompts, served, ref, tol, want_prefill,
                 want_decode):
    """Engine.prefill + Engine.decode_step (the Engine's default modes)
    teacher-forced on the served tokens, logits position by position
    against the reference. Returns what the megakernel phase compares
    with: request 0's first decode step."""
    import jax.numpy as jnp

    from triton_dist_tpu.lang.core import pallas_call_count

    calls0 = pallas_call_count()
    worst = 0.0
    decode_kernels = None
    first_decode = None
    for i, (p, toks) in enumerate(zip(prompts, served)):
        ids = jnp.asarray([p], jnp.int32)
        cache = eng.new_cache(1)
        kernels, t_compile = compile_and_name(eng._prefill, eng.params,
                                              ids, cache)
        t0 = time.perf_counter()
        logits, cache = eng.prefill(ids, cache)
        got = [np.asarray(logits[0])]
        t_first = time.perf_counter() - t0
        say(f"engine prefill S={len(p)} ({eng.prefill_mode}): compiled in "
            f"{t_compile:.1f}s, ran in {t_first:.3f}s; kernels: "
            f"{kernels or 'none'}")
        if len(p) == max(PROMPT_LENS):
            require(kernels, want_prefill, f"prefill S={len(p)}")
        if decode_kernels is None:
            tok0 = jnp.asarray(toks[:1], jnp.int32)[:, None]
            decode_kernels, t_compile = compile_and_name(
                eng._decode, eng.params, tok0, cache)
            say(f"engine decode B=1 ({eng.decode_mode}): compiled in "
                f"{t_compile:.1f}s; kernels: "
                + (str(decode_kernels) if decode_kernels or want_decode
                   else "none — by design at world=1: nothing to overlap, "
                   "the step is XLA matmuls and XLA attention"))
            require(decode_kernels, want_decode, "decode step")
        steps = []
        for tok in toks[:-1]:
            t0 = time.perf_counter()
            logits, cache = eng.decode_step([tok], cache)
            got.append(np.asarray(logits[0]))
            steps.append(time.perf_counter() - t0)
        if first_decode is None:
            first_decode = dict(prompt=p, token=toks[0], logits=got[1])
        diff = float(np.abs(np.stack(got) - ref[i]).max())
        worst = max(worst, diff)
        say(f"engine request {i}: {len(got)} positions, largest logit "
            f"difference from the reference {diff:.4f} (set-up "
            f"information: median decode step "
            f"{statistics.median(steps):.4f}s)")
    say(f"engine vs reference: largest difference {worst:.4f}, tolerance "
        f"{tol:.4f}; pallas_call_count +{pallas_call_count() - calls0}")
    if not worst < tol:
        raise RuntimeError(f"engine logits differ from the reference by "
                           f"{worst:.4f} >= {tol:.4f}")
    return first_decode


def mega_phase(eng, first_decode, tol: float) -> None:
    """One MegaQwen3 decode step, same widths and depth, against the
    Engine's decode logits on the same prefilled cache."""
    import jax.numpy as jnp

    from triton_dist_tpu.lang.core import pallas_call_count
    from triton_dist_tpu.mega.qwen3 import MegaKVCache, MegaQwen3

    calls0 = pallas_call_count()
    t0 = time.perf_counter()
    mega = MegaQwen3(eng.cfg, eng.mesh, batch=1, s_max=MAX_LEN,
                     params=eng.params)
    say(f"megakernel: built in {time.perf_counter() - t0:.1f}s; scheduler: "
        + ("native C++ (csrc/scheduler.cc, built from the committed "
           "source)" if mega.sched.native
           else "pure Python (TDT_NO_NATIVE=1)"))
    _, cache = eng.prefill(jnp.asarray([first_decode["prompt"]], jnp.int32),
                           eng.new_cache(1))
    mcache = MegaKVCache.from_dense(cache, MAX_LEN)
    del cache
    tok = jnp.asarray([first_decode["token"]], jnp.int32)
    kernels, t_compile = compile_and_name(
        mega._decode, mega.params, mega._w_gate_up, tok, mcache)
    t0 = time.perf_counter()
    logits, mcache = mega.decode_step(tok, mcache)
    logits = np.asarray(logits[0])
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    np.asarray(mega.decode_step(tok, mcache)[0])
    t_steady = time.perf_counter() - t0
    diff = float(np.abs(logits - first_decode["logits"]).max())
    say(f"megakernel decode step: compiled in {t_compile:.1f}s, first "
        f"{t_first:.3f}s, second {t_steady:.4f}s (set-up information); "
        f"kernels: {kernels or 'none'}; pallas_call_count +"
        f"{pallas_call_count() - calls0}; largest logit difference from "
        f"the Engine's decode step {diff:.4f}, tolerance {tol:.4f}")
    require(kernels, ["mega_qwen3"], "megakernel step")
    if not (np.isfinite(logits).all() and diff < tol):
        raise RuntimeError(f"megakernel logits differ from the Engine's "
                           f"by {diff:.4f} >= {tol:.4f}")


# Latent attention on the chip: 8 slots x 128 columns over views of
# 8,192 rows of 640, the benchmark's geometry for the hybrid family's
# latent member. Lengths and valid columns a slot: a full view, odd
# lengths that end inside a page of the kernel, decode rows of one
# valid column, a fresh slot.
LATENT_LENS = (8192, 6001, 4097, 2049, 1025, 513, 200, 128)
LATENT_VALID = (128, 128, 1, 128, 1, 128, 72, 128)
# Largest difference from the expanded float32 form, as a share of its
# largest output: what bf16 leaves (the absorbed query, the folded
# output and the two projections round once each) against what a
# cached row broken on purpose gives (latent_phase prints both).
LATENT_TOL = 0.04


def latent_phase(seed: int, max_len: int = 8192) -> None:
    """`layers.latent_attn.latent_attn_fwd` on the route the planner
    names for the chip, at Kimi-Linear's widths, against the EXPANDED
    form in float32 at `highest` written out here (every head's keys
    and values out of the cached rows, one slot at a time): what the
    benchmark's `correct` cannot see under random weights, where a
    near-uniform softmax over thousands of rows averages the values
    away. The hidden states are wide enough that a row attends few
    keys, so one page of cached rows with its value columns zeroed, and
    every cached row so, both have to read over the tolerance."""
    import jax
    import jax.numpy as jnp

    from triton_dist_tpu.kernels import flash_prefill
    from triton_dist_tpu.layers.latent_attn import (
        LatentAttnParams,
        LatentAttnSpec,
        latent_attn_fwd,
    )
    from triton_dist_tpu.models import ModelConfig
    from triton_dist_tpu.plan.planner import route_hybrid_attention

    cfg = ModelConfig.kimi_linear_48b(max_positions=max_len)
    (_, width), = cfg.page_arrays
    spec = LatentAttnSpec(cfg.num_q_heads, cfg.kv_lora_rank,
                          cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
    hq, r, dn, dr, dv = spec
    hidden, slots, cols = cfg.hidden_size, len(LATENT_LENS), 128
    lens = np.minimum(LATENT_LENS, max_len)
    valid = np.minimum(LATENT_VALID, lens)
    impl = route_hybrid_attention(cfg, slots, cols, max_len)
    bf16, f32 = jnp.bfloat16, jnp.float32
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    shapes = ((hidden, hq * (dn + dr)), (hidden, spec.row),
              (r, hq * (dn + dv)), (hq * dv, hidden))
    w_q, w_a, w_b, w_o = (
        (0.02 * jax.random.normal(k, s, f32)).astype(bf16)
        for k, s in zip(keys, shapes))
    p = LatentAttnParams(w_q, w_a, jnp.ones((r,), bf16), w_b, w_o)
    # a normed hidden state three times over: scores a few units wide
    x = (3.0 * jax.random.normal(keys[4], (slots, cols, hidden),
                                 f32)).astype(bf16)
    cached = jax.random.normal(keys[5], (slots, max_len, 1, spec.row), f32)
    cached = cached.at[..., r:].multiply(3.0)  # k_r is not normed
    start = jnp.asarray(lens - valid, jnp.int32)
    here = jnp.arange(max_len)[None, :] < start[:, None]
    # past a slot's cached rows lies what another request left there
    view = jnp.pad(jnp.where(here[..., None, None], cached, 50.0),
                   ((0, 0),) * 3 + ((0, width - spec.row),)).astype(bf16)
    pos = start[:, None] + jnp.arange(cols)[None, :]
    kv_len, n_valid = (jnp.asarray(a, jnp.int32) for a in (lens, valid))

    # every array an argument: a closed-over one is folded at compile
    fwd = jax.jit(lambda x, p, view: latent_attn_fwd(
        x, p, spec, pos, view, kv_len, n_valid, impl, cfg.rms_eps)[0])
    kernels, secs = compile_and_name(fwd, x, p, view)
    launch = flash_prefill.last_launch()
    say(f"latent attention: route {impl!r}, kernels {kernels or 'none'}, "
        f"launch {launch}, compiled in {secs:.1f}s")
    if impl == "pallas":
        require(kernels, ["_fp_local_kernel"], "latent attention")

    hi = jax.lax.Precision.HIGHEST

    @jax.jit
    def expanded(x_i, rows_i, start_i, w):
        """One slot: x_i (cols, H), rows_i (max_len, row) as cached."""
        w_q, w_a, w_b, w_o = w
        xs = x_i.astype(f32)
        q = jnp.dot(xs, w_q.astype(f32), precision=hi).reshape(
            cols, hq, dn + dr)
        a = jnp.dot(xs, w_a.astype(f32), precision=hi)
        c = a[:, :r] * jax.lax.rsqrt(
            jnp.mean(a[:, :r] ** 2, -1, keepdims=True) + cfg.rms_eps)
        new = jnp.concatenate([c, a[:, r:]], axis=-1)
        rows = jax.lax.dynamic_update_slice(
            rows_i.astype(f32), new, (start_i, 0))
        kv = jnp.dot(rows[:, :r], w_b.astype(f32), precision=hi).reshape(
            max_len, hq, dn + dv)
        att = (jnp.einsum("shd,thd->hst", q[..., :dn], kv[..., :dn],
                          precision=hi)
               + jnp.einsum("shd,td->hst", q[..., dn:], rows[:, r:],
                            precision=hi)) * (dn + dr) ** -0.5
        seen = (jnp.arange(max_len)[None, :]
                <= (start_i + jnp.arange(cols))[:, None])
        prob = jax.nn.softmax(jnp.where(seen[None], att, -jnp.inf), -1)
        o = jnp.einsum("hst,thd->shd", prob, kv[..., dn:], precision=hi)
        return jnp.dot(o.reshape(cols, hq * dv), w_o.astype(f32),
                       precision=hi)

    weights = (w_q, w_a, w_b, w_o)
    wants = [np.asarray(expanded(x[i], view[i, :, 0, :spec.row], start[i],
                                 weights))[:n]
             for i, n in enumerate(valid)]

    def worst(view) -> float:
        """Over the slots' valid columns (the others are discarded)."""
        got = np.asarray(fwd(x, p, view).astype(f32))
        return max(float(np.abs(got[i, :len(want)] - want).max()
                         / np.abs(want).max())
                   for i, want in enumerate(wants))

    own = worst(view)
    one_page = worst(view.at[:, 64:128, :, :r].set(0))
    every = worst(jnp.where(here[..., None, None], view.at[..., :r].set(0),
                            view))
    say(f"latent attention against the expanded form, largest "
        f"difference over largest output: {own:.4f} (tolerance "
        f"{LATENT_TOL}); with the value columns of cached rows 64-127 "
        f"zeroed {one_page:.4f}, of every cached row {every:.4f}")
    if not own < LATENT_TOL < min(one_page, every):
        raise RuntimeError(
            f"latent attention: {own:.4f} has to lie under {LATENT_TOL} "
            f"and the broken caches' {one_page:.4f}, {every:.4f} over it")


# Window and global attention on the chip: 8 slots x 128 columns at
# K-EXAONE's widths, one window block through its per-slot tail and one
# global block through a view of 8,192 positions. Lengths and valid
# columns a slot: a full view, odd lengths, decode rows of one valid
# column, a slot younger than the window, a fresh slot.
WINDOW_LENS = (8192, 6001, 4097, 2049, 1025, 513, 180, 128)
WINDOW_VALID = (128, 128, 1, 128, 1, 128, 72, 128)
# Largest difference from plain float32 attention, as a share of its
# largest output: what bf16 leaves against what a cache broken on
# purpose gives (window_phase prints each).
WINDOW_TOL = 0.04


def window_phase(seed: int, max_len: int = 8192) -> None:
    """`layers.gqa_attn.window_attn_fwd` and `global_attn_fwd` on the
    routes the planner names for the chip, at K-EXAONE's widths,
    against plain attention in float32 at `highest` written out here
    (one slot at a time over the slot's whole timeline, the window a
    mask, rotary in the window block alone): what the benchmark's
    `correct` may not see under random weights. Broken on purpose, each
    has to read over the tolerance: the tail zeroed, the window one key
    too wide, rotary on the global block, the global block's cached
    pages zeroed."""
    import jax
    import jax.numpy as jnp

    from triton_dist_tpu.layers import gqa_attn
    from triton_dist_tpu.layers.rope import apply_rope, rope_table
    from triton_dist_tpu.models import ModelConfig
    from triton_dist_tpu.plan.planner import (
        route_hybrid_attention,
        route_window_attention,
    )

    cfg = ModelConfig.k_exaone_236b(num_layers=8, max_positions=max_len)
    spec = gqa_attn.GQAttnSpec(cfg.num_q_heads, cfg.num_kv_heads,
                               cfg.head_dim)
    hq, hkv, d = spec
    win, hidden, cols = cfg.sliding_window, cfg.hidden_size, 128
    slots = len(WINDOW_LENS)
    lens = np.minimum(WINDOW_LENS, max_len)
    valid = np.minimum(WINDOW_VALID, lens)
    routes = {"global": route_hybrid_attention(cfg, slots, cols, max_len),
              "window": route_window_attention(cfg, slots, cols)}
    bf16, f32 = jnp.bfloat16, jnp.float32
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    shapes = ((hidden, hq * d), (hidden, 2 * hkv * d), (hq * d, hidden))
    w_q, w_kv, w_o = ((0.02 * jax.random.normal(k, s, f32)).astype(bf16)
                      for k, s in zip(keys, shapes))
    p = gqa_attn.GQAttnParams(w_q, w_kv, jnp.ones((d,), bf16),
                              jnp.ones((d,), bf16), w_o)
    x = jax.random.normal(keys[3], (slots, cols, hidden), f32).astype(bf16)
    # what every earlier position left in the cache: keys as stored
    # (normed, so of unit size a value, three times over: a row attends
    # few keys), values of unit size
    cached = tuple(
        (scale * jax.random.normal(k, (slots, max_len, hkv, d), f32)
         ).astype(bf16) for k, scale in zip(keys[4:6], (3.0, 1.0)))
    start = jnp.asarray(lens - valid, jnp.int32)
    pos = start[:, None] + jnp.arange(cols)[None, :]
    kv_len, n_valid = (jnp.asarray(a, jnp.int32) for a in (lens, valid))
    here = jnp.arange(max_len)[None, :] < start[:, None]
    # past a slot's cached rows lies what another request left there
    view = tuple(jnp.where(here[..., None, None], c, 50.0).astype(bf16)
                 for c in cached)
    # the tail: row t holds position start - window + t, and whatever
    # the slot's last tenant left where that is negative
    at = start[:, None] - win + jnp.arange(win)[None, :]
    tail = tuple(jnp.where(
        (at >= 0)[..., None, None],
        jnp.take_along_axis(c, jnp.maximum(at, 0)[..., None, None], axis=1),
        50.0).astype(bf16) for c in cached)
    cos, sin = rope_table(d, max_len, cfg.rope_theta)

    def window_fwd(x, p, tail):
        return gqa_attn.window_attn_fwd(
            x, p, spec, cos, sin, pos, tail, start, n_valid, win,
            routes["window"], cfg.rms_eps)[0]

    def global_fwd(x, p, view):
        return gqa_attn.global_attn_fwd(
            x, p, spec, pos, view, kv_len, routes["global"], cfg.rms_eps)[0]

    hi = jax.lax.Precision.HIGHEST

    @functools.partial(jax.jit, static_argnums=(5,))
    def plain(x_i, k_i, v_i, start_i, w, sliding):
        """One slot: x_i (cols, H); k_i, v_i (max_len, Hkv, D) as cached."""
        w_q, w_kv, w_o = (a.astype(f32) for a in w)
        xs = x_i.astype(f32)

        def normed(a):
            return a * jax.lax.rsqrt(
                jnp.mean(a * a, -1, keepdims=True) + cfg.rms_eps)

        q = normed(jnp.dot(xs, w_q, precision=hi).reshape(cols, hq, d))
        kv = jnp.dot(xs, w_kv, precision=hi)
        k = normed(kv[:, :hkv * d].reshape(cols, hkv, d))
        v = kv[:, hkv * d:].reshape(cols, hkv, d)
        mine = start_i + jnp.arange(cols)
        if sliding:
            q, k = (apply_rope(a, cos, sin, mine) for a in (q, k))
        # the cache holds what the program stored: bfloat16 rows
        k_all = jax.lax.dynamic_update_slice(
            k_i.astype(f32), k.astype(bf16).astype(f32), (start_i, 0, 0))
        v_all = jax.lax.dynamic_update_slice(
            v_i.astype(f32), v.astype(bf16).astype(f32), (start_i, 0, 0))
        att = jnp.einsum("sjgd,tjd->jgst",
                         q.reshape(cols, hkv, hq // hkv, d) * d ** -0.5,
                         k_all, precision=hi)
        t = jnp.arange(max_len)[None, :]
        seen = t <= mine[:, None]
        if sliding:
            seen &= t > mine[:, None] - win
        prob = jax.nn.softmax(jnp.where(seen[None, None], att, -jnp.inf), -1)
        o = jnp.einsum("jgst,tjd->sjgd", prob, v_all, precision=hi)
        return jnp.dot(o.reshape(cols, hq * d), w_o, precision=hi)

    def worst(fwd, cache, wants) -> float:
        """Over the slots' valid columns (the others are discarded)."""
        got = np.asarray(fwd(x, p, cache).astype(f32))
        return max(float(np.abs(got[i, :len(want)] - want).max()
                         / np.abs(want).max())
                   for i, want in enumerate(wants))

    weights = (w_q, w_kv, w_o)
    readings = {}
    for kind, fwd, cache in (("window", window_fwd, tail),
                             ("global", global_fwd, view)):
        jitted = jax.jit(fwd)
        kernels, secs = compile_and_name(jitted, x, p, cache)
        say(f"{kind} attention: route {routes[kind]!r}, kernels "
            f"{kernels or 'none'}, compiled in {secs:.1f}s")
        if routes[kind] == "pallas":
            require(kernels, ["_fp_local_kernel"], f"{kind} attention")
        wants = [np.asarray(plain(x[i], cached[0][i], cached[1][i],
                                  start[i], weights, kind == "window"))[:n]
                 for i, n in enumerate(valid)]
        readings[kind] = worst(jitted, cache, wants)
        zeroed = tuple(jnp.where(here[..., None, None], 0, c).astype(bf16)
                       for c in cache) if kind == "global" else tuple(
            jnp.zeros_like(c) for c in cache)
        readings[f"{kind}, cache zeroed"] = worst(jitted, zeroed, wants)
        real = gqa_attn.gqa_attention if kind == "window" else gqa_attn._qkv
        try:  # the model broken underneath, compiled anew
            if kind == "window":
                gqa_attn.gqa_attention = lambda *a, window, **kw: real(
                    *a, window=window + 1, **kw)
                name = "window, one key too wide"
            else:
                def turned(*a):
                    q, k, v = real(*a)
                    return (apply_rope(q, cos, sin, pos),
                            apply_rope(k, cos, sin, pos), v)

                gqa_attn._qkv = turned
                name = "global, rotary on"
            # a new function: the same one would find its compiled self
            readings[name] = worst(jax.jit(lambda *a: fwd(*a)), cache,
                                   wants)
        finally:
            if kind == "window":
                gqa_attn.gqa_attention = real
            else:
                gqa_attn._qkv = real
    say("window and global attention against plain float32 attention, "
        "largest difference over largest output (tolerance "
        f"{WINDOW_TOL}): " + "; ".join(
            f"{k} {v:.4f}" for k, v in readings.items()))
    own = max(readings["window"], readings["global"])
    broken = min(v for k, v in readings.items() if "," in k)
    if not own < WINDOW_TOL < broken:
        raise RuntimeError(
            f"window and global attention: {own:.4f} has to lie under "
            f"{WINDOW_TOL} and every broken reading ({broken:.4f} the "
            "least) over it")


# The held experts' grouped matmul on the chip, a hybrid configuration
# a row: its constructor, the experts its cell holds, and the (token,
# choice) pairs one of them sees a step there (the mean; the draw is
# Poisson about it). DEPLOYED_LOAD: what a deployment's batches bring.
EXPERT_CELLS = (("qwen3_next_80b", 128, 7), ("kimi_linear_48b", 16, 12),
                ("k_exaone_236b", 16, 21))
DEPLOYED_LOAD = 180
# Largest difference from the loop over experts, as a share of its
# largest output: the kernel sums K in tiles and rounds once to bf16,
# as the loop does (one bf16 step is 2**-8 of a value), against one
# group's offset shifted by ONE row, whose row meets another expert's
# weights (experts_phase prints both).
EXPERTS_TOL = 0.01


def experts_phase(seed: int, rows=None) -> None:
    """`kernels.grouped_gemm` on the route the chip gives it, both
    products of an expert block at the three hybrid configurations'
    widths over a stack of two layers' experts of which the second's
    groups alone are not empty (as `held_moe_fwd` hands it), against
    `grouped_gemm_ref` over that layer's experts and the groups' rows.
    The group sizes are the cells' and a deployment's (as many of 180
    as the step's rows hold). Broken on purpose: the same result
    against the reference with ONE group's offset shifted by a row has
    to read over the tolerance."""
    import jax
    import jax.numpy as jnp

    from triton_dist_tpu.kernels.grouped_gemm import (
        grouped_gemm,
        grouped_gemm_ref,
        grouped_gemm_route,
    )
    from triton_dist_tpu.models import ModelConfig

    bf16, f32 = jnp.bfloat16, jnp.float32
    rng = np.random.default_rng(seed)
    layers, layer = 2, 1
    for name, held, load in EXPERT_CELLS:
        cfg = getattr(ModelConfig, name)()
        hidden, inter = cfg.hidden_size, cfg.moe_intermediate_size
        t = rows or 1024 * cfg.num_experts_per_tok
        for k, n in ((hidden, 2 * inter), (inter, hidden)):
            route = grouped_gemm_route(t, k, n)
            keys = jax.random.split(jax.random.PRNGKey(seed + k + n), 2)
            w = (0.02 * jax.random.normal(keys[0], (layers * held, k, n),
                                          f32)).astype(bf16)
            x = jax.random.normal(keys[1], (t, k), f32).astype(bf16)
            fwd = jax.jit(grouped_gemm)
            for mean in (load, min(DEPLOYED_LOAD, t // held)):
                own = np.minimum(rng.poisson(mean, held), t // held)
                sizes = np.zeros(layers * held, np.int32)
                sizes[layer * held:] = own
                if mean == load:
                    kernels, secs = compile_and_name(fwd, x, w, sizes)
                    say(f"experts {name} ({k}, {n}): route {route!r}, "
                        f"kernels {kernels or 'none'}, compiled in "
                        f"{secs:.1f}s")
                    if route == "pallas":
                        require(kernels, ["_moe_gmm_kernel"],
                                f"{name}'s grouped matmul ({k}, {n})")
                m = int(own.sum())
                got = np.asarray(fwd(x, w, sizes)[:m], np.float32)
                # a row moves from the group behind the first boundary
                # to the group in front of it
                at = int(np.flatnonzero(own[1:] > 0)[0]) + 1
                shifted = own.copy()
                shifted[at - 1] += 1
                shifted[at] -= 1
                reads = []
                for wanted in (own, shifted):
                    ref = np.asarray(grouped_gemm_ref(
                        x[:m], w[layer * held:], jnp.asarray(wanted),
                        out_dtype=f32))
                    reads.append(float(np.abs(got - ref).max()
                                       / np.abs(ref).max()))
                say(f"experts {name} ({k}, {n}) at {mean} rows an expert "
                    f"({m} rows in {held} groups): {reads[0]:.5f} of the "
                    f"largest output; one offset shifted {reads[1]:.4f} "
                    f"(tolerance {EXPERTS_TOL})")
                if not reads[0] < EXPERTS_TOL < reads[1]:
                    raise RuntimeError(
                        f"grouped matmul {name} ({k}, {n}): {reads[0]:.5f} "
                        f"has to lie under {EXPERTS_TOL} and the shifted "
                        f"offset's {reads[1]:.4f} over it")
    combine_case(seed)


# The held experts' combine at granite-4.0-h-small's widths: hidden,
# choices a token, experts routed over, experts held, the step's rows;
# the rows valid in each case: about the cell's third, and every row
# (whose held pairs overflow the kernel's stage: the jnp fold runs)
COMBINE_CELL = (4096, 10, 72, 36, 1024)
COMBINE_VALID = (352, 1024)


def combine_case(seed: int) -> None:
    """`kernels.moe_combine` on the route the chip gives it against the
    jnp forms on the same routed pairs: on the kernel's route bit for
    bit the left fold in choice order (`combine_fold`), within
    EXPERTS_TOL the un-sort and einsum (`combine_unsort`), with the
    sorted rows behind the held pairs NaN, and the rows it says it
    read. Broken on purpose: two tokens' held pairs swapped have to
    read over the tolerance."""
    import jax
    import jax.numpy as jnp

    from triton_dist_tpu.kernels.moe_combine import (
        combine_fold,
        combine_unsort,
        moe_combine,
        moe_combine_route,
    )
    from triton_dist_tpu.kernels.moe_utils import sort_by_expert, topk_routing

    h, top_k, experts, held, m = COMBINE_CELL
    route = moe_combine_route(m, top_k, h)
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    weights, ids = topk_routing(
        jax.random.normal(keys[0], (m, experts), jnp.float32), top_k)
    y = jax.random.normal(keys[1], (m * top_k, h), jnp.float32)
    fwd = jax.jit(moe_combine)
    for valid in COMBINE_VALID:
        here = (ids < held) & (jnp.arange(m) < valid)[:, None]
        sort = sort_by_expert(jnp.where(here, ids, held), held + 1)
        n = jnp.sum(sort.group_sizes[:held])
        mine = jnp.where(here, weights, 0.0)
        rows = jnp.where((jnp.arange(m * top_k) < n)[:, None], y,
                         jnp.nan).astype(jnp.bfloat16)
        args = (rows, sort.unsort_idx, n, mine)
        if valid == COMBINE_VALID[0]:
            kernels, secs = compile_and_name(fwd, *args)
            say(f"combine ({m} x {top_k}, {h}): route {route!r}, kernels "
                f"{kernels or 'none'}, compiled in {secs:.1f}s")
            if route == "pallas":
                require(kernels, ["_moe_combine_kernel"],
                        "the held experts' combine")
        got, read = fwd(*args)
        got = np.asarray(got)
        fold = np.asarray(jax.jit(combine_fold)(*args))
        unsort = np.asarray(jax.jit(combine_unsort)(*args))
        # the first held pair and the first of another token
        flat = np.flatnonzero(np.asarray(here).reshape(-1))
        pair = np.array([flat[0], flat[flat // top_k > flat[0] // top_k][0]])
        swapped = sort.unsort_idx.at[pair].set(sort.unsort_idx[pair[::-1]])
        broken = np.asarray(fwd(rows, swapped, n, mine)[0])
        scale = np.abs(unsort).max()
        reads = [float(np.abs(a - unsort).max() / scale)
                 for a in (got, broken)]
        differ = int(np.sum(got != fold))
        say(f"combine at {valid} valid rows ({int(n)} held pairs of "
            f"{m * top_k}, {int(read)} rows read): {differ} values not "
            f"the fold's bits; {reads[0]:.2e} of the largest output from "
            f"the un-sort; two held pairs swapped {reads[1]:.4f} "
            f"(tolerance {EXPERTS_TOL})")
        # the kernel's route is the fold's sum (the jnp route's einsum
        # sums in an order of its own)
        if not np.isfinite(got).all() or (route == "pallas" and differ):
            raise RuntimeError(
                f"combine at {valid} valid rows: {differ} values differ "
                "from the left fold in choice order, or a row behind the "
                "held pairs reached the output")
        if not reads[0] < EXPERTS_TOL < reads[1]:
            raise RuntimeError(
                f"combine at {valid} valid rows: {reads[0]:.2e} has to lie "
                f"under {EXPERTS_TOL} and the swapped pairs' "
                f"{reads[1]:.4f} over it")


# real columns of each of eight slots in three steps of 128: whole
# chunks, one column, a part, none; slot 7 is taken by a NEW request in
# the last step (fresh among warm ones)
SSD_VALID = ((128, 128, 1, 128, 72, 0, 128, 128),
             (128, 1, 128, 0, 128, 128, 40, 0),
             (128, 128, 128, 128, 1, 128, 0, 128))
# rms of the difference from the recurrence in float32 over the rms of
# its output, over the steps that start from a carried state: what
# bfloat16 leaves against what a carry broken on purpose gives
# (ssd_phase prints each)
SSD_TOL = 0.012


def ssd_phase(seed: int, cols: int = 128) -> None:
    """`layers.mamba2.mamba2_fwd` (the chunked form, XLA) at
    granite-4.0-h-micro's widths over three steps with the state
    carried and ragged `n_valid`, against the recurrence position by
    position in float32 at `highest` (the benchmark's plain reference,
    `perfbench/reference/granite_hybrid.py` `mamba`): what the
    benchmark's `correct` may see faintly under random weights. Broken
    on purpose, each has to read over the tolerance: the state dropped
    between steps, the convolution tail dropped, D x dropped."""
    import jax
    import jax.numpy as jnp

    from triton_dist_tpu.layers import mamba2
    from triton_dist_tpu.models import ModelConfig, hybrid

    cfg = ModelConfig.granite_4_h_micro()
    spec = hybrid.mamba_spec(cfg)
    hh, pd, n, di, ch = (spec.num_heads, spec.head_dim, spec.state,
                         spec.inner, spec.channels)
    hidden, taps = cfg.hidden_size, spec.conv
    valid = np.minimum(np.asarray(SSD_VALID, np.int32), cols)
    slots = valid.shape[1]
    bf16, f32 = jnp.bfloat16, jnp.float32
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    drawn = {}
    for k, (name, shape, init) in zip(
            keys, hybrid._mixer_leaves(cfg, "mamba2", 1)):
        shape = shape[1:]  # one block's
        if init == "ones":
            drawn[name] = jnp.ones(shape, bf16)
        elif init == "normal":
            drawn[name] = (0.02 * jax.random.normal(k, shape, f32)
                           ).astype(bf16)
        else:  # Mamba-2's published initialisation, as the model draws
            drawn[name] = hybrid._mamba_init(
                jax.random.uniform(k, shape, f32), init, jnp,
                taps).astype(bf16)
    p = mamba2.Mamba2Params(*(drawn[nm] for nm in hybrid._MIXER_LEAVES[
        "mamba2"]))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (len(valid), slots, cols, hidden), f32
                          ).astype(bf16)

    def served(fwd, drop=None):
        """[y a step] with the state carried; `drop` zeroes one of the
        two states between steps."""
        rec = jnp.zeros((slots, hh, pd, n), f32)
        conv = jnp.zeros((slots, taps - 1, ch), bf16)
        lengths, out = np.zeros((slots,), np.int32), []
        for step, nv in enumerate(valid):
            if step == 2:
                lengths[7] = 0
            y, rec, conv = fwd(x[step], p, spec, rec, conv,
                               jnp.asarray(nv), jnp.asarray(lengths == 0),
                               cfg.rms_eps)
            if drop == "state":
                rec = jnp.zeros_like(rec)
            if drop == "tail":
                conv = jnp.zeros_like(conv)
            out.append(np.asarray(y.astype(f32)))
            lengths += nv
        return out

    # the benchmark's plain reference of this mixer (float32 at
    # `highest`, position by position from zero; imports nothing of the
    # program) over the same weights, under the names it draws them by
    from perfbench import harness

    root = os.path.dirname(os.path.abspath(__file__))
    ref = harness.load_reference(root, "granite_hybrid")
    sizes = ref.Sizes.from_config(harness.load_json(os.path.join(
        root, "perfbench", "configs", "granite-4.0-h-micro.1chip.json")))
    recurrence = jax.jit(lambda rows: ref.mamba(
        sizes, rows.astype(f32), drawn, None))

    # a slot's sequences: slot 7 has two, the others one
    wants = {}
    for slot in range(slots):
        for which, steps in (((0, (0, 1)), (1, (2,))) if slot == 7
                             else ((0, (0, 1, 2)),)):
            rows = [x[st, slot, :valid[st, slot]] for st in steps]
            full = np.asarray(recurrence(jnp.concatenate(rows)))
            at = 0
            for st in steps:
                wants[(st, slot)] = full[at:at + valid[st, slot]]
                at += valid[st, slot]

    def reading(ys):
        """Over the rows of steps 1 and 2 whose slot carries a state
        into the step."""
        num = den = 0.0
        for (st, slot), want in wants.items():
            if st == 0 or (st == 2 and slot == 7) or not len(want):
                continue
            got = ys[st][slot, :len(want)]
            num += float(((got - want) ** 2).sum())
            den += float((want ** 2).sum())
        return (num / den) ** 0.5

    fwd = jax.jit(mamba2.mamba2_fwd, static_argnums=(2, 7))
    no_skip = jax.jit(
        lambda h, q, *a: mamba2.mamba2_fwd(
            h, q._replace(d=jnp.zeros_like(q.d)), *a),
        static_argnums=(2, 7))
    readings = {"carried": reading(served(fwd)),
                "the state dropped": reading(served(fwd, "state")),
                "the tail dropped": reading(served(fwd, "tail")),
                "D x dropped": reading(served(no_skip))}
    say(f"state-space mixer at {hh} heads x {pd} over a state of {n}, "
        f"{slots} slots x {cols} columns x {len(valid)} steps, against "
        "the recurrence in float32, rms of the difference over the rms "
        f"of the output (tolerance {SSD_TOL}): " + "; ".join(
            f"{k} {v:.4f}" for k, v in readings.items()))
    broken = min(v for k, v in readings.items() if k != "carried")
    if not readings["carried"] < SSD_TOL < broken:
        raise RuntimeError(
            f"state-space mixer: {readings['carried']:.4f} has to lie "
            f"under {SSD_TOL} and every broken reading ({broken:.4f} the "
            "least) over it")


# cached positions and real columns of each of eight slots in one step
# of 128 (as WINDOW_LENS / WINDOW_VALID: whole chunks, one column, a
# part, a slot that starts)
NOPE_LENS = (8192, 6001, 4097, 2049, 1025, 513, 180, 128)
NOPE_VALID = (128, 128, 1, 128, 1, 128, 72, 128)
# largest difference over largest output: what bfloat16 leaves against
# what a break on purpose gives (nope_phase prints each)
NOPE_TOL = 0.04


def nope_phase(seed: int, max_len: int = 8192) -> None:
    """`layers.gqa_attn.global_attn_fwd` with a head NARROWER than a
    page keeps it, on the route the planner names for the chip, at
    granite-4.0-h-micro's widths (32 q / 8 kv heads of 64 kept 128
    wide, no rotary, no q/k norm, the scale 1/64) over a paged view of
    up to 8,192 cached positions, against the benchmark's plain
    reference of this block (`perfbench/reference/granite_hybrid.py`
    `attention`: float32 at `highest`, one causal pass over the slot's
    whole timeline, heads of 64, no cache): what the benchmark's
    `correct` does not see under random weights (four such blocks of
    forty). Broken on purpose, each has to read over the tolerance: the
    scale taken as 64 ** -0.5, the cached pages zeroed."""
    import jax
    import jax.numpy as jnp

    from perfbench import harness
    from triton_dist_tpu.layers import gqa_attn
    from triton_dist_tpu.models import ModelConfig, hybrid
    from triton_dist_tpu.plan.planner import route_hybrid_attention

    cfg = ModelConfig.granite_4_h_micro(max_positions=max_len)
    spec = hybrid.gqa_spec(cfg)
    hq, hkv, d = spec[:3]
    assert (d, spec.store, spec.qk_norm) == (64, 128, False), spec
    hidden, cols = cfg.hidden_size, 128
    slots = len(NOPE_LENS)
    lens = np.minimum(NOPE_LENS, max_len)
    valid = np.minimum(NOPE_VALID, lens)
    route = route_hybrid_attention(cfg, slots, cols, max_len)
    bf16, f32 = jnp.bfloat16, jnp.float32
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    names = ("attn_w_q", "attn_w_kv", "attn_w_o")
    shapes = ((hidden, hq * d), (hidden, 2 * hkv * d), (hq * d, hidden))
    w = {n: (0.02 * jax.random.normal(k, s, f32)).astype(bf16)
         for n, k, s in zip(names, keys, shapes)}
    p = gqa_attn.GQAttnParams(w["attn_w_q"], w["attn_w_kv"], None, None,
                              w["attn_w_o"])
    # each slot's whole timeline of normed rows, three times over: at
    # unit size the scores under 1/64 lie 0.1 apart and every scale
    # gives the mean of the values
    line = (3.0 * jax.random.normal(keys[3], (slots, max_len, hidden), f32)
            ).astype(bf16)
    start = jnp.asarray(lens - valid, jnp.int32)
    pos = start[:, None] + jnp.arange(cols)[None, :]
    x = jnp.take_along_axis(line, jnp.minimum(pos, max_len - 1)[..., None],
                            axis=1)
    kv_len = jnp.asarray(lens, jnp.int32)
    # what the earlier steps left in the pages: the keys and values of
    # the positions before `start` as the block stores them (bfloat16,
    # 64 values and 64 zeros a head); past them what another request
    # left there
    kv = jnp.einsum("bth,hc->btc", line, w["attn_w_kv"],
                    preferred_element_type=f32).astype(bf16)
    here = (jnp.arange(max_len)[None, :] < start[:, None])[..., None, None]
    view = tuple(jnp.where(here, jnp.pad(
        half.reshape(slots, max_len, hkv, d),
        ((0, 0),) * 3 + ((0, spec.store - d),)), 50.0).astype(bf16)
        for half in (kv[..., :hkv * d], kv[..., hkv * d:]))

    def fwd(x, p, view, spec=spec):
        return gqa_attn.global_attn_fwd(x, p, spec, pos, view, kv_len,
                                        route, cfg.rms_eps)[0]

    root = os.path.dirname(os.path.abspath(__file__))
    ref = harness.load_reference(root, "granite_hybrid")
    sizes = ref.Sizes.from_config(harness.load_json(os.path.join(
        root, "perfbench", "configs", "granite-4.0-h-micro.1chip.json")))
    plain = jax.jit(lambda rows: ref.attention(
        sizes, rows.astype(f32), w, None))
    wants = [np.asarray(jax.lax.dynamic_slice_in_dim(
        plain(line[i]), int(start[i]), int(n))) for i, n in enumerate(valid)]

    def worst(jitted, cache) -> float:
        """Over the slots' valid columns (the others are discarded)."""
        got = np.asarray(jitted(x, p, cache).astype(f32))
        return max(float(np.abs(got[i, :len(want)] - want).max()
                         / np.abs(want).max())
                   for i, want in enumerate(wants))

    jitted = jax.jit(fwd)
    kernels, secs = compile_and_name(jitted, x, p, view)
    say(f"attention with heads of {d} kept {spec.store} wide: route "
        f"{route!r}, kernels {kernels or 'none'}, compiled in {secs:.1f}s")
    if route == "pallas":
        require(kernels, ["_fp_local_kernel"], "padded-head attention")
    readings = {
        "as served": worst(jitted, view),
        "the cached pages zeroed": worst(jitted, tuple(
            jnp.where(here, 0, c).astype(bf16) for c in view)),
        "the scale taken as d ** -0.5": worst(jax.jit(
            lambda *a: fwd(*a, spec=spec._replace(scale=None))), view)}
    say(f"{hq} q / {hkv} kv heads of {d} over pages {spec.store} wide "
        f"against the reference's attention in float32, largest "
        f"difference over largest output (tolerance {NOPE_TOL}): "
        + "; ".join(f"{k} {v:.4f}" for k, v in readings.items()))
    broken = min(v for k, v in readings.items() if k != "as served")
    if not readings["as served"] < NOPE_TOL < broken:
        raise RuntimeError(
            f"padded-head attention: {readings['as served']:.4f} has to "
            f"lie under {NOPE_TOL} and every broken reading ({broken:.4f} "
            "the least) over it")


def run(cfg, mesh, seed: int, prompts, cross_chip: bool) -> None:
    """Every phase on `mesh`. cross_chip: the tp>1 contract (kernels of
    the overlapped collectives by name, no megakernel phase)."""
    import jax

    from triton_dist_tpu.models import Engine

    t0 = time.perf_counter()
    eng = Engine(cfg, mesh, max_len=MAX_LEN, seed=seed, fast_init=True)
    jax.block_until_ready(eng.params)
    say(f"engine: prefill_mode={eng.prefill_mode} decode_mode="
        f"{eng.decode_mode}, parameters drawn sharded from seed {seed} in "
        f"{time.perf_counter() - t0:.1f}s")
    if cross_chip:
        check_ring(mesh)
        model = sum(x.size * x.dtype.itemsize
                    for x in jax.tree.leaves(eng.params))
        check_memory_spread(mesh, model)

    served = serve_phase(
        eng, prompts,
        want_kernels=["_gemm_rs_kernel"] if cross_chip
        else ["_fp_local_kernel"])
    ref = reference_logits(eng, prompts, served)
    tol = (TOL_ULPS * 2.0 ** -8 * cfg.num_layers ** 0.5
           * float(ref.std()))
    say(f"tolerance: {tol:.4f} = {TOL_ULPS} x 2^-8 x sqrt(depth "
        f"{cfg.num_layers}) x the reference logits' standard deviation "
        f"{float(ref.std()):.4f}")
    check_served_tokens(ref, served, tol)
    first_decode = engine_phase(
        eng, prompts, served, ref, tol,
        want_prefill=(["_ag_gemm_kernel", "_gemm_rs_kernel"] if cross_chip
                      else ["_fp_local_kernel"]),
        want_decode=["_one_shot_ar_kernel"] if cross_chip else [])
    if not cross_chip:
        mega_phase(eng, first_decode, tol)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the cross-chip path (tp=4, full depth) and "
                         "its reference, no other phase")
    args = ap.parse_args()

    import jax

    from triton_dist_tpu import lang
    from triton_dist_tpu.models import ModelConfig
    from triton_dist_tpu.runtime import enable_compile_cache, make_mesh

    dev = jax.devices()[0]
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())} jax={jax.__version__}")
    if dev.platform != "tpu" or lang.use_interpret():
        print("chip_smoke.py needs a TPU (JAX's first device is "
              f"{dev.platform!r}, interpret={lang.use_interpret()}): "
              "nothing was built", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, JAX "
              f"found {len(jax.devices())}", file=sys.stderr)
        return 2
    cache = CacheCounter()
    say(f"compile cache: {enable_compile_cache()}")

    full = ModelConfig.qwen3_8b(max_positions=MAX_LEN)
    if args.chips == 1:
        depth = depth_for_one_chip(full, dev.memory_stats()["bytes_limit"])
    else:
        depth = full.num_layers
    cfg = ModelConfig.qwen3_8b(num_layers=depth, max_positions=MAX_LEN)
    say(f"model: Qwen3-8B widths untouched (hidden {cfg.hidden_size}, "
        f"intermediate {cfg.intermediate_size}, {cfg.num_q_heads} q / "
        f"{cfg.num_kv_heads} kv heads, head_dim {cfg.head_dim}, vocab "
        f"{cfg.vocab_size}, {cfg.dtype}, untied head); reduced: depth "
        f"{depth} of {full.num_layers}"
        + ("" if depth < full.num_layers else " (nothing cut)"))
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in PROMPT_LENS]

    if args.chips == 1:
        latent_phase(args.seed)
        window_phase(args.seed)
        experts_phase(args.seed)
        ssd_phase(args.seed)
        nope_phase(args.seed)
    run(cfg, make_mesh((args.chips,), ("tp",)), args.seed, prompts,
        cross_chip=args.chips > 1)

    say(f"compile cache: {cache.hits} hits, {cache.misses} misses")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
