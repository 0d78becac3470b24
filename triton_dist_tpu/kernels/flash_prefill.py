"""Device-side SP flash prefill — online-softmax consumer over per-segment
LL-AG delivery semaphores.

TPU-native re-design of the reference's signature SP mechanism
(ref: python/triton_dist/kernels/nvidia/sp_ag_attention_intra_node.py:105-427):
there, the copy engine allgathers the KV shards segment-by-segment while a
flash-attention consumer waits on per-segment barriers before folding each
arriving segment — the compute/collective overlap T3 (arXiv 2401.16677)
argues for at kernel granularity. `kernels/sp_attention.ring_attention`
expresses the same schedule as `lax.ppermute` + XLA async overlap; THIS
module is the thesis applied: one Pallas kernel whose ring ingest pushes
the local KV shard to every peer with a per-segment delivery semaphore
(the LL-AG producer discipline, `low_latency_allgather.segment_collect_
start` — each arriving segment counted on its own slot so the consumer can
gate on exactly one segment), folds the LOCAL block at step 0 (the
reference's rank-offset swizzle: zero-wait start), then waits each
remaining segment's slot before folding it while later segments are still
in flight.

Two kernel faces:

  flash_prefill_local — the n=1 core: blockwise online-softmax GQA
  prefill streaming (block, Hkv*D) KV pages double-buffered from HBM
  (the prefill analog of `flash_decode._fd_partial_kernel`), with
  general `q_positions` / `kv_len` masking so it serves both long-context
  prefill and the serve plane's prefill-into-cache chunks.

  sp_flash_prefill — the distributed form: per-device inside shard_map,
  rank r holds Q rows and KV rows [r*S_loc, (r+1)*S_loc). Bit-identical
  to `flash_prefill_ref` (the same swizzle-order fold over an XLA-
  gathered KV — the per-segment semaphore transport moves bytes, never
  bits) and allclose to the dense `ring_attention_ref` oracle (online
  softmax re-associates the reductions, so dense-softmax bit parity is
  not a meaningful target; the kernel-math oracle is the bit contract).

Numerical contract of the fold: a fully-masked block is a BITWISE no-op
(m_new == m so alpha == exp(0) == 1.0 exactly; p == 0 under the mask),
which is what lets the kernel skip dead KV pages (`n_act`) while staying
bit-identical to an unskipped replay, and lets causal ranks fold future
segments as masked no-ops without a divergent branch.

Impl selection (flash vs the `ring_attention` fallback) is priced by
`perf_model.estimate_flash_prefill_ms` / `choose_sp_prefill_impl` and
block candidates by `autotuner.prune_flash_prefill_configs`; see
`sp_prefill_attention` (the autotuner-selectable switch) and
docs/performance.md "Prefill regimes". Claimed against the bench artifact
(first measured by the r06 cpu-world1 rig — interpreter semantics, see
docs/performance.md "Rigs"; the bands span the 0.67-2.4x run-to-run
spread of the 2-core rig's slope ratio, and the default-rig S=4096
artifact re-narrows)
as [perf:sp_prefill_vs_ring=0.3-2.6] / [perf:sp_prefill_vs_xla=0.45-2.0].
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.kernels.flash_decode import _fd_chunk as _kv_block
from triton_dist_tpu.kernels.low_latency_allgather import (
    segment_collect_start,
)
from triton_dist_tpu.faults import guard as _guard
from triton_dist_tpu.faults import plan as _fplan
from triton_dist_tpu.lang import shmem
from triton_dist_tpu.lang.core import (
    cdiv,
    compiler_params,
    cost_estimate,
    interpret_no_headroom,
    next_collective_id,
    tpu_call,
    use_interpret,
)
from triton_dist_tpu.runtime.init import SP_AXIS
from triton_dist_tpu.trace import events as trace_ev

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class FlashPrefillConfig:
    """Tunable knobs of the flash-prefill kernels (the autotuner's
    candidate unit; `autotuner.flash_prefill_config_space`)."""

    block: int = 512  # KV page rows streamed per fold step


def supports_flash_prefill(hq: int, hkv: int, d: int,
                           v_prefix: Optional[int] = None) -> bool:
    """Shapes the native kernel accepts: lane-aligned head_dim (the
    per-head column slices of the (rows, Hkv*D) pages must be
    lane-aligned for Mosaic) and an integral GQA group. With
    `v_prefix` (the values are the first `v_prefix` columns of the key
    page: latent attention) there is ONE kv head and the prefix is
    lane-aligned too (Mosaic refuses a page row of 576: "Slice shape
    along dimension 2 must be aligned to tiling (128)"). Interpret mode
    accepts anything; callers' auto paths gate on this for native."""
    if v_prefix is not None and not (
            hkv == 1 and v_prefix % 128 == 0 and v_prefix <= d):
        return False
    return d % 128 == 0 and hq % hkv == 0


def flash_prefill_native_ok(hq: int, hkv: int, d: int) -> bool:
    """THE auto-routing gate: native backend + supported shape.
    Interpret-mode runs stay on the xla formulation so CPU-mesh results
    are bit-stable. Every auto path (layers.attention routing, the
    serve Scheduler's chunk pricing, sp_prefill_attention) consults
    this one definition — a constraint added here reaches them all.
    Memory feasibility is a separate, shape-dependent question:
    flash_prefill_fits."""
    return not use_interpret() and supports_flash_prefill(hq, hkv, d)


def fit_block(t: int, block: Optional[int] = None) -> int:
    """THE page-height fitting rule: the largest sublane-aligned
    DIVISOR of t that is <= block (whole-t fallback when none exists).
    sp_flash_prefill, flash_prefill_ref, the autotuner's pruner, and
    the bench arm all fit through here, so no consumer ever models or
    measures a page geometry the kernel would not run."""
    return _kv_block(t, int(block)) if block else _kv_block(t)


# f32 (rows, Hq*D) Q slab one grid step of the local kernel holds. The
# per-head online-softmax state scales with it (see
# flash_prefill_vmem_bytes), so this bounds the kernel's VMEM whatever
# the prompt length: 128 rows at Qwen3-8B's 32 heads, 512 at a tp=4
# shard's 8.
Q_TILE_BYTES = 2 << 20

# Mosaic margin on top of the modeled residents (DMA descriptors,
# pipeline bookkeeping, operands XLA itself pins in VMEM around the
# call) — added by BOTH the routing gate and the launch's
# vmem_limit_bytes, so the two cannot disagree.
VMEM_MARGIN = 8 << 20


def fit_q_rows(s: int, hq: int, d: int) -> int:
    """Query rows one grid step of the LOCAL kernel folds: the largest
    divisor of s, a multiple of 16 (a packed bf16 sublane tile), whose
    f32 (rows, Hq*D) slab stays within Q_TILE_BYTES; whole-s when no
    such divisor exists (short or odd chunks)."""
    cap = max(Q_TILE_BYTES // (hq * d * 4), 16)
    cands = [c for c in range(16, min(cap, s) + 1, 16) if s % c == 0]
    return cands[-1] if cands else s


def shared_head_rows(s: int, hq: int, v_prefix: Optional[int]):
    """(query rows, query heads) as the LOCAL kernel sees them. Under
    `v_prefix` every query head attends the same one kv head, so the
    heads are stacked into the rows (row s * Hq + h) and each page
    meets ONE (rows, d) slab in one matmul, not Hq slabs of a few rows
    each. The one place that knows: `flash_prefill_local` launches on
    it and the VMEM gate sizes by it."""
    return (s * hq, 1) if v_prefix is not None else (s, hq)


def flash_prefill_vmem_bytes(s_q: int, hq: int, hkv: int, d: int,
                             block: int, dtype=jnp.bfloat16,
                             batch: int = 1,
                             q_rows: Optional[int] = None,
                             v_prefix: Optional[int] = None) -> int:
    """Per-grid-step resident VMEM of the flash-prefill kernels — THE
    accounting behind the launch's vmem_limit_bytes, the routing gate
    (flash_prefill_fits) and the autotuner's pruner.

    KV side: the double-buffered K+V page pair in the cache dtype plus
    the f32 cast of the page being folded. Q side, per resident row
    block of `q_rows` rows: ten (rows, Hq*D) f32 slabs — the scaled Q
    slab, acc, and m and l (each (rows, 1) per head, which Mosaic pads
    to a full 128-lane tile: as large as acc at D=128), the loop's
    carried copy of those three, the finalized output, and the
    double-buffered bf16 Q/O blocks. The multiplier was read off the
    chip compiler's scoped-VMEM reports at Qwen3-8B widths (PR 24).

    q_rows: rows resident at once — default the local kernel's tile
    (fit_q_rows); the SP kernel keeps all s_q rows of all `batch` rows
    live across its segment sweep and passes q_rows=s_q, batch=B.
    v_prefix: one stream of pages, not two, and the heads stacked into
    the rows (`shared_head_rows`); a (rows, block) float32 logits and
    probabilities pair is then as large as a slab and is counted."""
    isz = jnp.dtype(dtype).itemsize
    s_q, hq = shared_head_rows(s_q, hq, v_prefix)
    rows = fit_q_rows(s_q, hq, d) if q_rows is None else q_rows
    streams = 1 if v_prefix is not None else 2
    kv = streams * (2 * block * hkv * d * isz + block * hkv * d * 4)
    logits = 2 * rows * block * 4 if v_prefix is not None else 0
    return kv + logits + batch * 10 * rows * hq * max(d, 128) * 4


def flash_prefill_fits(s_q: int, t: int, hq: int, hkv: int, d: int,
                       block: Optional[int] = None,
                       dtype=jnp.bfloat16, batch: int = 1,
                       q_rows: Optional[int] = None,
                       v_prefix: Optional[int] = None) -> bool:
    """Memory-feasibility gate for auto routing: the per-grid-step
    residents plus VMEM_MARGIN must fit the forced-kernel VMEM ceiling
    — exactly what the launch will ask Mosaic for. What does not fit
    stays on the fallback path (blockwise-xla locally, the ppermute
    ring for SP) instead of failing at Mosaic allocation. batch/q_rows:
    see flash_prefill_vmem_bytes — pass B and s_q when gating the SP
    kernel."""
    from triton_dist_tpu.perf_model import kernel_vmem_ceiling

    need = flash_prefill_vmem_bytes(s_q, hq, hkv, d, fit_block(t, block),
                                    dtype, batch=batch, q_rows=q_rows,
                                    v_prefix=v_prefix)
    return need + VMEM_MARGIN <= kernel_vmem_ceiling()


# -- shared fold math (kernel body AND the bit-exact host replay) ------------


def _block_live(s: int, blk: int, base, qp_col, valid_len, causal: bool,
                window: Optional[int] = None, valid_from=None):
    """(S, blk) liveness mask of one KV block at global offset `base`:
    rows are q positions (qp_col (S,1) i32), columns KV positions.
    With `window` (static) a row at position i also sees no key before
    i - window + 1, and none before `valid_from`."""
    kpos = jax.lax.broadcasted_iota(jnp.int32, (s, blk), 1) + base
    live = kpos < valid_len
    if causal:
        live = jnp.logical_and(live, kpos <= qp_col)
    if window is not None:
        live = jnp.logical_and(live, kpos > qp_col - window)
        live = jnp.logical_and(live, kpos >= valid_from)
    return live


def _head_update(q_hg, k_blk, v_blk, live, state):
    """Fold one (blk, D) KV block into one (h, g) head's online-softmax
    state (m, l (S,1); acc (S, D) — all f32). The same op sequence runs
    inside the kernel and in flash_prefill_ref: bit parity between the
    overlapped transport and the plain replay rests on it."""
    m, l, acc = state
    lg = jax.lax.dot_general(
        q_hg, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (S, blk)
    lg = jnp.where(live, lg, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(lg, -1, keepdims=True))
    # fully-masked block: m_new == m bitwise, alpha == exp(0) == 1.0,
    # p == 0 -> the whole update is a bitwise no-op (see module doc)
    alpha = jnp.exp(m - m_new)
    p = jnp.where(live, jnp.exp(lg - m_new), 0.0)
    l_new = l * alpha + jnp.sum(p, -1, keepdims=True)
    pv = jax.lax.dot_general(
        p, v_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (S, D)
    return (m_new, l_new, acc * alpha + pv)


def _fold_block_heads(q_slabs, kpage, vpage, live, states, hkv, g, d,
                      dv=None):
    """One KV page folded into every (h, g) head state. kpage/vpage:
    (blk, Hkv*D) f32; q_slabs[hg]: (S, D) f32 pre-scaled. With `dv`
    the values are the first dv columns of the ONE kv head's key page
    (vpage is then the key page itself)."""
    out = []
    for h in range(hkv):
        k_h = kpage[:, h * d:(h + 1) * d]
        v_h = vpage[:, h * d:(h + 1) * d] if dv is None else vpage[:, :dv]
        for gg in range(g):
            hg = h * g + gg
            out.append(_head_update(q_slabs[hg], k_h, v_h, live,
                                    states[hg]))
    return out


def _init_states(hq: int, s: int, d: int):
    return [
        (jnp.full((s, 1), NEG_INF, jnp.float32),
         jnp.zeros((s, 1), jnp.float32),
         jnp.zeros((s, d), jnp.float32))
        for _ in range(hq)
    ]


def _finalize(states):
    """(S, Hq*D) f32 output from the per-head states (empty rows -> 0)."""
    outs = []
    for m, l, acc in states:
        empty = l <= 0.0
        outs.append(jnp.where(empty, 0.0, acc / jnp.maximum(l, 1e-30)))
    return jnp.concatenate(outs, axis=-1)


def _q_slabs(qf, hq: int, d: int, scale: float):
    qs = qf.astype(jnp.float32) * scale  # (S, Hq*D)
    return [qs[:, hg * d:(hg + 1) * d] for hg in range(hq)]


# -- local kernel (n = 1 core; serves blockwise prefill + serve chunks) ------


def _fp_local_kernel(hq, hkv, d, s, t, blk, causal, scale, dv, window,
                     len_ref, *refs):
    """One grid step = `s` query rows (one fit_q_rows tile) of one batch
    row: stream (blk, Hkv*D) KV pages double-buffered from HBM and fold
    each into the per-head online-softmax states (the prefill
    generalization of flash_decode._fd_partial_kernel: S query rows
    instead of 1, per-head 2-D matmuls instead of the block-diagonal
    operand — prefill is MXU-bound, so the decode kernel's Hkv-times
    FLOP inflation is not free here). Rows are independent, so tiling
    them changes no bit of any row's fold; a causal tile also stops at
    ITS last row's page, not the chunk's. With `dv` (the values a
    column prefix of the key page, `flash_prefill_local`'s `v_prefix`)
    there is no v operand: one stream of pages, each read once. With
    `window` a second scalar operand follows the lengths, each batch
    row's first valid KV position, and `_block_live` takes both."""
    if window is not None:
        from_ref, *refs = refs
    q_ref, qpos_ref, *pages, o_ref, vkv, sems = refs  # pages: (k, v) | (k,)
    b = pl.program_id(0)
    g = hq // hkv
    nblk = t // blk
    valid = len_ref[b]

    def kv_start(ci, slot):
        for which, ref in enumerate(pages):
            pltpu.make_async_copy(
                ref.at[b, pl.ds(ci * blk, blk)], vkv.at[slot, which],
                sems.at[slot],
            ).start()

    def kv_wait(slot):
        for which, ref in enumerate(pages):
            pltpu.make_async_copy(
                ref.at[0, pl.ds(0, blk)], vkv.at[slot, which],
                sems.at[slot],
            ).wait()

    qp_col = qpos_ref[0]  # (S, 1) — pre-shaped by the host wrapper
    slabs = _q_slabs(q_ref[0], hq, d, scale)

    # dead-page skip: pages past kv_len — and, causally, past the last
    # q row — fold as bitwise no-ops, so skipping them changes nothing
    hi = valid
    if causal:
        hi = jnp.minimum(hi, jnp.max(qp_col) + 1)
    n_act = jnp.minimum(cdiv(hi, blk), nblk)

    def loop_body(ci, states):
        @pl.when(ci + 1 < n_act)
        def _ahead():
            kv_start(ci + 1, (ci + 1) % 2)

        kv_wait(ci % 2)
        kv = vkv[ci % 2].astype(jnp.float32)  # (streams, blk, W)
        live = _block_live(s, blk, ci * blk, qp_col, valid, causal, window,
                           None if window is None else from_ref[b])
        return tuple(_fold_block_heads(slabs, kv[0], kv[len(pages) - 1], live,
                                       list(states), hkv, g, d, dv))

    @pl.when(n_act > 0)
    def _first():
        kv_start(0, 0)

    states = jax.lax.fori_loop(0, n_act, loop_body,
                               tuple(_init_states(hq, s, dv or d)))
    o_ref[0] = _finalize(list(states)).astype(o_ref.dtype)


# Trace-time record of the most recent flash_prefill_local lowering —
# the fitted KV page height and grid (last_regime()/last_launch()
# idiom): tests pin that a tune-cache attn_block changes the launched
# fold without reverse-engineering the jaxpr.
_last_launch = None


def last_launch():
    return _last_launch


def flash_prefill_local(
    q: jax.Array,  # (B, S, Hq, D)
    k: jax.Array,  # (B, T, Hkv, D)
    v: Optional[jax.Array],
    q_positions: Optional[jax.Array] = None,  # (B, S) absolute positions
    q_offset=0,
    kv_len: Optional[jax.Array] = None,  # (B,) valid KV prefix
    causal: bool = True,
    scale: Optional[float] = None,
    block: Optional[int] = None,
    v_prefix: Optional[int] = None,
    window: Optional[int] = None,
    kv_from: Optional[jax.Array] = None,  # (B,) first valid KV position
) -> jax.Array:
    """Pallas blockwise (flash) GQA prefill over local KV: same contract
    as layers.attention.gqa_attention_blockwise, but KV streams through
    double-buffered (block, Hkv*D) pages so the (S, T) logits tensor
    never exists — peak memory O(S*block). Returns (B, S, Hq, D) in
    q.dtype.

    `v_prefix` (with v None): the values are the first `v_prefix`
    columns of the ONE kv head's keys (latent attention in its
    absorbed form). The pages are then read once, not once as keys and
    once as values; the query heads, which all meet the same page, are
    stacked into the rows (`shared_head_rows`); the result is
    (B, S, Hq, v_prefix).

    `window`: a row at position i attends the keys at i - window + 1
    .. i alone, and none before `kv_from` (default 0). A static branch:
    with no window the kernel's body and operands are what they were."""
    global _last_launch
    assert window is not None or kv_from is None, "kv_from needs a window"
    out_heads = q.shape[2:3] + (v_prefix or q.shape[3],)
    if v_prefix is not None:
        assert v is None and k.shape[2] == 1, (
            "v_prefix: one kv head whose values are a prefix of its keys")
        b, s, hq, d = q.shape
        if q_positions is None:
            q_positions = jnp.broadcast_to(
                jnp.arange(s)[None, :] + q_offset, (b, s))
        q = q.reshape((b,) + shared_head_rows(s, hq, v_prefix) + (d,))
        q_positions = jnp.repeat(q_positions, hq, axis=1)
    b, s, hq, d = q.shape
    _, t, hkv, _ = k.shape
    w = hkv * d
    scale = float(scale if scale is not None else d ** -0.5)
    blk = int(block or _kv_block(t))
    tq = fit_q_rows(s, hq, d)
    grid = (b, s // tq)
    _last_launch = {"kernel": "flash_prefill", "path": "local",
                    "block": blk, "grid": grid, "q_rows": tq,
                    "widths": (d, v_prefix or d),
                    "streams": 1 if v_prefix is not None else 2,
                    "overridden": block is not None}
    pages = (k,) if v_prefix is not None else (k, v)
    t_valid = t
    if t % blk:
        pad = blk - t % blk
        pages = tuple(jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for x in pages)
        t += pad
    len_arr = (jnp.full((b,), t_valid, jnp.int32) if kv_len is None
               else jnp.minimum(jnp.reshape(kv_len, (-1,)),
                                t_valid).astype(jnp.int32))
    if q_positions is None:
        q_positions = jnp.broadcast_to(
            jnp.arange(s)[None, :] + q_offset, (b, s))
    # column-shaped on the host: the kernel consumes (S, 1) directly
    # (no in-kernel minor-dim reshape for Mosaic to lower)
    qpos = q_positions.astype(jnp.int32).reshape(b, s, 1)
    itemsize = jnp.dtype(k.dtype).itemsize
    dv = v_prefix or d
    scalars = (len_arr,)
    if window is not None:
        scalars += (jnp.zeros((b,), jnp.int32) if kv_from is None
                    else jnp.reshape(kv_from, (-1,)).astype(jnp.int32),)
    out = tpu_call(
        functools.partial(_fp_local_kernel, hq, hkv, d, tq, t, blk,
                          causal, scale, v_prefix, window),
        grid=grid,
        out_shape=jax.ShapeDtypeStruct((b, s, hq * dv), q.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * len(scalars) + [
            pl.BlockSpec((1, tq, hq * d), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tq, 1), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
        ] + [pl.BlockSpec(memory_space=pl.ANY)] * len(pages),
        out_specs=pl.BlockSpec((1, tq, hq * dv), lambda i, j: (i, j, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, len(pages), blk, w), k.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=compiler_params(
            vmem_limit_bytes=flash_prefill_vmem_bytes(
                s, hq, hkv, d, blk, k.dtype, q_rows=tq,
                v_prefix=v_prefix) + VMEM_MARGIN,
        ),
        cost_estimate=cost_estimate(
            flops=2 * b * s * hq * t * (d + dv),
            bytes_accessed=len(pages) * b * t * w * itemsize,
        ),
    )(*scalars, q.reshape(b, s, hq * d), qpos,
      *(x.reshape(b, t, w) for x in pages))
    return out.reshape((b, -1) + out_heads)


# -- SP kernel: per-segment-semaphore ring ingest + in-kernel consumer -------


def _fp_sp_kernel(axis, n, bsz, s, hq, hkv, d, blk, causal, scale,
                  straggler, build, gbuild, *refs):
    refs = list(refs)
    len_ref, q_ref, k_ref, v_ref, o_ref, kbuf, vbuf = refs[:7]
    del refs[:7]
    tbuf = refs.pop(0) if build is not None else None
    gbuf = refs.pop(0) if gbuild is not None else None
    gcur = refs.pop() if gbuild is not None else None
    tcur = refs.pop() if build is not None else None
    vkv, sems, send_sem, seg_sems = refs
    me = jax.lax.axis_index(axis)
    g = hq // hkv
    nblk = s // blk
    tctx = trace_ev.make_ctx(build, tbuf, tcur)
    trace_ev.init_ctx(tctx, rank=me)
    R = trace_ev.REGIONS
    gctx = _guard.make_ctx(gbuild, gbuf, gcur, tctx=tctx)
    _guard.init_ctx(gctx, rank=me)

    # peers must be inside the kernel before the segment puts land
    with _guard.attached(gctx):
        shmem.barrier_all(axis)
        shmem.fault_delay(axis, "flash_prefill")
    if straggler is not None:
        trace_ev.instant(
            tctx, R["straggle"],
            payload=jnp.where(me == straggler[0], straggler[1], 0))
        shmem.straggler_delay(axis, straggler[0], straggler[1])

    # LL-AG producer with exposed per-segment delivery semaphores: our
    # shard flies to every peer while we fold the local block — the
    # copy-engine AG of the reference, with slot [t, i-1] counting
    # exactly segment-offset i's K (t=0) / V (t=1) arrival.
    handles = segment_collect_start(
        lambda t_i, i: (kbuf, vbuf)[t_i].at[i - 1],
        (k_ref, v_ref), send_sem,
        lambda t_i, i: seg_sems.at[t_i, i - 1], axis, n,
        on_send=lambda i: trace_ev.instant(tctx, R["fp.send"],
                                           payload=i),
    )

    qp_base = jax.lax.broadcasted_iota(jnp.int32, (s, 1), 0) + me * s
    slabs = {bi: _q_slabs(q_ref[bi], hq, d, scale) for bi in range(bsz)}
    states = {bi: _init_states(hq, s, d) for bi in range(bsz)}

    def fold_segment(seg_base, k_at, v_at):
        """Stream one segment's (bi, page) blocks double-buffered and
        fold each into every head state of its batch row."""
        pairs = [(bi, j) for bi in range(bsz) for j in range(nblk)]

        def start(idx, slot):
            bi, j = pairs[idx]
            for which, at in ((0, k_at), (1, v_at)):
                pltpu.make_async_copy(
                    at(bi, pl.ds(j * blk, blk)), vkv.at[slot, which],
                    sems.at[slot],
                ).start()

        def wait(slot):
            for which, at in ((0, k_at), (1, v_at)):
                pltpu.make_async_copy(
                    at(0, pl.ds(0, blk)), vkv.at[slot, which],
                    sems.at[slot],
                ).wait()

        start(0, 0)
        for idx, (bi, j) in enumerate(pairs):
            if idx + 1 < len(pairs):
                start(idx + 1, (idx + 1) % 2)
            wait(idx % 2)
            kv = vkv[idx % 2].astype(jnp.float32)
            live = _block_live(s, blk, seg_base + j * blk, qp_base,
                               len_ref[bi], causal)
            states[bi] = _fold_block_heads(slabs[bi], kv[0], kv[1], live,
                                           states[bi], hkv, g, d)

    # step 0: the rank-offset swizzle — fold the LOCAL block while the
    # segment puts are in flight (zero-wait start)
    with trace_ev.span(tctx, R["fp.fold"], payload=0):
        fold_segment(me * s,
                     lambda bi, ds: k_ref.at[bi, ds],
                     lambda bi, ds: v_ref.at[bi, ds])
    for i in range(1, n):
        # gate on exactly THIS segment's delivery (K then V — same slot
        # pair every rank's descriptor names for offset i), while
        # segments i+1.. are still in flight. Under a guard build each
        # gate is a bounded watchdog wait at site "recv" (slot = the
        # segment offset) — a dropped delivery becomes a guard row,
        # never a hang.
        _guard.set_progress(i, ctx=gctx)
        with trace_ev.span(tctx, R["fp.wait"], payload=i):
            with _guard.attached(gctx):
                for h in handles[i]:
                    h.wait_recv(slot=i)
        chunk = jax.lax.rem(me - i + n, n)
        with trace_ev.span(tctx, R["fp.fold"], payload=i):
            fold_segment(chunk * s,
                         lambda bi, ds, i=i: kbuf.at[i - 1, bi, ds],
                         lambda bi, ds, i=i: vbuf.at[i - 1, bi, ds])
    # drain outbound sends (semaphore balance: re-entrancy)
    for i in range(1, n):
        for h in handles[i]:
            h.wait_send()

    for bi in range(bsz):
        o_ref[bi] = _finalize(states[bi]).astype(o_ref.dtype)


def sp_flash_prefill(
    q: jax.Array,  # (B, S_loc, Hq, D)
    k: jax.Array,  # (B, S_loc, Hkv, D)
    v: jax.Array,
    axis: str = SP_AXIS,
    causal: bool = True,
    scale: Optional[float] = None,
    kv_len: Optional[jax.Array] = None,  # (B,) GLOBAL valid length
    block: Optional[int] = None,
    straggler=None,
):
    """SP flash prefill; per-device inside shard_map. Same contract as
    `sp_attention.ring_attention` (rank r holds Q rows and KV rows
    [r*S_loc, (r+1)*S_loc); returns (B, S_loc, Hq, D) attended over the
    full sharded sequence), but the KV exchange is the in-kernel
    per-segment-semaphore protocol instead of `lax.ppermute`.

    straggler: optional (rank, nanos) skew injection (stress/trace
    tests). Tracing (trace.building active): returns (out, trace_buf)
    on every path — fallbacks hand back an empty stream."""
    n = jax.lax.axis_size(axis)
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    w = hkv * d
    scale = float(scale if scale is not None else d ** -0.5)
    build = trace_ev.active_build()
    gbuild = _guard.active_build()
    straggler = _fplan.scheduled_straggler("flash_prefill", straggler)
    # segments cannot pad (padding would shift global KV positions), so
    # a requested block is re-fitted to the divisor rule (fit_block) —
    # the same rule the autotuner's pruner models and flash_prefill_ref
    # replays. Fitted BEFORE the n==1 dispatch: the world=1 path must
    # fold the same page granularity as the replay
    # (flash_prefill_local would otherwise pad a non-dividing block)
    blk = fit_block(s, block)
    assert s % blk == 0, f"block {blk} must divide S_loc {s}"
    if n == 1:
        out = flash_prefill_local(q, k, v, kv_len=kv_len, causal=causal,
                                  scale=scale, block=blk)
        return _guard.with_guard(gbuild, trace_ev.with_trace(build, out))
    if interpret_no_headroom():
        from triton_dist_tpu.kernels.sp_attention import ring_attention

        return _guard.with_guard(gbuild, trace_ev.with_trace(
            build, ring_attention(
                q, k, v, axis, causal=causal, scale=scale,
                kv_len=kv_len)))
    len_arr = (jnp.full((b,), n * s, jnp.int32) if kv_len is None
               else jnp.reshape(kv_len, (-1,)).astype(jnp.int32))
    itemsize = jnp.dtype(k.dtype).itemsize
    k2 = k.reshape(b, s, w)
    v2 = v.reshape(b, s, w)
    out_shape = (
        jax.ShapeDtypeStruct((b, s, hq * d), q.dtype),
        jax.ShapeDtypeStruct((n - 1, b, s, w), k.dtype),  # gather bufs
        jax.ShapeDtypeStruct((n - 1, b, s, w), v.dtype),
    )
    out_specs = (
        pl.BlockSpec(memory_space=pltpu.VMEM),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    )
    scratch = [
        pltpu.VMEM((2, 2, blk, w), k.dtype),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA,
        pltpu.SemaphoreType.DMA((2, n - 1)),
    ]
    if build is not None:
        out_shape += (trace_ev.out_shape(build),)
        out_specs += (trace_ev.out_spec(),)
        scratch.append(trace_ev.cursor_scratch())
    if gbuild is not None:
        out_shape += (_guard.out_shape(gbuild),)
        out_specs += (_guard.out_spec(),)
        scratch.append(_guard.cursor_scratch())
    res = tpu_call(
        functools.partial(_fp_sp_kernel, axis, n, b, s, hq, hkv, d, blk,
                          causal, scale, straggler, build, gbuild),
        out_shape=out_shape,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=out_specs,
        scratch_shapes=scratch,
        compiler_params=compiler_params(
            has_side_effects=True,
            collective_id=next_collective_id(f"flash_prefill_{axis}"),
            vmem_limit_bytes=flash_prefill_vmem_bytes(
                s, hq, hkv, d, blk, k.dtype, batch=b, q_rows=s)
            + VMEM_MARGIN,
        ),
        cost_estimate=cost_estimate(
            flops=4 * b * s * hq * n * s * d,
            bytes_accessed=2 * b * n * s * w * itemsize,
            remote_bytes=2 * b * (n - 1) * s * w * itemsize,
        ),
    )(len_arr, q.reshape(b, s, hq * d), k2, v2)
    out = res[0].reshape(b, s, hq, d)
    k_res = 3
    tbuf = res[k_res] if build is not None else None
    k_res += 1 if build is not None else 0
    gbuf = res[k_res] if gbuild is not None else None
    return _guard.with_guard(
        gbuild, trace_ev.with_trace(build, out, tbuf), gbuf)


def flash_prefill_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis: str = SP_AXIS,
    causal: bool = True,
    scale: Optional[float] = None,
    kv_len: Optional[jax.Array] = None,
    block: Optional[int] = None,
    window: Optional[int] = None,
    kv_from: Optional[jax.Array] = None,
) -> jax.Array:
    """Plain-transport replay of sp_flash_prefill: XLA all_gathers the
    KV shards, then folds segments in the SAME swizzle order through the
    SAME per-block `_head_update` op sequence. The overlapped kernel
    must be BIT-IDENTICAL to this — the per-segment semaphore protocol
    moves bytes, never bits (tests/test_flash_prefill.py pins it)."""
    n = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    w = hkv * d
    scale = float(scale if scale is not None else d ** -0.5)
    # same divisor re-fit as sp_flash_prefill — the replay must fold at
    # exactly the kernel's page granularity to stay bit-identical
    blk = fit_block(s, block)
    len_arr = (jnp.full((b,), n * s, jnp.int32) if kv_len is None
               else jnp.reshape(kv_len, (-1,)).astype(jnp.int32))
    k_full = jax.lax.all_gather(k, axis)  # (n, B, S, Hkv, D)
    v_full = jax.lax.all_gather(v, axis)
    nblk = s // blk
    outs = []
    for bi in range(b):
        slabs = _q_slabs(q[bi].reshape(s, hq * d), hq, d, scale)
        qp_col = jax.lax.broadcasted_iota(jnp.int32, (s, 1), 0) + me * s
        states = _init_states(hq, s, d)
        for i in range(n):
            chunk = jax.lax.rem(me - i + n, n)
            kseg = jax.lax.dynamic_index_in_dim(
                k_full, chunk, 0, keepdims=False)[bi].reshape(s, w)
            vseg = jax.lax.dynamic_index_in_dim(
                v_full, chunk, 0, keepdims=False)[bi].reshape(s, w)
            for j in range(nblk):
                kpage = kseg[j * blk:(j + 1) * blk].astype(jnp.float32)
                vpage = vseg[j * blk:(j + 1) * blk].astype(jnp.float32)
                live = _block_live(
                    s, blk, chunk * s + j * blk, qp_col, len_arr[bi], causal,
                    window, None if window is None else (
                        0 if kv_from is None else kv_from[bi]))
                states = _fold_block_heads(slabs, kpage, vpage, live,
                                           states, hkv, g, d)
        outs.append(_finalize(states))
    return jnp.stack(outs).reshape(b, s, hq, d).astype(q.dtype)


# -- the autotuner-selectable switch -----------------------------------------


def sp_prefill_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis: str = SP_AXIS,
    causal: bool = True,
    scale: Optional[float] = None,
    kv_len: Optional[jax.Array] = None,
    impl: str = "auto",
    config: Optional[FlashPrefillConfig] = None,
):
    """SP prefill with impl selection: "flash" (this module's
    per-segment-semaphore kernel), "ring" (`sp_attention.ring_attention`,
    the XLA-overlap fallback — always available), or "auto" (the
    perf-model pick, `perf_model.choose_sp_prefill_impl`, gated on
    native-TPU shape support). The layers' blockwise prefill and the
    serve prefill-chunk path ride the same switch through
    `layers.attention.gqa_attention`."""
    from triton_dist_tpu.kernels.sp_attention import ring_attention

    b, s, hq, d = q.shape
    hkv = k.shape[2]
    if impl == "auto":
        # same gate pair as the local auto path (_route_prefill_impl):
        # native shape support AND VMEM feasibility — the SP kernel
        # keeps EVERY batch row's state resident (batch=b), and the
        # ring fallback handles what does not fit
        if not (flash_prefill_native_ok(hq, hkv, d)
                and flash_prefill_fits(
                    s, s, hq, hkv, d,
                    block=config.block if config else None,
                    dtype=k.dtype, batch=b, q_rows=s)):
            impl = "ring"
        else:
            from triton_dist_tpu.perf_model import choose_sp_prefill_impl

            n = jax.lax.axis_size(axis)
            impl = choose_sp_prefill_impl(s, n, hq, hkv, d, batch=b,
                                          dtype=k.dtype)
    if impl == "flash":
        return sp_flash_prefill(
            q, k, v, axis, causal=causal, scale=scale, kv_len=kv_len,
            block=config.block if config else None)
    if impl == "ring":
        out = ring_attention(q, k, v, axis, causal=causal, scale=scale,
                             kv_len=kv_len)
        return _guard.with_guard(
            _guard.active_build(),
            trace_ev.with_trace(trace_ev.active_build(), out))
    raise ValueError(f"unknown sp prefill impl {impl!r}")


# -- protocol model (static verifier, triton_dist_tpu.verify) ----------------

from triton_dist_tpu import verify as _v  # noqa: E402


@_v.protocol("flash_prefill",
             doc="SP flash prefill ingest: full-mesh segment push with "
                 "per-(tensor, offset) delivery slots; consumer folds "
                 "local at step 0 then gates each remaining segment on "
                 "exactly its own slot pair (_fp_sp_kernel)")
def _fp_protocol(n):
    """The producer is the SAME segment_collect_start the kernel calls
    (protocol and kernel evolve together); the consumer contract is the
    swizzle-order read sequence: local shard first (no wait — the
    zero-wait start), then segment offset i's gather slots strictly
    after BOTH its K and V delivery waits. Outbound sends drain at the
    end (semaphore balance = re-entrancy)."""
    k, v = _v.ref("k"), _v.ref("v")
    kbuf, vbuf = _v.ref("kbuf"), _v.ref("vbuf")
    send = _v.sem("send_sem")
    seg = _v.sem("seg_sems")
    shmem.barrier_all(SP_AXIS)
    handles = segment_collect_start(
        lambda t_i, i: (kbuf, vbuf)[t_i].at(i - 1),
        (k.at(), v.at()), send.at(),
        lambda t_i, i: seg.at(t_i, i - 1), SP_AXIS, n,
    )
    _v.read(k.at())  # zero-wait local fold
    _v.read(v.at())
    for i in range(1, n):
        for h in handles[i]:
            h.wait_recv()
        _v.read(kbuf.at(i - 1))  # fold segment offset i
        _v.read(vbuf.at(i - 1))
    for i in range(1, n):
        for h in handles[i]:
            h.wait_send()


# -- conformance runner (verify.conform) --------------------------------------

from jax.sharding import PartitionSpec as _P  # noqa: E402

from triton_dist_tpu.verify import conform as _conform  # noqa: E402


@_conform.conforms(
    "flash_prefill",
    grids=((4, {}),),
    doc="ring-rotated KV flash prefill on the interpret mesh")
def _fp_conform(n):
    mesh = _conform.team_mesh(n, (SP_AXIS,))
    if isinstance(mesh, _conform.Skip):
        return mesh
    q = jnp.ones((1, 8, 1, 128), jnp.float32)
    k = jnp.ones((1, 8, 1, 128), jnp.float32)
    v = jnp.ones((1, 8, 1, 128), jnp.float32)
    return _conform.collect_streams(
        mesh, SP_AXIS,
        lambda q_, k_, v_: sp_flash_prefill(q_, k_, v_, SP_AXIS),
        in_specs=(_P(), _P(), _P()), args=(q, k, v))
