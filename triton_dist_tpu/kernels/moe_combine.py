"""The held experts' combine: each token's weighted sum of its rows of
the second grouped matmul, in the order of its choices.

`layers.held_moe` multiplies the (token, choice) pairs sorted by
expert, so a token's rows lie scattered over the sorted product `y`
(`unsort_idx[t k + j]` is the row of token t's choice j), and only the
first `n_here` rows are real: the pairs of a held expert. Every other
pair is absent or padding, its weight 0 and its row nobody's.

The jnp form (`combine_unsort`) un-sorts all `m k` rows, real or not,
widens them to float32 and reduces them with the weights. On the chip
the Pallas kernel `_moe_combine_kernel` reads each real row once and
skips the rest: its first grid step copies rows `[0, n_here)` from HBM
in whole chunks into a float32 stage in VMEM, and each step then sums
its tokens' staged rows by index, `acc += w[t, j] * row` for j = 0 ..
k-1 where the row is real. Why a stage: a single row of a bfloat16
array is neither a unit a DMA can move out of HBM (its tiles hold
eight or sixteen rows) nor one a load can take from VMEM at a dynamic
offset; a float32 row of VMEM is.

The sum is float32 over rows read in their stored dtype, a token's
terms added in choice order, so a row's result depends on nothing else
in the step, bit for bit. A term is `w_hi * row + w_lo * row`, the
weight split at bfloat16's precision (`_split`): each half's product
with a row in at most 16 bits is exact in float32, so the term is the
product rounded once whether the compiler fuses a multiply into an add
(the CPU's does) or not, and the sum's bits are the same on every
backend. A step whose real rows overflow the stage (`STAGE_BYTES`)
takes `combine_fold`, the same sum in jnp, which gives the same bits.

`moe_combine` decides the route from the backend and the shapes
(`moe_combine_route`), and returns the sum with the rows of `y` it
read: `n_here` where the kernel ran, `m k` where the jnp forms did.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.lang.core import (
    compiler_params,
    compute_vmem_bytes,
    cost_estimate,
    tpu_call,
    use_interpret,
)

# Rows of `y` one staging DMA moves: whole HBM tiles in every dtype.
CHUNK_ROWS = 128
# VMEM for the float32 stage. 64 MiB holds 4,096 rows of 4,096: with ten
# choices a token, half of them held, a 1,024-row step overflows it only
# where more than four fifths of its rows are valid.
STAGE_BYTES = 64 << 20
# VMEM for one block of the float32 output, which the pipeline doubles.
OUT_BLOCK_BYTES = 2 << 20
VMEM_MARGIN = 2 << 20


def moe_combine_route(m: int, k: int, h: int) -> str:
    """"pallas" where `_moe_combine_kernel` runs: on the chip, for a
    lane-multiple H, whole staging chunks of the m k rows and tokens in
    whole sublane tiles; "xla" (the jnp form) under the interpreter and
    for every other shape."""
    if use_interpret() or h % 128 or (m * k) % CHUNK_ROWS or m % 8:
        return "xla"
    return "pallas"


def _token_tile(m: int, h: int) -> int:
    """Tokens a grid step: the most, a multiple of 8 dividing m, whose
    float32 rows fit OUT_BLOCK_BYTES."""
    tm = max(8, OUT_BLOCK_BYTES // (4 * h) // 8 * 8)
    while m % tm:
        tm -= 8
    return tm


def _stage_rows(pairs: int, h: int) -> int:
    """Rows of the float32 stage: whole chunks within STAGE_BYTES, and
    no more than the m k rows there are."""
    fit = STAGE_BYTES // (4 * h) // CHUNK_ROWS * CHUNK_ROWS
    return max(CHUNK_ROWS, min(fit, pairs))


def _split(weights):
    """weights as hi + lo, hi rounded to bfloat16's 8 significant bits
    and lo the rest (at most 16 more): both exact in float32."""
    hi = weights.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, weights - hi


def _moe_combine_kernel(k, stage_rows, n_ref, idx_ref, hi_ref, lo_ref,
                        y_hbm, o_ref, stage, raw, sem):
    """The first step stages rows [0, n) of `y` as float32, a chunk's
    DMA in flight while the one before is widened; every step sums its
    tokens' real rows from the stage, choice by choice."""
    n = n_ref[0]
    tm, h = o_ref.shape

    @pl.when(pl.program_id(0) == 0)
    def _stage():
        chunks = pl.cdiv(jnp.minimum(n, stage_rows), CHUNK_ROWS)

        def copy(c, slot):
            rows = pl.ds(pl.multiple_of(c * CHUNK_ROWS, CHUNK_ROWS),
                         CHUNK_ROWS)
            return pltpu.make_async_copy(y_hbm.at[rows], raw.at[slot],
                                         sem.at[slot])

        @pl.when(chunks > 0)
        def _first():
            copy(0, 0).start()

        def widen(c, carry):
            slot = c % 2

            @pl.when(c + 1 < chunks)
            def _next():
                copy(c + 1, 1 - slot).start()

            copy(c, slot).wait()
            rows = pl.ds(pl.multiple_of(c * CHUNK_ROWS, CHUNK_ROWS),
                         CHUNK_ROWS)
            stage[rows, :] = raw[slot].astype(jnp.float32)
            return carry

        jax.lax.fori_loop(0, chunks, widen, 0)

    # each token's sum builds in its own row of the output block: a
    # vector yielded by a branch (`lax.cond`) hung the v5e
    o_ref[...] = jnp.zeros_like(o_ref)
    first = pl.program_id(0) * tm * k

    def token(t, carry):
        out = pl.ds(t, 1)
        for j in range(k):
            at = first + t * k + j
            pos, hi, lo = idx_ref[at], hi_ref[at], lo_ref[at]

            @pl.when(pos < n)
            def _add(pos=pos, hi=hi, lo=lo):
                row = stage[pl.ds(pos, 1), :]
                o_ref[out, :] = o_ref[out, :] + (hi * row + lo * row)

        return carry

    jax.lax.fori_loop(0, tm, token, 0)


def combine_fold(y, unsort_idx, n_here, weights):
    """The kernel's sum in jnp: a left fold over the choices of
    `weights[:, j] * f32(y[unsort_idx[t k + j]])` (each term split as
    the kernel's), the rows at or past `n_here` taken as 0 (so NaN there
    never reaches the sum)."""
    m, k = weights.shape
    idx = unsort_idx.reshape(m, k)
    hi, lo = _split(weights)
    out = jnp.zeros((m, y.shape[1]), jnp.float32)
    for j in range(k):
        real = (idx[:, j] < n_here)[:, None]
        rows = jnp.where(real, y[idx[:, j]].astype(jnp.float32), 0.0)
        out = out + (hi[:, j:j + 1] * rows + lo[:, j:j + 1] * rows)
    return out


# jitted, so that the calls of one shape (every expert block of a
# step) are traced and lowered to Mosaic once, not once each
@functools.partial(jax.jit, static_argnums=(4, 5))
def _moe_combine(y, unsort_idx, n_here, weights, tm: int, stage_rows: int):
    m, k = weights.shape
    pairs, h = y.shape
    itemsize = y.dtype.itemsize
    n_here = jnp.asarray(n_here, jnp.int32)

    def kernel(y, unsort_idx, n_here, weights):
        return tpu_call(
            functools.partial(_moe_combine_kernel, k, stage_rows),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(m // tm,),
                in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                          pl.BlockSpec(memory_space=pltpu.SMEM),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((tm, h), lambda i, *_: (i, 0)),
                scratch_shapes=[pltpu.VMEM((stage_rows, h), jnp.float32),
                                pltpu.VMEM((2, CHUNK_ROWS, h), y.dtype),
                                pltpu.SemaphoreType.DMA((2,))],
            ),
            out_shape=jax.ShapeDtypeStruct((m, h), jnp.float32),
            compiler_params=compiler_params(
                # the stage persists from the first step to the last
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=compute_vmem_bytes(
                    ((stage_rows, h), jnp.float32),
                    ((2, CHUNK_ROWS, h), y.dtype),
                    ((2, tm, h), jnp.float32)) + VMEM_MARGIN,
            ),
            # at the most: every pair real
            cost_estimate=cost_estimate(
                flops=4 * pairs * h,
                bytes_accessed=pairs * (h * itemsize + 12) + m * h * 4,
            ),
        )(n_here.reshape(1), unsort_idx.astype(jnp.int32),
          *(w.reshape(-1) for w in _split(weights)), y)

    fits = n_here <= stage_rows
    out = jax.lax.cond(fits, kernel, combine_fold, y, unsort_idx, n_here,
                       weights)
    return out, jnp.where(fits, n_here, pairs).astype(jnp.int32)


def moe_combine(y: jax.Array, unsort_idx: jax.Array, n_here: jax.Array,
                weights: jax.Array):
    """y (m k, H), the second product's rows sorted by expert, its first
    `n_here` real; unsort_idx (m k,), the row of each (token, choice)
    pair; weights (m, k) float32, 0 where a pair is not real. Returns
    (out (m, H) float32, the sum of each token's weighted rows; the
    rows of `y` read, int32)."""
    m, k = weights.shape
    pairs, h = y.shape
    if moe_combine_route(m, k, h) == "pallas":
        return _moe_combine(y, unsort_idx, n_here, weights,
                            _token_tile(m, h), _stage_rows(pairs, h))
    return combine_unsort(y, unsort_idx, n_here, weights), jnp.int32(pairs)


def combine_unsort(y, unsort_idx, n_here, weights):
    """The jnp form: every pair's row masked behind `n_here`, un-sorted
    into (m, k, H), widened to float32 and reduced with the weights."""
    m, k = weights.shape
    y = jnp.where((jnp.arange(y.shape[0]) < n_here)[:, None], y,
                  jnp.zeros((), y.dtype))
    return jnp.einsum(
        "mkh,mk->mh",
        y[unsort_idx].reshape(m, k, -1).astype(jnp.float32), weights)
