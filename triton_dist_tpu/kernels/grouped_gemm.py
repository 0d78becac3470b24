"""Grouped GEMM over expert segments — the MoE matmul core.

TPU-native analog of the reference's grouped-GEMM consumers
(ref: python/triton_dist/kernels/nvidia/allgather_group_gemm.py:535
`consumer scatter-group-GEMM`; moe_reduce_rs.py:167-246). Like the
reference, the chip's route hand-tiles a kernel over sorted token
blocks with per-block expert ids: at the few rows an expert sees in a
serving step the product is a STREAM of expert weights, and
`_moe_gmm_kernel` reads each non-empty group's `(K, N)` weights once a
row tile that holds rows of it, at the memory's speed, where
`lax.ragged_dot` reached a third of it (PERF.md section 6, PR 39).

`grouped_gemm` decides the route from what it can see, the backend and
the shapes (`grouped_gemm_route`): the kernel on the chip, XLA's
`lax.ragged_dot` under the interpreter (the CPU mesh of the tests) and
for shapes the kernel does not tile. Rows behind the last group belong
to nobody: `ragged_dot` leaves zeros there, the kernel whatever the
buffer held — a caller reads the groups' rows alone.
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
    fit_tile,
    tpu_call,
    use_interpret,
)

# Rows of a tile. A visit multiplies TILE_ROWS rows by one expert's
# weights whatever share of them is the expert's own: at 128 the MXU's
# time stays under the stream's for every served shape (PERF.md
# section 6, PR 39: the sweep).
TILE_ROWS = 128
# Bytes of the weight block a grid step streams, (tk, N): tk is the
# most rows of an expert's (K, N) that fit, a lane multiple dividing K.
# From the shapes alone, so the order of a row's K-accumulation never
# depends on what else rides the step.
WEIGHT_BLOCK_BYTES = 4 << 20
VMEM_MARGIN = 2 << 20


def _k_tile(k: int, n: int, itemsize: int) -> int:
    """Rows of the streamed weight block: the largest lane multiple
    that divides K and keeps (tk, N) within WEIGHT_BLOCK_BYTES."""
    return fit_tile(max(WEIGHT_BLOCK_BYTES // (n * itemsize), 128), k)


def grouped_gemm_route(t: int, k: int, n: int) -> str:
    """"pallas" where `_moe_gmm_kernel` runs: on the chip, for whole
    row tiles and lane-multiple K and N; "xla" (`lax.ragged_dot`)
    under the interpreter and for every other shape."""
    if use_interpret() or t % TILE_ROWS or k % 128 or n % 128:
        return "xla"
    return "pallas"


def tile_visits(group_sizes: jax.Array, t: int):
    """The kernel's schedule from the group sizes, on the device: one
    VISIT a (non-empty group, row tile that holds rows of it), groups
    in order and a group's tiles in order, so the visits of one tile
    are consecutive. Returns (tile, group, lo, hi, n): a visit's row
    tile, its group, the group's first row and the row behind its
    last, each (tiles + groups - 1,), the most visits there can be,
    and `n`, how many of them are real. An empty group is no visit."""
    g = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // TILE_ROWS
    count = jnp.where(sizes > 0, (ends - 1) // TILE_ROWS - first + 1, 0)
    count_end = jnp.cumsum(count)
    v = jnp.arange(t // TILE_ROWS + g - 1, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.sum(v[:, None] >= count_end[None, :], axis=1, dtype=jnp.int32),
        g - 1)
    tile = first[group] + v - (count_end - count)[group]
    return tile, group, starts[group], ends[group], count_end[-1]


def _moe_gmm_kernel(tiles_k, tile_ref, group_ref, lo_ref, hi_ref,
                    x_ref, w_ref, o_ref, acc_ref):
    """One visit's (TILE_ROWS, tk) @ (tk, N), accumulated over the K
    tiles in float32; at the last, the visit's group's rows of the
    tile are stored, rounded once. A tile's first visit finds its
    buffer unwritten and zeroes the rows that are not its group's."""
    del group_ref  # the weights' index map reads it
    v, kk = pl.program_id(0), pl.program_id(1)

    @pl.when(kk == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(kk == tiles_k - 1)
    def _store():
        tile = tile_ref[v]
        rows = tile * TILE_ROWS + jax.lax.broadcasted_iota(
            jnp.int32, (TILE_ROWS, 1), 0)
        mine = (rows >= lo_ref[v]) & (rows < hi_ref[v])
        fresh = (v == 0) | (tile_ref[jnp.maximum(v - 1, 0)] != tile)
        kept = jnp.where(fresh, jnp.zeros(o_ref.shape, o_ref.dtype),
                         o_ref[...])
        o_ref[...] = jnp.where(mine, acc_ref[...].astype(o_ref.dtype), kept)


def moe_gmm(x_sorted: jax.Array, w_stack: jax.Array,
            group_sizes: jax.Array, out_dtype=None) -> jax.Array:
    """`grouped_gemm`'s contract through `_moe_gmm_kernel`, for ANY
    group sizes: the grid is the visits (`tile_visits`, scalar-
    prefetched) x the K tiles, so an empty group costs no step and no
    weight read, the rows behind the groups are visited by nobody, and
    the stack goes in as it lies in HBM. Operands as they come (bf16
    in the served models), float32 accumulation."""
    t, k = x_sorted.shape
    n = w_stack.shape[2]
    assert t % TILE_ROWS == 0 and k % 128 == 0 and n % 128 == 0, (t, k, n)
    return _moe_gmm(x_sorted.astype(w_stack.dtype), w_stack, group_sizes,
                    _k_tile(k, n, w_stack.dtype.itemsize),
                    jnp.dtype(out_dtype or x_sorted.dtype))


# jitted, so that a step's calls of one shape (every block of an
# unrolled model) are traced and lowered to Mosaic once, not once each
@functools.partial(jax.jit, static_argnums=(3, 4))
def _moe_gmm(x_sorted, w_stack, group_sizes, tk: int, out_dtype):
    t, k = x_sorted.shape
    g, _, n = w_stack.shape
    dtype, itemsize = w_stack.dtype, w_stack.dtype.itemsize
    tiles_k = k // tk
    *visits, n_visits = tile_visits(group_sizes, t)
    return tpu_call(
        functools.partial(_moe_gmm_kernel, tiles_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(visits),
            grid=(n_visits, tiles_k),
            in_specs=[
                pl.BlockSpec((TILE_ROWS, tk),
                             lambda v, kk, tile, *_: (tile[v], kk)),
                pl.BlockSpec((None, tk, n),
                             lambda v, kk, tile, group, *_: (group[v], kk, 0)),
            ],
            out_specs=pl.BlockSpec((TILE_ROWS, n),
                                   lambda v, kk, tile, *_: (tile[v], 0)),
            scratch_shapes=[pltpu.VMEM((TILE_ROWS, n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((t, n), out_dtype),
        compiler_params=compiler_params(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the blocks double-buffered, the accumulator once
            vmem_limit_bytes=2 * compute_vmem_bytes(
                ((tk, n), dtype), ((TILE_ROWS, tk), dtype),
                ((TILE_ROWS, n), out_dtype))
            + compute_vmem_bytes(((TILE_ROWS, n), jnp.float32))
            + VMEM_MARGIN,
        ),
        # at the most: every row real, no two of them of one group
        cost_estimate=cost_estimate(
            flops=2 * t * k * n,
            bytes_accessed=(min(g, t) * k * n + t * k) * itemsize
            + t * n * out_dtype.itemsize,
        ),
    )(*visits, x_sorted, w_stack)


def grouped_gemm(
    x_sorted: jax.Array,  # (T, K) tokens sorted by expert
    w_stack: jax.Array,  # (E, K, N) per-expert weights
    group_sizes: jax.Array,  # (E,) rows per expert
    out_dtype=None,
) -> jax.Array:
    """y[i] = x_sorted[i] @ w_stack[expert_of_segment(i)] -> (T, N);
    rows behind the last group are nobody's (module doc)."""
    out_dtype = out_dtype or x_sorted.dtype
    if grouped_gemm_route(*x_sorted.shape, w_stack.shape[2]) == "pallas":
        return moe_gmm(x_sorted, w_stack, group_sizes, out_dtype)
    y = jax.lax.ragged_dot(
        x_sorted, w_stack, group_sizes,
        preferred_element_type=jnp.float32,
    )
    return y.astype(out_dtype)


def grouped_gemm_tile_rows(x_sorted, w_stack, group_sizes) -> jax.Array:
    """Rows of the tiles ONE `grouped_gemm` of these operands visits
    (visits x TILE_ROWS, int32 on the device): the rows it multiplies,
    of which the groups' own are the real ones. 0 on the `ragged_dot`
    route, which has no tiles to count."""
    if grouped_gemm_route(*x_sorted.shape, w_stack.shape[2]) != "pallas":
        return jnp.zeros((), jnp.int32)
    return tile_visits(group_sizes, x_sorted.shape[0])[-1] * TILE_ROWS


def grouped_gemm_ref(x_sorted, w_stack, group_sizes, out_dtype=None):
    """Loop-over-experts reference (masked einsum; O(E) passes)."""
    out_dtype = out_dtype or x_sorted.dtype
    e = w_stack.shape[0]
    t = x_sorted.shape[0]
    starts = jnp.cumsum(group_sizes) - group_sizes
    rows = jnp.arange(t)[:, None]
    # membership mask (T, E)
    member = (rows >= starts[None, :]) & (
        rows < (starts + group_sizes)[None, :]
    )
    xf = x_sorted.astype(jnp.float32)
    acc = jnp.zeros((t, w_stack.shape[2]), jnp.float32)
    for ei in range(e):
        y = xf @ w_stack[ei].astype(jnp.float32)
        acc = jnp.where(member[:, ei:ei + 1], y, acc)
    return acc.astype(out_dtype)
