"""The held experts' grouped-matmul kernel (`kernels/grouped_gemm.py`
`_moe_gmm_kernel`), in the interpreter at small 128-aligned sizes.

On the chip `grouped_gemm` IS this kernel; under the interpreter its
route gives `lax.ragged_dot`, so these tests call `moe_gmm` itself (or
steer the route, never the interpreter) and hold it to
`grouped_gemm_ref` on the rows of the groups. The rows behind the
groups are nobody's: what the kernel leaves there is poisoned on
purpose where a caller's masking is under test.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import triton_dist_tpu.kernels  # noqa: F401 — the name below is shadowed
from triton_dist_tpu.kernels.grouped_gemm import (
    TILE_ROWS,
    grouped_gemm,
    grouped_gemm_ref,
    grouped_gemm_route,
    grouped_gemm_tile_rows,
    moe_gmm,
    tile_visits,
)
from triton_dist_tpu.lang import core
from triton_dist_tpu.layers import held_moe
from triton_dist_tpu.layers.held_moe import (
    HeldMoEParams,
    held_moe_counted,
    held_moe_fwd,
)

# `kernels/__init__` exports the function under the module's name
gg = sys.modules["triton_dist_tpu.kernels.grouped_gemm"]

T, K, N = 512, 256, 128  # four row tiles

# group sizes a pattern; LAYER marks the stack of three layers' groups
# of which one layer's alone are not empty, its index traced
PATTERNS = {
    "all_groups_empty": [0, 0, 0, 0, 0, 0],
    "one_group_holds_every_row": [0, 0, T, 0, 0, 0],
    "a_group_crosses_a_tile_boundary": [100, 60, 0, 0, 0, 0],
    "groups_of_1_7_33_between_empty_ones": [0, 1, 0, 0, 7, 33],
    "rows_behind_the_groups": [3, 0, 5, 0, 0, 2],
    "a_group_over_three_tiles": [120, 300, 0, 10, 0, 0],
    "one_layer_of_a_stack": "LAYER",
}


def _operands(groups, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((T, K)), dtype)
    w = jnp.asarray(rng.standard_normal((groups, K, N)) * 0.1, dtype)
    return x, w


@pytest.fixture(params=["whole_k", "two_k_tiles"])
def k_tiles(request, monkeypatch):
    """The weight block a grid step streams: an expert's whole (K, N),
    or half of its rows (the accumulation over K tiles)."""
    if request.param == "two_k_tiles":
        monkeypatch.setattr(gg, "WEIGHT_BLOCK_BYTES", 128 * N * 4)
    return request.param


@pytest.mark.parametrize("pattern", list(PATTERNS))
def test_kernel_is_the_reference_on_the_groups_rows(pattern, k_tiles):
    sizes = PATTERNS[pattern]
    if sizes == "LAYER":
        layers, held, layer = 3, 4, 1
        own = jnp.asarray([40, 0, 90, 5], jnp.int32)
        x, w = _operands(layers * held)

        @jax.jit
        def run(x, w, own, layer):
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((layers * held,), jnp.int32), own,
                (layer * held,))
            return moe_gmm(x, w, sizes), sizes

        got, sizes = run(x, w, own, jnp.int32(layer))
        assert int(sizes[layer * held]) == 40
    else:
        x, w = _operands(len(sizes))
        sizes = jnp.asarray(sizes, jnp.int32)
        got = jax.jit(moe_gmm)(x, w, sizes)
    m = int(sizes.sum())
    want = grouped_gemm_ref(x, w, sizes)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got[:m]), np.asarray(want[:m]),
                               atol=2e-5, rtol=0)
    # a visited tile's rows behind the groups are zeroed by its first
    # visit; a tile nobody visits holds whatever the buffer held
    upto = -(-m // TILE_ROWS) * TILE_ROWS
    assert not np.asarray(got[m:upto]).any()


@pytest.mark.parametrize("seed", range(4))
def test_tile_visits_is_every_group_s_every_tile_once_in_order(seed):
    rng = np.random.default_rng(seed)
    g, t = 12, 1024
    sizes = rng.integers(0, 200, g) * (rng.random(g) < 0.6)
    sizes[rng.integers(g)] += 1  # never all empty
    sizes = (sizes * min(1.0, t / sizes.sum())).astype(np.int32)
    tile, group, lo, hi, n = (np.asarray(a) for a in tile_visits(
        jnp.asarray(sizes), t))
    assert tile.shape == (t // TILE_ROWS + g - 1,)
    want, start = [], 0
    for i, size in enumerate(sizes):
        if size:
            want += [(r, i, start, start + size) for r in range(
                start // TILE_ROWS, (start + size - 1) // TILE_ROWS + 1)]
        start += size
    assert int(n) == len(want)
    assert list(zip(tile[:n], group[:n], lo[:n], hi[:n])) == want
    assert (np.diff(tile[:n]) >= 0).all()  # a tile's visits are consecutive


def test_a_row_s_result_is_bitwise_the_same_whatever_else_rides(k_tiles):
    """The tiles and the order of the K-accumulation come from the
    shapes: the same rows of the same expert give the same bits at
    another offset, in another tile, among other groups."""
    x, w = _operands(4, jnp.bfloat16, seed=1)
    rows = x[:20]
    results = []
    for sizes, at in (([5, 20, 0, 3], 5), ([150, 20, 170, 60], 150),
                      ([0, 20, 0, 0], 0), ([250, 20, 1, 0], 250)):
        moved = x.at[at:at + 20].set(rows)
        y = jax.jit(moe_gmm)(moved, w, jnp.asarray(sizes, jnp.int32))
        results.append(np.asarray(y[at:at + 20].astype(jnp.float32)))
    for other in results[1:]:
        np.testing.assert_array_equal(results[0], other)


@pytest.mark.parametrize("layer", [None, 1])
def test_held_moe_reads_nothing_behind_the_groups(monkeypatch, layer):
    """`held_moe_fwd` over the kernel with every row behind the groups
    poisoned is finite and the `ragged_dot` route's result, with one
    layer's experts and with all layers' stacks and a traced layer."""
    rng = np.random.default_rng(2)
    m, h, inter, e, held, top_k, off = 64, 128, 128, 8, 4, 2, 2
    layers = 3

    def arr(*shape, scale=0.2):
        return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)

    stack = () if layer is None else (layers,)
    p = HeldMoEParams(arr(h, e), arr(*stack, held, h, 2 * inter),
                      arr(*stack, held, inter, h), arr(h, 2 * inter),
                      arr(inter, h), arr(h))
    x = arr(m, h, scale=1.0)
    valid = jnp.arange(m) < 50

    def fwd(x, layer):
        return held_moe_fwd(x, valid, p, top_k, off, layer=layer)

    at = None if layer is None else jnp.int32(layer)
    assert grouped_gemm_route(m * top_k, h, 2 * inter) == "xla"
    want, here, absent = jax.jit(fwd)(x, at)
    assert 0 < int(here) < m * top_k and int(here) + int(absent) == 100

    def poisoned(x_sorted, w_stack, sizes, out_dtype=None):
        y = moe_gmm(x_sorted, w_stack, sizes, out_dtype)
        behind = jnp.arange(y.shape[0]) >= jnp.sum(sizes)
        return jnp.where(behind[:, None], jnp.nan, y)

    monkeypatch.setattr(held_moe, "grouped_gemm", poisoned)
    got, here_k, absent_k = jax.jit(fwd)(x, at)
    assert np.isfinite(np.asarray(got)).all()
    assert (int(here_k), int(absent_k)) == (int(here), int(absent))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=0)


def test_route_is_the_backend_and_the_shapes(monkeypatch):
    """`ragged_dot` under the interpreter and for whatever the kernel
    does not tile; the kernel on the chip for whole tiles of
    lane-multiple widths. Nothing else decides."""
    assert core.use_interpret()
    assert grouped_gemm_route(8192, 2048, 1024) == "xla"
    x, w = _operands(3)
    sizes = jnp.asarray([10, 0, 20], jnp.int32)
    calls = core.pallas_call_count()
    np.testing.assert_allclose(
        np.asarray(grouped_gemm(x, w, sizes)[:30]),
        np.asarray(grouped_gemm_ref(x, w, sizes)[:30]), atol=2e-5, rtol=0)
    assert core.pallas_call_count() == calls  # no kernel was built
    assert int(grouped_gemm_tile_rows(x, w, sizes)) == 0

    monkeypatch.setattr(core, "backend_platform", lambda: "tpu")
    for k, n in ((2048, 1024), (512, 2048), (2304, 2048), (1024, 2304),
                 (6144, 4096), (2048, 6144)):
        assert grouped_gemm_route(8192, k, n) == "pallas"
    for t, k, n in ((8192, 2048, 1000), (8192, 96, 1024), (8192, 64, 64),
                    (100, 2048, 1024)):
        assert grouped_gemm_route(t, k, n) == "xla"
    # the rows of the tiles the kernel would visit: 10 rows in tile 0,
    # 20 more in tile 0
    assert int(grouped_gemm_tile_rows(x, w, sizes)) == 2 * TILE_ROWS


def test_held_moe_counts_the_rows_of_the_tiles_it_visits(monkeypatch):
    """`held_moe_counted`'s fourth result: two products a block, a
    visit a (group, tile) each, TILE_ROWS rows a visit; none on the
    `ragged_dot` route. The route is steered, the interpreter is not:
    the kernel itself runs."""
    rng = np.random.default_rng(3)
    m, h, inter, e, held, top_k = 64, 128, 128, 4, 4, 2

    def arr(*shape, scale=0.2):
        return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)

    p = HeldMoEParams(arr(h, e), arr(held, h, 2 * inter),
                      arr(held, inter, h), arr(h, 2 * inter), arr(inter, h))
    x, valid = arr(m, h, scale=1.0), jnp.ones((m,), bool)
    want, here, _, rows, *_ = held_moe_counted(x, valid, p, top_k, 0)
    assert int(here) == m * top_k and int(rows) == 0
    monkeypatch.setattr(gg, "use_interpret", lambda: False)
    kernel, calls = gg.moe_gmm, []
    monkeypatch.setattr(gg, "moe_gmm", lambda *a: (
        calls.append(a[1].shape), kernel(*a))[1])
    got, here, _, rows, *_ = held_moe_counted(x, valid, p, top_k, 0)
    assert calls == [(held, h, 2 * inter), (held, inter, h)]
    # 128 pairs fill one tile, which each of the four groups visits
    assert int(rows) == 2 * held * TILE_ROWS
    assert 2 * int(here) / int(rows) == 1 / held
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=0)


def test_chip_smoke_experts_phase_rehearsal(monkeypatch, capsys):
    """`chip_smoke.experts_phase`'s own control flow at a tiny size
    (the routes are `ragged_dot` and the jnp combine here; the chip run
    takes the kernels and requires them by name): both products, the
    cell's load and a deployment's, each with the shifted offset seen;
    the combine at a third of the rows valid and at all, each with the
    swapped pairs seen."""
    import chip_smoke

    monkeypatch.setattr(chip_smoke, "EXPERT_CELLS",
                        (("tiny_next", 4, 3), ("tiny_exaone", 4, 5)))
    monkeypatch.setattr(chip_smoke, "COMBINE_CELL", (128, 4, 8, 4, 64))
    monkeypatch.setattr(chip_smoke, "COMBINE_VALID", (22, 64))
    chip_smoke.experts_phase(7, rows=256)
    out = capsys.readouterr().out
    assert out.count("route 'xla', kernels none") == 5
    assert out.count("one offset shifted") == 8
    assert out.count("values not the fold's bits") == 2
    assert out.count("two held pairs swapped") == 2
