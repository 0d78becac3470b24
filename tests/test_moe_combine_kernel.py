"""The held experts' combine kernel (`kernels/moe_combine.py`
`_moe_combine_kernel`), in the interpreter at reduced token counts.

On the chip `moe_combine` IS this kernel; under the interpreter its
route gives the jnp un-sort, so these tests steer the route (never the
interpreter) and hold the kernel to a left fold in choice order written
here in numpy, bit for bit, and to the jnp form within float32
rounding. The rows of `y` behind `n_here` are nobody's: they are NaN
on purpose.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.kernels.moe_combine import (
    combine_fold,
    moe_combine,
    moe_combine_route,
)
from triton_dist_tpu.lang import core
from triton_dist_tpu.layers.held_moe import HeldMoEParams, held_moe_counted

mc = sys.modules["triton_dist_tpu.kernels.moe_combine"]

# (H, top_k) of the four expert configurations, at the fewest tokens
# that fill whole staging chunks: q3n, kl48b, kx236b, g4hs
CONFIGS = {"qwen3_next_80b": (2048, 10), "kimi_linear_48b": (2304, 8),
           "k_exaone_236b": (6144, 8), "granite_4_h_small": (4096, 10)}


@pytest.fixture
def kernel_route(monkeypatch):
    """`moe_combine` takes the kernel's route; the kernel itself runs
    in the interpreter."""
    monkeypatch.setattr(mc, "use_interpret", lambda: False)


def _operands(m, k, h, share=0.3, seed=0, absent_weight=0.0):
    """y with its rows behind n_here NaN, a permutation of the pairs
    over the rows, weights in (0, 1] on the real pairs."""
    rng = np.random.default_rng(seed)
    n = int(share * m * k)
    y = rng.standard_normal((m * k, h)).astype(np.float32)
    y[n:] = np.nan
    idx = rng.permutation(m * k).astype(np.int32)
    w = rng.uniform(0.01, 1.0, (m, k)).astype(np.float32)
    w = np.where(idx.reshape(m, k) < n, w, absent_weight).astype(np.float32)
    return (jnp.asarray(y, jnp.bfloat16), jnp.asarray(idx), jnp.int32(n),
            jnp.asarray(w))


def _left_fold(y, idx, n, w):
    """out[t] = (((0 + w[t,0] y[p0]) + w[t,1] y[p1]) + ...), the choices
    in order, a pair at or past n left out; each product rounded once
    to float32 (exact in float64), each sum in float32."""
    m, k = w.shape
    y = np.asarray(y.astype(jnp.float32), np.float64)
    idx = np.asarray(idx).reshape(m, k)
    w = np.asarray(w, np.float64)
    out = np.zeros((m, y.shape[1]), np.float32)
    for j in range(k):
        real = idx[:, j] < int(n)
        term = (w[:, j, None] * y[np.where(real, idx[:, j], 0)]).astype(
            np.float32)
        out[real] = out[real] + term[real]
    return out


@pytest.mark.parametrize("config", list(CONFIGS))
def test_kernel_is_the_left_fold_in_choice_order(config, kernel_route,
                                                 monkeypatch):
    """Each expert configuration's (H, top_k): bitwise the fold, within
    float32 rounding the jnp un-sort and einsum of today, and it read
    the real rows alone."""
    h, k = CONFIGS[config]
    m = 64 if k == 10 else 32  # m k whole 128-row chunks
    assert moe_combine_route(m, k, h) == "pallas"
    y, idx, n, w = _operands(m, k, h)
    calls = core.pallas_call_count()
    got, rows = moe_combine(y, idx, n, w)
    assert core.pallas_call_count() > calls  # the kernel was built
    assert got.shape == (m, h) and got.dtype == jnp.float32
    assert int(rows) == int(n)
    np.testing.assert_array_equal(np.asarray(got), _left_fold(y, idx, n, w))
    # the jnp form, as the CPU runs it
    monkeypatch.setattr(mc, "use_interpret", lambda: True)
    want, rows_jnp = moe_combine(y, idx, n, w)
    assert int(rows_jnp) == m * k
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6, atol=2e-6)


def test_rows_behind_n_here_never_reach_the_output(kernel_route):
    """Every row at or past n_here is NaN and every pair there carries
    a weight of 1: the output is finite and the fold over the real
    pairs; so is the stage's overflow path."""
    m, k, h = 64, 10, 256
    y, idx, n, w = _operands(m, k, h, share=0.45, seed=1, absent_weight=1.0)
    want = _left_fold(y, idx, n, w)
    got, _ = moe_combine(y, idx, n, w)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), want)
    fold = jax.jit(combine_fold)(y, idx, n, w)
    np.testing.assert_array_equal(np.asarray(fold), want)


def test_a_row_s_result_is_bitwise_the_same_whatever_else_rides(
        kernel_route, monkeypatch):
    """Token 0's choices hold the same rows and weights in four steps:
    at other positions of y, among other tokens and other n_here, and
    in a step whose real rows overflow the stage (the jnp fold runs
    there, and the counter says every pair was read)."""
    m, k, h = 64, 10, 256
    rng = np.random.default_rng(2)
    own = rng.standard_normal((k, h)).astype(np.float32)
    weights = rng.uniform(0.01, 1.0, k).astype(np.float32)
    real = np.array([1, 0, 1, 1, 0, 1, 1, 1, 0, 1], bool)
    results = []
    for seed, share, stage_rows in ((3, 0.2, None), (4, 0.5, None),
                                    (5, 0.9, None), (6, 0.5, 128)):
        y, idx, n, w = (np.array(a) for a in _operands(
            m, k, h, share=share, seed=seed))
        n = int(n)
        # token 0's real choices on rows under n, the others past it
        free_real = [p for p in rng.permutation(n) if p not in idx[:k]]
        free_absent = [p for p in range(n, m * k) if p not in idx[:k]]
        for j in range(k):
            pos = free_real.pop() if real[j] else free_absent.pop()
            other = int(np.flatnonzero(idx == pos)[0])
            idx[other], idx[j] = idx[j], pos
            y[pos] = own[j] if real[j] else np.nan
        w = np.where(idx.reshape(m, k) < n, w, 0.0).astype(np.float32)
        w[0] = np.where(real, weights, 0.0)
        if stage_rows:
            monkeypatch.setattr(mc, "STAGE_BYTES", stage_rows * h * 4)
            assert n > stage_rows
        got, rows = moe_combine(jnp.asarray(y, jnp.bfloat16),
                                jnp.asarray(idx), jnp.int32(n),
                                jnp.asarray(w))
        assert int(rows) == (m * k if stage_rows else n)
        results.append(np.asarray(got[0]))
    for other in results[1:]:
        np.testing.assert_array_equal(results[0], other)


def test_route_is_the_backend_and_the_shapes(monkeypatch):
    """The jnp form under the interpreter; the kernel on the chip for
    a lane-multiple H, m k in whole staging chunks and m in whole
    sublane tiles. Nothing else decides."""
    assert core.use_interpret()
    for m, k, h in ((1024, 10, 4096), (1024, 8, 6144)):
        assert moe_combine_route(m, k, h) == "xla"
    y, idx, n, w = _operands(64, 2, 128)
    calls = core.pallas_call_count()
    _, rows = moe_combine(y, idx, n, w)
    assert core.pallas_call_count() == calls  # no kernel was built
    assert int(rows) == 128

    monkeypatch.setattr(core, "backend_platform", lambda: "tpu")
    for m, k, h in ((1024, 10, 2048), (1024, 8, 2304), (1024, 8, 6144),
                    (1024, 10, 4096), (64, 2, 128)):
        assert moe_combine_route(m, k, h) == "pallas"
    for m, k, h in ((1024, 10, 1000), (100, 8, 2048), (36, 32, 2048)):
        assert moe_combine_route(m, k, h) == "xla"
    # the token tile and the stage from H and the VMEM budgets alone
    assert [mc._token_tile(1024, h) for h in (2048, 2304, 4096, 6144)] == [
        256, 128, 128, 64]
    assert [mc._stage_rows(8192, h) for h in (2048, 2304, 4096, 6144)] == [
        8192, 7168, 4096, 2688]


def _expert_block(seed=3):
    rng = np.random.default_rng(seed)
    m, h, inter, held, top_k = 64, 128, 128, 4, 2

    def arr(*shape, scale=0.2):
        return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)

    p = HeldMoEParams(arr(h, 8), arr(held, h, 2 * inter),
                      arr(held, inter, h), arr(h, 2 * inter), arr(inter, h))
    x, valid = arr(m, h, scale=1.0), jnp.arange(m) < 50
    return x, valid, p, top_k


def test_held_moe_counts_the_rows_its_combine_read(monkeypatch):
    """`held_moe_counted`'s last two results: on the jnp route every
    pair's row is read, m x top_k both; on the kernel's, the held
    pairs' rows alone, and the same output within float32 rounding."""
    x, valid, p, top_k = _expert_block()
    pairs = x.shape[0] * top_k
    want, here, _, _, rows, counted = held_moe_counted(x, valid, p, top_k, 4)
    assert int(rows) == int(counted) == pairs
    assert 0 < int(here) < pairs
    monkeypatch.setattr(mc, "use_interpret", lambda: False)
    calls = core.pallas_call_count()
    got, here_k, _, _, rows, counted = held_moe_counted(
        x, valid, p, top_k, 4)
    assert core.pallas_call_count() > calls
    assert (int(rows), int(counted)) == (int(here), pairs)
    assert int(here_k) == int(here)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6, atol=2e-6)
