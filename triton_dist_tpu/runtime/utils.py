"""Perf measurement, rank-filtered printing, allclose with diff dump.

TPU-native analogs of the reference host utilities
(ref: python/triton_dist/utils.py:274-318 perf_func/dist_print,
:870-899 assert_allclose, :505-589 group_profile).
"""

from __future__ import annotations

import time
from typing import Callable, Tuple

import jax
import numpy as np


def dist_print(*args, prefix: bool = True, allowed_ranks="0", **kwargs):
    """Rank-filtered printing (ref: utils.py:289-318).

    allowed_ranks: comma string, list of ints, or "all".
    """
    r = jax.process_index()
    if allowed_ranks == "all":
        allowed = None
    elif isinstance(allowed_ranks, str):
        allowed = {int(x) for x in allowed_ranks.split(",") if x != ""}
    else:
        allowed = set(int(x) for x in allowed_ranks)
    if allowed is None or r in allowed:
        if prefix:
            print(f"[rank {r}]", *args, **kwargs)
        else:
            print(*args, **kwargs)


def perf_func(
    fn: Callable[[], jax.Array],
    iters: int = 10,
    warmup_iters: int = 3,
) -> Tuple[object, float]:
    """Time `fn` with blocking sync; returns (last_output, ms_per_iter).

    The reference times with CUDA events (ref: utils.py:274-286); on TPU we
    block on the async dispatch queue with block_until_ready, which measures
    the same device-side wall clock once warm.
    """
    out = None
    for _ in range(warmup_iters):
        out = fn()
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    t1 = time.perf_counter()
    return out, (t1 - t0) * 1e3 / iters


def chain_timer(build_fn, args, k_lo=1, k_hi=101, pairs=9, warmup=2):
    """Interleaved paired diffs of two chain lengths inside one jit.

    The reliable timing method behind bench.py whatever one dispatch
    costs on the machine at hand: build_fn(k) must return a jitted
    callable whose device time scales linearly in k via a data-dependent
    chain; the per-iteration estimate is the median of paired
    (k_hi - k_lo)-normalized differences, so the fixed per-call overhead
    and drift cancel. A
    non-positive median raises (never clamped — round-2 ADVICE)."""
    f_lo, f_hi = build_fn(k_lo), build_fn(k_hi)
    np.asarray(f_lo(*args))  # compile
    np.asarray(f_hi(*args))

    def once(f):
        t0 = time.perf_counter()
        np.asarray(f(*args))  # host fetch forces completion
        return (time.perf_counter() - t0) * 1e3

    for _ in range(warmup):
        once(f_lo), once(f_hi)
    diffs = [
        (once(f_hi) - once(f_lo)) / (k_hi - k_lo) for _ in range(pairs)
    ]
    ms = float(np.median(diffs))
    if ms <= 0:
        raise RuntimeError(f"measurement failed: median diff {ms} <= 0")
    # p25/min ride along for pool-interference context: contamination is
    # predominantly upward (the hi chain is ~k_hi/k_lo times more exposed
    # than the lo chain), so the lower tail approximates the uncontended
    # latency. Tail stats drop glitched non-positive pairs (a lo-chain
    # RTT spike can make a diff negative — same filter ratio_timer
    # applies). The headline stays the median — never the optimistic
    # tail.
    pos = [d for d in diffs if d > 0]
    return ms, {
        "diffs_ms": [round(d, 4) for d in diffs],
        "k": (k_lo, k_hi),
        "p25_ms": round(float(np.percentile(pos, 25)), 4),
        "min_ms": round(float(np.min(pos)), 4),
    }


def ratio_timer(build_a, build_b, args, k_lo=1, k_hi=51, pairs=7,
                warmup=2):
    """Median per-round ratio of two chain-timed kernels.

    The chip's clock drifts on a seconds timescale (shared pool /
    DVFS): two chain_timer calls made back to back can disagree by
    ±8%, which swamps a few-percent kernel comparison. Here each round
    measures BOTH chains within milliseconds of each other, so the
    drift cancels in the per-round ratio; the cross-round median then
    rejects stragglers. Returns (ratio, a_ms, b_ms)."""
    fa_lo, fa_hi = build_a(k_lo), build_a(k_hi)
    fb_lo, fb_hi = build_b(k_lo), build_b(k_hi)
    for f in (fa_lo, fa_hi, fb_lo, fb_hi):
        np.asarray(f(*args))  # compile

    def once(f):
        t0 = time.perf_counter()
        np.asarray(f(*args))  # host fetch forces completion
        return (time.perf_counter() - t0) * 1e3

    for _ in range(warmup):
        once(fa_hi), once(fb_hi)
    ratios, da_all, db_all = [], [], []
    for _ in range(pairs):
        da = (once(fa_hi) - once(fa_lo)) / (k_hi - k_lo)
        db = (once(fb_hi) - once(fb_lo)) / (k_hi - k_lo)
        if da > 0 and db > 0:  # drop glitched rounds, never clamp
            ratios.append(da / db)
            da_all.append(da)
            db_all.append(db)
    if not ratios:
        raise RuntimeError("ratio measurement failed: no positive rounds")
    return (float(np.median(ratios)), float(np.median(da_all)),
            float(np.median(db_all)))


def _once_ms(f, args):
    t0 = time.perf_counter()
    np.asarray(f(*args))  # host fetch forces completion
    return (time.perf_counter() - t0) * 1e3


def _theil_sen(t_by_k: dict) -> float:
    """Median of pairwise slopes over {chain length: median time}."""
    ks = sorted(t_by_k)
    slopes = [
        (t_by_k[k2] - t_by_k[k1]) / (k2 - k1)
        for i, k1 in enumerate(ks) for k2 in ks[i + 1:]
    ]
    return float(np.median(slopes))


def slope_timer(build_fn, args, ks=(1, 201, 401), rounds=6, warmup=2):
    """Per-iteration time via a robust slope fit over chain lengths.

    Why not paired diffs at small k: where the fixed per-call overhead
    is large and jitters BOTH ways (an earlier rig showed a k=51 sample
    below its own k=1 baseline), a short chain's signal drowns. The
    answer is signal amplification — chains long enough
    (ks up to ~400 iterations for sub-ms kernels) that the per-k spread
    is small relative to the span — plus a median per chain length (the
    jitter is two-sided, so min would chase deflated samples) and a
    Theil-Sen slope (median of pairwise slopes) across chain lengths,
    which tolerates one fully-contaminated k. Costs one compile per
    chain length — use for small kernels, not model-scale programs."""
    fns = {k: build_fn(k) for k in ks}
    for f in fns.values():
        np.asarray(f(*args))  # compile
    for _ in range(warmup):
        for f in fns.values():
            _once_ms(f, args)
    t_med = {
        k: float(np.median([_once_ms(fns[k], args)
                            for _ in range(rounds)]))
        for k in ks
    }
    ms = _theil_sen(t_med)
    if ms <= 0:
        raise RuntimeError(f"measurement failed: median slope {ms} <= 0")
    return ms, {"t_med_ms": {k: round(v, 4) for k, v in t_med.items()}}


def slope_ratio_timer(build_a, build_b, args, ks=(1, 201, 401), rounds=6,
                      warmup=2):
    """Ratio of two kernels' per-iteration slopes, rounds interleaved
    across both arms so a clock-drift window hits them alike. Returns
    (ratio, a_ms, b_ms). See slope_timer for the robustness argument."""
    fa = {k: build_a(k) for k in ks}
    fb = {k: build_b(k) for k in ks}
    for f in list(fa.values()) + list(fb.values()):
        np.asarray(f(*args))  # compile
    for _ in range(warmup):
        for k in ks:
            _once_ms(fa[k], args), _once_ms(fb[k], args)
    ta = {k: [] for k in ks}
    tb = {k: [] for k in ks}
    for _ in range(rounds):
        for k in ks:
            ta[k].append(_once_ms(fa[k], args))
            tb[k].append(_once_ms(fb[k], args))

    def slope(t):
        return _theil_sen({k: float(np.median(v)) for k, v in t.items()})

    a_ms, b_ms = slope(ta), slope(tb)
    if a_ms <= 0 or b_ms <= 0:
        raise RuntimeError(
            f"measurement failed: slopes {a_ms}, {b_ms} not positive")
    return a_ms / b_ms, a_ms, b_ms


def assert_allclose(x, y, atol=1e-3, rtol=1e-3, verbose=True):
    """allclose with mismatch dump (ref: utils.py:870-899)."""
    x = np.asarray(x)
    y = np.asarray(y)
    if x.shape != y.shape:
        raise AssertionError(f"shape mismatch {x.shape} vs {y.shape}")
    if np.allclose(x, y, atol=atol, rtol=rtol):
        return
    diff = np.abs(x.astype(np.float64) - y.astype(np.float64))
    mask = diff > (atol + rtol * np.abs(y.astype(np.float64)))
    n_bad = int(mask.sum())
    idx = np.argwhere(mask)[:10]
    msg = [
        f"assert_allclose failed: {n_bad}/{x.size} mismatched "
        f"(atol={atol}, rtol={rtol}), max_abs_diff={diff.max():.6g}"
    ]
    if verbose:
        for i in idx:
            ti = tuple(int(v) for v in i)
            msg.append(f"  at {ti}: {x[ti]!r} vs {y[ti]!r}")
    raise AssertionError("\n".join(msg))


# group_profile / merge_traces moved to triton_dist_tpu.trace.export —
# ONE trace-merging code path beside the in-kernel trace exporter. These
# aliases keep the historical `runtime.utils` import surface working.
from triton_dist_tpu.trace.export import (  # noqa: E402,F401
    group_profile,
    merge_traces,
)
