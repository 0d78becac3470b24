"""Analytic performance models for comm and GEMM on TPU.

TPU-native re-design of the reference's perf models
(ref: python/triton_dist/kernels/nvidia/comm_perf_model.py:51-130 — NIC
bandwidth discovery + AG/RS time estimates; gemm_perf_model.py:61-126 —
tensor-core TFLOPS estimation). There the models discover NVLink/IB/NUMA
topology from pynvml; here the topology is the TPU generation (device_kind)
plus the ICI mesh shape, and the roofline is MXU flops vs HBM vs ICI link
bandwidth. Consumers: kernel method auto-selection and the contextual
autotuner's config pre-pruning (autotuner.prune_configs).

Numbers are public per-chip specs (cloud.google.com/tpu/docs/system-
architecture-tpu-vm): peak bf16 FLOPS, HBM bandwidth, ICI links and
per-link bandwidth. Efficiency factors are deliberately conservative —
the model ranks candidates; it does not promise wall-clock.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Peak capabilities of one TPU chip (one Pallas 'device')."""

    name: str
    bf16_tflops: float        # peak MXU bf16 TFLOP/s per chip
    hbm_gbps: float           # HBM bandwidth, GB/s
    ici_gbps_per_link: float  # one-direction bandwidth of one ICI link, GB/s
    ici_links: int            # ICI links per chip (torus degree)
    vmem_mb: int              # VMEM per core, MiB
    ici_latency_us: float = 1.0   # per-hop ICI latency
    dcn_gbps: float = 25.0        # per-host DCN bandwidth (inter-slice plane)


# Public spec sheet. v5e has a single TensorCore per chip; v4/v5p have two
# (the perf_model works per chip, which is the Pallas device granularity).
CHIPS = {
    "TPU v4": ChipSpec("v4", 275.0, 1228.0, 50.0, 6, 128),
    "TPU v5 lite": ChipSpec("v5e", 197.0, 819.0, 50.0, 4, 128),
    "TPU v5": ChipSpec("v5p", 459.0, 2765.0, 100.0, 6, 128),
    "TPU v5p": ChipSpec("v5p", 459.0, 2765.0, 100.0, 6, 128),
    "TPU v6 lite": ChipSpec("v6e", 918.0, 1640.0, 100.0, 4, 128),
    "TPU v6e": ChipSpec("v6e", 918.0, 1640.0, 100.0, 4, 128),
    # CPU-mesh tests land here; values only need to rank consistently.
    "cpu": ChipSpec("cpu", 1.0, 50.0, 5.0, 2, 128),
}


@functools.lru_cache(maxsize=None)
def detect_chip() -> ChipSpec:
    """ChipSpec for the local device (the reference's pynvml topology
    discovery, comm_perf_model.py:51-93, collapses to a table lookup on
    TPU: the generation fixes link count and bandwidth). A TPU whose
    device_kind is not in CHIPS is an error — pricing it as some other
    generation would hide the device from every chooser."""
    from triton_dist_tpu.lang.core import backend_device

    d = backend_device()
    if d.platform != "tpu":
        return CHIPS["cpu"]
    for key, spec in CHIPS.items():
        if d.device_kind.startswith(key):
            return spec
    raise RuntimeError(
        f"unknown TPU device_kind {d.device_kind!r}: add its published "
        f"peaks to perf_model.CHIPS (known: {sorted(CHIPS)})")


def _dtype_bytes(dtype) -> int:
    return jnp.dtype(dtype).itemsize


def kernel_vmem_ceiling(chip: Optional[ChipSpec] = None) -> int:
    """VMEM budget a single forced/tuned kernel candidate may plan
    against: half the chip's VMEM, capped at 64 MiB. The conservative
    per-kernel dataclass defaults (14-15 MiB) exist for the AUTO
    fallback decision — where exceeding VMEM silently flips regimes —
    but using them to prune the measured candidate set was cutting the
    frontier exactly where the roofline says the winners live (wide
    tiles, nk==1 direct-store): on a 128 MiB v5e the model's best
    configs need 30-63 MiB. The cap keeps a compile-failure margin —
    Mosaic needs headroom beyond the declared scratch."""
    chip = chip or detect_chip()
    return min((chip.vmem_mb << 20) // 2, 64 << 20)


# -- HBM burst-efficiency model (megakernel byte-accurate floor) ------------

# Effective-bandwidth penalty of short strided bursts. A DMA whose
# contiguous runs are `burst` bytes long sustains roughly
# burst / (burst + HBM_BURST_GAP_BYTES) of peak — the gap term folds
# per-burst row turnaround and descriptor overhead into one constant.
# Calibrated on the round-5 32B megakernel ledger: with the legacy
# 512-column tiles (gate_up/qkv streaming in 512-byte bursts, o/down in
# 1024-byte bursts) the model prices the 9.76 ms raw-byte floor at
# ~11.4 ms, against 11.50 ms measured then (a round-5 record, deleted
# in PR 24; not measured on today's code) — the "missing 1.7 ms" the old
# floor could not attribute was mostly burst inefficiency, not stalls
# (trace attribution showed scoreboard/sem waits near zero at 1 queue).
HBM_BURST_GAP_BYTES = 96.0


def hbm_stream_efficiency(burst_bytes: Optional[float],
                          gap_bytes: float = HBM_BURST_GAP_BYTES) -> float:
    """Fraction of peak HBM bandwidth sustained at this contiguous
    burst length; None (or non-positive) means a contiguous stream."""
    if burst_bytes is None or burst_bytes <= 0:
        return 1.0
    b = float(burst_bytes)
    return b / (b + gap_bytes)


@dataclasses.dataclass(frozen=True)
class TrafficTerm:
    """One HBM traffic component of a kernel/step byte ledger."""

    name: str
    nbytes: int
    burst_bytes: Optional[float] = None  # None = contiguous


def streamed_floor_ms(terms, chip: Optional[ChipSpec] = None) -> float:
    """Byte-accurate HBM floor: each term streams at the effective
    bandwidth its burst length sustains. This is the floor a schedule
    that hides every stall would still pay — gap-vs-floor ratios above
    1.0 are attributable work (stalls, uncounted bytes), not layout."""
    chip = chip or detect_chip()
    bw = chip.hbm_gbps * 1e9
    return sum(
        t.nbytes / (bw * hbm_stream_efficiency(t.burst_bytes))
        for t in terms
    ) * 1e3


# -- GEMM model (ref: gemm_perf_model.py:61-126) ----------------------------


def mxu_efficiency(m: int, n: int, k: int) -> float:
    """Fraction of peak the MXU sustains at these dims.

    The reference discounts by SM occupancy/quantization
    (gemm_perf_model.py:94-126); the TPU analogs are 128-alignment of each
    dim (MXU systolic tiles) and short-K pipeline drain."""
    eff = 1.0
    for dim in (m, n):
        if dim % 128:
            eff *= dim / (128 * ((dim + 127) // 128))
        if dim < 512:
            eff *= max(dim / 512, 0.25)
    if k < 512:
        eff *= max(k / 512, 0.25)
    return max(eff, 0.02)


def estimate_gemm_ms(
    m: int,
    n: int,
    k: int,
    dtype=jnp.bfloat16,
    chip: Optional[ChipSpec] = None,
    efficiency: float = 0.85,
) -> float:
    """Roofline GEMM time: max(MXU compute, HBM traffic)."""
    chip = chip or detect_chip()
    b = _dtype_bytes(dtype)
    compute_ms = (2.0 * m * n * k) / (
        chip.bf16_tflops * 1e12 * efficiency * mxu_efficiency(m, n, k)
    ) * 1e3
    traffic = b * (m * k + k * n + m * n)
    mem_ms = traffic / (chip.hbm_gbps * 1e9) * 1e3
    return max(compute_ms, mem_ms)


def gemm_arith_intensity(m: int, n: int, k: int, dtype=jnp.bfloat16) -> float:
    """FLOPs per HBM byte; below the chip ridge point the GEMM is
    memory-bound (decode GEMMs at bs<=8 always are)."""
    b = _dtype_bytes(dtype)
    return (2.0 * m * n * k) / (b * (m * k + k * n + m * n))


# -- Blocked-GEMM tile model (fused-kernel autotuning) ----------------------

# Fixed cost of one Pallas grid step (scalar bookkeeping + pipeline
# bubble between tiles). Calibrated on the v5e ag_gemm sweeps
# (benchmark/sweep_ag_gemm.py, round 5): the measured spread between the
# (256, 3200, 512) winner and narrow-tile losers at fixed HBM traffic is
# explained by ~0.2-0.4 us per step; the model only needs to RANK
# configs, so one conservative constant serves every chip generation.
GRID_STEP_US = 0.3


def estimate_blocked_gemm_ms(
    m: int,
    n: int,
    k: int,
    tile_m: int,
    tile_n: int,
    tile_k: int,
    dtype=jnp.bfloat16,
    out_dtype=None,
    chip: Optional[ChipSpec] = None,
    step_us: float = GRID_STEP_US,
) -> float:
    """Tile-aware roofline for a blocked matmul on the (i, j, kk) grid
    both fused kernels use for their local/forced regimes (kk innermost,
    j middle): per-tile HBM traffic counts the A-strip re-reads (once per
    column-tile sweep) and the B re-reads (once per row-tile sweep) that
    the coarse `estimate_gemm_ms` roofline ignores, plus a fixed
    per-grid-step overhead — the term that actually separates candidate
    tile shapes at the benched Qwen3 shapes, where total traffic barely
    moves but step counts differ 10x.

    Used by the autotuner's prune helpers (autotuner.
    prune_ag_gemm_configs / prune_gemm_rs_local_configs) to cut the
    measured config set to the model-plausible frontier; it ranks
    candidates, it does not promise wall-clock."""
    chip = chip or detect_chip()
    b_in = _dtype_bytes(dtype)
    b_out = _dtype_bytes(out_dtype or dtype)
    mt = -(-m // tile_m)
    nt = -(-n // tile_n)
    nk = -(-k // tile_k)
    # A block (i, kk) is re-fetched for every j; B block (kk, j) for
    # every i; C written once.
    traffic = b_in * (nt * m * k + mt * k * n) + b_out * m * n
    mem_ms = traffic / (chip.hbm_gbps * 1e9) * 1e3
    # MXU efficiency is a property of the PROBLEM dims here, not the
    # tiles: a 256-row tile still feeds the 128x128 systolic array at
    # full rate inside a long blocked sweep, so scoring tiles with the
    # short-dim penalty would misrank the measured wide-N winners. Tile
    # choice enters through traffic and the step count only.
    compute_ms = (2.0 * m * n * k) / (
        chip.bf16_tflops * 1e12 * 0.85 * mxu_efficiency(m, n, k)
    ) * 1e3
    step_ms = mt * nt * nk * step_us * 1e-3
    return max(compute_ms, mem_ms) + step_ms


def roofline_frontier(configs, model_ms, slack: float = 1.25):
    """Keep the configs the analytic model places within `slack` of the
    modeled optimum (the reference folds the same style of pre-filter
    into its config spaces). model_ms: cfg -> predicted ms; returns the
    surviving subset, never empty (the best-modeled config always
    survives)."""
    configs = list(configs)
    if not configs:
        return configs
    preds = [model_ms(c) for c in configs]
    best = min(preds)
    return [c for c, p in zip(configs, preds) if p <= best * slack]


# -- Comm models (ref: comm_perf_model.py:94-130) ---------------------------


def ici_ring_bw_gbps(chip: Optional[ChipSpec] = None, axes: int = 1) -> float:
    """Bandwidth available to a ring over `axes` ICI dimensions. Each torus
    axis contributes 2 links (both directions around the ring)."""
    chip = chip or detect_chip()
    usable = min(2 * axes, chip.ici_links)
    return chip.ici_gbps_per_link * usable


def estimate_ag_ms(
    nbytes_shard: int,
    n: int,
    chip: Optional[ChipSpec] = None,
    axes: int = 1,
) -> float:
    """Ring AllGather: each device receives (n-1) shards over the ring."""
    if n <= 1:
        return 0.0
    chip = chip or detect_chip()
    bw = ici_ring_bw_gbps(chip, axes) * 1e9
    wire_ms = (n - 1) * nbytes_shard / bw * 1e3
    return wire_ms + (n - 1) * chip.ici_latency_us * 1e-3


def estimate_rs_ms(
    nbytes_full: int,
    n: int,
    chip: Optional[ChipSpec] = None,
    axes: int = 1,
) -> float:
    """Ring ReduceScatter moves the same volume as AG (shard = full/n)."""
    if n <= 1:
        return 0.0
    return estimate_ag_ms(nbytes_full // n, n, chip, axes)


def estimate_ar_ms(
    nbytes: int,
    n: int,
    chip: Optional[ChipSpec] = None,
    axes: int = 1,
    method: str = "two_shot",
) -> float:
    """AllReduce: one-shot = every shard pushed to every peer (latency
    optimal, bandwidth n×); two-shot = RS + AG (bandwidth optimal)."""
    if n <= 1:
        return 0.0
    chip = chip or detect_chip()
    if method == "one_shot":
        bw = ici_ring_bw_gbps(chip, axes) * 1e9
        return (n - 1) * nbytes / bw * 1e3 + chip.ici_latency_us * 1e-3
    return estimate_rs_ms(nbytes, n, chip, axes) + estimate_ag_ms(
        nbytes // n, n, chip, axes
    )


# -- quantized-wire models (ISSUE 9: bytes-by-precision rooflines) ----------

# HBM passes a quantized wire adds at each codec edge: the encode reads
# the f32 value and writes the wire image; the decode reads the image
# and folds into the f32 accumulator. Conservative (VPU math rides the
# same passes); what matters is that the codec term scales with the
# NATIVE bytes while the wire term scales with the packed bytes, so
# native wins when there is no ICI to save (n small) and quantized wins
# once the hop term dominates — the crossover choose_wire_format walks.
WIRE_CODEC_PASSES = 3.0

# Cosine-drift bases per format kind, calibrated on the numerics
# harness (wire.numerics.collective_drift, H=512 per-row blocks, normal
# data): one gather-family encode/decode roundtrip. fp8 e4m3 carries
# ~3.5 significant bits -> ~3.5e-4; int8's 7+sign bits land ~3e-5.
WIRE_DRIFT_BASE = {"fp8": 3.5e-4, "int8": 3.0e-5}
# Reduction rings requantize per hop; measured drift grows ~sqrt(hops)
# with this calibrated prefactor (fp8 two-shot AR at n=8 measured
# ~1.5e-3 = base * sqrt(7) * 1.7).
WIRE_HOP_DRIFT_FACTOR = 1.7

_REDUCTION_COLLECTIVES = ("allreduce", "reduce_scatter",
                          "gemm_reduce_scatter")


def wire_shrink(dtype, fmt, row_width: int = 512) -> float:
    """Wire bytes / native bytes for rows of `row_width` elements in
    `dtype` under wire format `fmt` (1.0 for native). The packed image
    is 1 byte/element plus the bitcast f32 scales plus lane padding —
    wire.wire_row_bytes is the exact ledger; this is its ratio."""
    from triton_dist_tpu.wire import codec as wcodec

    f = wcodec.resolve(fmt)
    native = row_width * _dtype_bytes(dtype)
    return wcodec.wire_row_bytes(row_width, f, dtype) / native


def estimate_wire_drift(fmt, n: int = 1,
                        collective: str = "allgather") -> float:
    """Modeled cosine drift of one (collective, format) execution vs the
    f32/native wire — the admissibility side of choose_wire_format.
    Gather-family collectives pay one roundtrip; reduction rings pay a
    per-hop requantization chain growing ~sqrt(n-1). Conservative
    (per-row scale granularity — finer blocks only lower it); the
    harness (wire.numerics) is the measured ground truth this model is
    calibrated on."""
    from triton_dist_tpu.wire import codec as wcodec

    f = wcodec.resolve(fmt)
    if f.kind == "native":
        return 0.0
    base = WIRE_DRIFT_BASE[f.kind]
    if collective in _REDUCTION_COLLECTIVES and n > 1:
        return base * WIRE_HOP_DRIFT_FACTOR * max(n - 1, 1) ** 0.5
    return base


def estimate_collective_wire_ms(
    collective: str,
    nbytes: int,
    n: int,
    dtype=jnp.bfloat16,
    fmt=None,
    chip: Optional[ChipSpec] = None,
    row_width: int = 512,
) -> float:
    """Roofline of one collective under a wire format: the ICI term at
    the format's bytes-by-precision (wire_shrink) plus the codec edge
    passes over HBM (WIRE_CODEC_PASSES x the native bytes, zero for
    native). `nbytes` is the NATIVE payload: per-device full tensor for
    allreduce/reduce_scatter, per-rank shard for the gather family.
    Ranks formats for choose_wire_format; does not promise wall-clock."""
    chip = chip or detect_chip()
    shrink = wire_shrink(dtype, fmt, row_width)
    wb = int(nbytes * shrink)
    if collective == "allreduce":
        wire_ms = estimate_ar_ms(wb, n, chip, method="two_shot")
    elif collective == "reduce_scatter":
        wire_ms = estimate_rs_ms(wb, n, chip)
    elif collective in ("allgather", "low_latency_allgather",
                        "allgather_gemm"):
        wire_ms = estimate_ag_ms(wb, n, chip)
    elif collective == "gemm_reduce_scatter":
        wire_ms = estimate_rs_ms(wb, n, chip)
    else:
        raise ValueError(f"unknown collective {collective!r}")
    from triton_dist_tpu.wire import codec as wcodec

    if wcodec.is_native(fmt):
        return wire_ms  # no codec edges on the native wire
    codec_ms = WIRE_CODEC_PASSES * nbytes / (chip.hbm_gbps * 1e9) * 1e3
    return wire_ms + codec_ms


def choose_wire_format(
    nbytes: int,
    n: int,
    dtype=jnp.bfloat16,
    error_budget: Optional[float] = None,
    collective: str = "allreduce",
    formats=("fp8", "int8"),
    chip: Optional[ChipSpec] = None,
    row_width: int = 512,
):
    """The budget-gated wire selector: among `formats` whose modeled
    drift (estimate_wire_drift) clears `error_budget` — plus native,
    always admissible — pick the cheapest by the bytes-by-precision
    roofline (estimate_collective_wire_ms). error_budget=None uses
    wire.DEFAULT_ERROR_BUDGET; 0.0 forces native. Ties favor native
    (quantization is never free in fidelity). Returns a
    wire.WireFormat — pass it straight to the collective's
    wire_format= knob."""
    from triton_dist_tpu.wire import codec as wcodec
    from triton_dist_tpu.wire.numerics import DEFAULT_ERROR_BUDGET

    budget = DEFAULT_ERROR_BUDGET if error_budget is None else error_budget
    chip = chip or detect_chip()
    cands = [wcodec.NATIVE] + [
        wcodec.resolve(f) for f in formats
        if estimate_wire_drift(f, n, collective) <= budget
    ]
    best = min(cands, key=lambda f: estimate_collective_wire_ms(
        collective, nbytes, n, dtype, f, chip, row_width))
    native_ms = estimate_collective_wire_ms(
        collective, nbytes, n, dtype, wcodec.NATIVE, chip, row_width)
    best_ms = estimate_collective_wire_ms(
        collective, nbytes, n, dtype, best, chip, row_width)
    return wcodec.NATIVE if best_ms >= native_ms else best


# -- 2-level ICI+DCN collectives (ISSUE 18, xslice/) -------------------------

# DCN economics (EQuARX, arXiv 2506.17615): the inter-slice hop runs
# ~30x under ICI. Bandwidth defaults from ChipSpec.dcn_gbps (a
# deployment parameter, not a chip constant — pass `dcn_gbps` to
# override); the latency constant models the DCN hop running orders
# above the ICI hop.
DCN_LATENCY_US = 50.0


def estimate_xslice_collective_ms(
    nbytes: int,
    n_local: int,
    slices: int,
    collective: str = "allgather",
    chip: Optional[ChipSpec] = None,
    dcn_gbps: Optional[float] = None,
    wire_format=None,
    chunks: int = 1,
    dtype=jnp.bfloat16,
    row_width: int = 512,
) -> float:
    """Roofline of a 2-level (ICI + DCN) collective
    (xslice/collectives.py). `nbytes` follows the
    estimate_collective_wire_ms convention: per-device full tensor for
    allreduce/reduce_scatter, per-rank shard for allgather. The ICI leg
    prices at the existing ring estimators over `n_local`; the DCN leg
    prices the rail exchange at `dcn_gbps` with `wire_format`'s shrink
    (the wire rides the DCN leg ONLY — the shrink pays where the
    transport is ~30x slower) plus the codec edge passes. `chunks > 1`
    models the T3-style overlap: the ICI leg of chunk i+1 hides under
    the DCN exchange of chunk i, so the pipeline costs
    ici + dcn + (chunks-1) * max(ici, dcn) per-chunk terms instead of
    chunks * (ici + dcn)."""
    from triton_dist_tpu.wire import codec as wcodec

    chip = chip or detect_chip()
    chunks = max(int(chunks), 1)
    nb = nbytes / chunks
    shrink = wire_shrink(dtype, wire_format, row_width)
    dcn_bw = (chip.dcn_gbps if dcn_gbps is None else dcn_gbps) * 1e9

    if collective in ("allgather", "low_latency_allgather"):
        ici_ms = estimate_ag_ms(int(nb), n_local, chip)
        # every rank receives the other slices' whole slice blocks
        dcn_native = (slices - 1) * n_local * nb
    elif collective == "reduce_scatter":
        ici_ms = estimate_rs_ms(int(nb), n_local, chip)
        part = nb / n_local
        dcn_native = part * (slices - 1) / max(slices, 1)
    elif collective == "allreduce":
        part = nb / n_local
        ici_ms = (estimate_rs_ms(int(nb), n_local, chip)
                  + estimate_ag_ms(int(part), n_local, chip))
        dcn_native = 2 * part * (slices - 1) / max(slices, 1)
    else:
        raise ValueError(f"unknown 2-level collective {collective!r}")

    if slices <= 1:
        return chunks * ici_ms
    dcn_ms = (dcn_native * shrink / dcn_bw * 1e3
              + DCN_LATENCY_US * 1e-3)
    if not wcodec.is_native(wire_format):
        dcn_ms += (WIRE_CODEC_PASSES * dcn_native
                   / (chip.hbm_gbps * 1e9) * 1e3)
    return ici_ms + dcn_ms + (chunks - 1) * max(ici_ms, dcn_ms)


def estimate_migration_ms(
    nbytes: int,
    dcn_gbps: Optional[float] = None,
    wire_format=None,
    chip: Optional[ChipSpec] = None,
    dtype=jnp.bfloat16,
    row_width: int = 512,
) -> float:
    """One KV-page migration (xslice/migrate.py): a point-to-point DCN
    send of the page image at the format's shrink, plus the codec edge
    passes for quantized formats. Pass `row_width=head_dim` when it is
    known — the codec packs (rows, head_dim) KV planes, and a narrow
    row pays lane padding that can erase the shrink entirely."""
    from triton_dist_tpu.wire import codec as wcodec

    chip = chip or detect_chip()
    shrink = wire_shrink(dtype, wire_format, row_width)
    bw = (chip.dcn_gbps if dcn_gbps is None else dcn_gbps) * 1e9
    ms = nbytes * shrink / bw * 1e3 + DCN_LATENCY_US * 1e-3
    if not wcodec.is_native(wire_format):
        ms += WIRE_CODEC_PASSES * nbytes / (chip.hbm_gbps * 1e9) * 1e3
    return ms


def choose_migration_format(
    page_bytes: int,
    n_pages: int,
    dtype=jnp.bfloat16,
    error_budget: Optional[float] = None,
    dcn_gbps: Optional[float] = None,
    formats=("fp8", "int8"),
    chip: Optional[ChipSpec] = None,
    row_width: int = 512,
):
    """The budget-gated format chooser for KV migration: among
    `formats` whose ONE-ROUNDTRIP drift (the image encodes once at the
    prefill slice and decodes once at admission — no per-hop
    requantization chain) clears `error_budget`, pick the cheapest by
    estimate_migration_ms; native is always admissible and wins ties
    (quantization is never free in fidelity). error_budget=None uses
    wire.DEFAULT_ERROR_BUDGET; 0.0 forces native. Monotone both ways:
    a tighter budget never picks a lossier format, and a slower DCN
    never makes quantization less attractive
    (tests/test_tuning.py)."""
    from triton_dist_tpu.wire import codec as wcodec
    from triton_dist_tpu.wire.numerics import DEFAULT_ERROR_BUDGET

    budget = (DEFAULT_ERROR_BUDGET if error_budget is None
              else error_budget)
    chip = chip or detect_chip()
    nbytes = int(page_bytes) * max(int(n_pages), 1)
    cands = [wcodec.NATIVE] + [
        wcodec.resolve(f) for f in formats
        if estimate_wire_drift(f, 1, "allgather") <= budget
    ]
    cost = {f: estimate_migration_ms(nbytes, dcn_gbps, f, chip, dtype,
                                     row_width) for f in cands}
    best = min(cands, key=lambda f: cost[f])
    return wcodec.NATIVE if cost[best] >= cost[wcodec.NATIVE] else best


def estimate_a2a_ms(
    nbytes_per_peer: int,
    n: int,
    chip: Optional[ChipSpec] = None,
) -> float:
    """All-to-all over a 1-D torus: bisection-limited. Each of the two
    directions carries ~n/2 * payload across the cut."""
    if n <= 1:
        return 0.0
    chip = chip or detect_chip()
    bw = ici_ring_bw_gbps(chip, axes=1) * 1e9
    volume = nbytes_per_peer * n * n / 4
    return volume / (bw * n / 2) * 1e3 + chip.ici_latency_us * 1e-3


# -- chunk-pipelined EP MoE model (ISSUE 2 tentpole (c)) ---------------------


def estimate_ep_moe_ms(
    m: int,
    hidden: int,
    inter: int,
    e_loc: int,
    n: int,
    top_k: int,
    capacity: Optional[int] = None,
    n_chunks: int = 1,
    dtype=jnp.bfloat16,
    payload_dtype=None,
    chip: Optional[ChipSpec] = None,
    overlap: bool = True,
) -> float:
    """Pipeline roofline of the chunk-pipelined EP MoE layer
    (kernels/ep_a2a.ep_moe_pipeline): per-chunk dispatch A2A vs per-chunk
    grouped FFN, exposed time = ramp (first chunk's wire time in, last
    chunk's combine out) + per-chunk max-imbalance.

    The two chunk-count forces the model must capture:
      - more chunks -> less exposed comm (only the first chunk's A2A and
        the last chunk's combine are outside the overlap window);
      - more chunks -> worse per-chunk GEMM: mxu_efficiency of the
        shrinking row count, plus the expert weight stacks re-streamed
        from HBM once per chunk when they exceed VMEM residence.

    overlap=False models the same chunked math run sequentially
    (every chunk pays wire + compute back to back). Ranks candidates for
    autotuner.prune_ep_moe_configs; does not promise wall-clock."""
    chip = chip or detect_chip()
    c = capacity if capacity is not None else m * top_k
    q = max(1, min(int(n_chunks), c))
    rows = c / q
    b_wire = _dtype_bytes(payload_dtype or dtype)
    b = _dtype_bytes(dtype)

    # wire: dispatch chunk (token payload) and combine chunk (f32 back)
    ta = estimate_a2a_ms(int(rows * hidden * b_wire), n, chip)
    tc = estimate_a2a_ms(int(rows * hidden * 4), n, chip)

    # per-chunk grouped FFN over the n received sub-segments
    t_rows = int(n * rows)
    compute_ms = 0.0
    for (mm, nn, kk) in ((t_rows, 2 * inter, hidden),
                         (t_rows, hidden, inter)):
        compute_ms += (2.0 * mm * nn * kk) / (
            chip.bf16_tflops * 1e12 * 0.85 * mxu_efficiency(mm, nn, kk)
        ) * 1e3
    w_bytes = e_loc * (hidden * 2 * inter + inter * hidden) * b
    act_bytes = t_rows * (2 * hidden + 3 * inter) * b
    mem_ms = (w_bytes + act_bytes) / (chip.hbm_gbps * 1e9) * 1e3
    tf = max(compute_ms, mem_ms)

    if not overlap:
        return q * (ta + tf + tc)
    # ramp in (first chunk's wire), steady state (per-chunk max
    # imbalance), ramp out (last chunk's combine)
    return ta + q * max(ta, tf) + tc


def choose_ep_chunks(
    m: int,
    hidden: int,
    inter: int,
    e_loc: int,
    n: int,
    top_k: int,
    capacity: Optional[int] = None,
    dtype=jnp.bfloat16,
    payload_dtype=None,
    chip: Optional[ChipSpec] = None,
    candidates=(1, 2, 4, 8, 16),
    overlap: bool = False,
) -> int:
    """Model-picked chunk count for ep_moe_fwd: the candidate divisor of
    `capacity` minimizing the pipeline roofline.

    `overlap` must describe the composition that actually RUNS.
    The default False models today's execution, where the chunked
    transport kernel completes before the per-chunk FFNs start (the
    per-chunk delivery semaphores are kernel-internal; cross-kernel
    overlap needs semaphore-carrying outputs — see docs/performance.md),
    so every chunk pays wire + compute back to back and extra chunks
    can only add per-chunk GEMM and weight-restream cost: the pick
    degenerates to 1. overlap=True scores the true pipeline (the
    in-kernel-consumer target) where chunking shrinks the exposed ramp
    on comm-heavy multi-rank shapes. Picking overlap=True for a
    composition that does not overlap is a model-driven SLOWDOWN —
    q-fold MXU-efficiency and weight-traffic penalties hiding nothing."""
    c = capacity if capacity is not None else m * top_k
    live = [q for q in candidates if q <= c and c % q == 0] or [1]
    return min(live, key=lambda q: estimate_ep_moe_ms(
        m, hidden, inter, e_loc, n, top_k, capacity=c, n_chunks=q,
        dtype=dtype, payload_dtype=payload_dtype, chip=chip,
        overlap=overlap,
    ))


# -- megakernel decode byte ledger (world=1 latency ledger) -----------------


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def weight_shard_matrices(hidden: int, inter_loc: int, hq_loc: int,
                          hkv_loc: int, head_dim: int) -> dict:
    """The per-rank, per-layer dense weight matrices as wname -> (K, N),
    mirroring mega/qwen3.build_qwen3_graph's branch keys. The ONE
    definition of the layer's weight footprint: the megakernel decode
    ledger turns these into TrafficTerm rows and the serve-step
    roofline sums them into its amortized-once weight stream — the two
    callers previously spelled the same four shapes independently."""
    hqd = hq_loc * head_dim
    kwd = hkv_loc * head_dim
    return {
        "w_qkv": (hidden, hqd + 2 * kwd),
        "w_o": (hqd, hidden),
        "w_gate_up": (hidden, 2 * inter_loc),
        "w_down": (inter_loc, hidden),
    }


def weight_stream_bytes(num_layers: int, hidden: int, inter_loc: int,
                        hq_loc: int, hkv_loc: int, head_dim: int,
                        vocab_loc: int, dtype=jnp.bfloat16) -> int:
    """Bytes of ONE full pass over the per-rank weight shard: L x the
    weight_shard_matrices footprint plus the lm_head. This is the
    paid-once-per-step term continuous batching amortizes — both
    estimate_serve_step_ms and the mega decode ledger's weight rows
    reduce to exactly this total (tests/test_plan.py pins the
    equality)."""
    isz = _dtype_bytes(dtype)
    per_layer = sum(k * n for k, n in weight_shard_matrices(
        hidden, inter_loc, hq_loc, hkv_loc, head_dim).values())
    return (num_layers * per_layer + hidden * vocab_loc) * isz


def mega_decode_traffic_terms(
    num_layers: int,
    hidden: int,
    inter_loc: int,
    hq_loc: int,
    hkv_loc: int,
    head_dim: int,
    vocab_loc: int,
    s_max: int,
    batch: int = 1,
    dtype=jnp.bfloat16,
    tiled_weights=("w_gate_up",),
):
    """The per-step HBM byte ledger of the Qwen3 megakernel decode
    (mega/qwen3.build_qwen3_graph), as TrafficTerm rows.

    This replaces the weights-only floor that round 5 showed cannot
    explain the measured 32B step: it counts every byte class the
    schedule must move — weights AT THEIR ACTUAL TILE BURST LENGTHS
    (the same core.plan_mm_tiles map the kernel tiles with; tile-major
    weights stream contiguously), the lm_head matmul, the f32 norm
    stripes, the KV pages, the rope stripes, and the workspace
    store/load round trips (counted un-forwarded: the store/forward
    pipeline saves some of these, so the floor is a hair conservative
    on that one small term). Dims are the PER-RANK shard (what one chip
    streams)."""
    from triton_dist_tpu.lang.core import min_tile
    from triton_dist_tpu.mega.core import plan_mm_tiles

    L = num_layers
    isz = _dtype_bytes(dtype)
    pb = _round_up(max(batch, 1), min_tile(dtype)[0])
    wqkv = (hq_loc + 2 * hkv_loc) * head_dim
    hqd = hq_loc * head_dim
    kw = hkv_loc * head_dim
    hqdp = _round_up(hqd, 128)
    kwp = _round_up(kw, 128)

    # wname -> (K, N): the ONE weight-footprint definition shared with
    # estimate_serve_step_ms (weight_stream_bytes pins the totals equal)
    mm = weight_shard_matrices(hidden, inter_loc, hq_loc, hkv_loc,
                               head_dim)
    tn_of = plan_mm_tiles([("matmul", w, k, n, None, 0.0)
                           for w, (k, n) in mm.items()])
    terms = []
    for w, (k, n) in sorted(mm.items()):
        tn = tn_of[("matmul", w, k, n, None, 0.0)]
        burst = None if w in tiled_weights else tn * isz
        terms.append(TrafficTerm(w, L * k * n * isz, burst))
    # lm_head runs as a plain XLA dot outside the kernel: contiguous
    terms.append(TrafficTerm("lm_head", hidden * vocab_loc * isz))
    # f32 norm stripes: 8-row full-width rows, contiguous
    nw = _round_up(max(hidden, head_dim), 128)
    terms.append(TrafficTerm("norms", (4 * L + 1) * 8 * nw * 4))
    # rope cos|sin stripe per attention task per sequence
    terms.append(TrafficTerm("rope", L * batch * 8 * head_dim * 4))
    # KV pages (contiguous (page, D) blocks)
    terms.append(TrafficTerm(
        "kv", 2 * L * hkv_loc * batch * s_max * head_dim * isz))
    # workspace round trips: per-task input loads + output stores at
    # pb-row stripes (un-forwarded upper bound; rows are width*isz
    # contiguous — burst effects are noise at these widths)
    per_layer_cols = (
        (hidden + wqkv)                    # ln1+qkv matmul
        + (wqkv + hqdp + 2 * kwp)          # attention
        + (hqdp + hidden)                  # o matmul
        + 3 * hidden                       # ar_attn (+residual)
        + (hidden + 2 * inter_loc)         # ln2+gate_up
        + (2 * inter_loc + hidden)         # silu+down
        + 3 * hidden                       # ar_mlp
    )
    ws_cols = L * per_layer_cols + 2 * hidden  # + final rms in/out
    terms.append(TrafficTerm("workspace", pb * ws_cols * isz))
    return terms


def mega_decode_floor_ms(*args, chip: Optional[ChipSpec] = None,
                         **kwargs) -> float:
    """Byte-accurate megakernel decode floor (streamed_floor_ms over
    mega_decode_traffic_terms) — what bench.py's mega_*_hbm_floor_ms
    fields report since the world=1 ledger PR."""
    return streamed_floor_ms(
        mega_decode_traffic_terms(*args, **kwargs), chip)


# -- SP flash-prefill pipeline model (ISSUE 7 tentpole) ----------------------

# Fixed cost of dispatching the Pallas prefill kernel (launch + scalar
# prologue + the first page's un-overlapped DMA). The XLA formulations
# fuse into the surrounding program and pay no such step, so this term
# is what makes choose_prefill_impl a real decision: tiny serve chunks
# (s*t small — logits traffic below a few MB) stay on the fused dense
# path; the kernel wins as soon as the logits term clears it.
FLASH_PREFILL_LAUNCH_US = 5.0


def estimate_flash_prefill_ms(
    s_q: int,
    t: int,
    hq: int,
    hkv: int,
    d: int,
    batch: int = 1,
    dtype=jnp.bfloat16,
    chip: Optional[ChipSpec] = None,
    block: Optional[int] = None,
) -> float:
    """Roofline of ONE flash-prefill fold sweep: s_q query rows against
    t KV rows (kernels/flash_prefill._fp_local_kernel, or one segment
    of the SP kernel with t = S_loc). Compute is the per-block flash
    FLOPs (4*S*T*Hq*D — logits + p@v, online state updates are noise);
    memory is the double-buffered KV page stream at the page's burst
    efficiency (`block` rows x Hkv*D columns contiguous — taller pages
    amortize the per-burst gap, the trade the autotuner's pruner
    ranks); plus the fixed kernel-dispatch term the fused XLA paths do
    not pay. The (S, T) logits tensor never exists, which is exactly
    the term that separates this from estimate_xla_prefill_ms."""
    chip = chip or detect_chip()
    b = _dtype_bytes(dtype)
    flops = 4.0 * batch * s_q * t * hq * d
    compute_ms = flops / (
        chip.bf16_tflops * 1e12 * 0.85 * mxu_efficiency(s_q, t, d)
    ) * 1e3
    kv_bytes = 2 * batch * t * hkv * d * b
    burst = block * hkv * d * b if block else None
    mem_ms = kv_bytes / (
        chip.hbm_gbps * 1e9 * hbm_stream_efficiency(burst)) * 1e3
    return max(compute_ms, mem_ms) + FLASH_PREFILL_LAUNCH_US * 1e-3


def estimate_xla_prefill_ms(
    s_q: int,
    t: int,
    hq: int,
    hkv: int,
    d: int,
    batch: int = 1,
    dtype=jnp.bfloat16,
    chip: Optional[ChipSpec] = None,
) -> float:
    """The XLA fold (ring_attention's _block_update / the blockwise
    scan): same FLOPs, but the f32 logits materialize in HBM between
    the two einsums — written by the first einsum's fusion and read
    back by the softmax/p@v fusion. The TOTAL is s_q*t regardless of
    how the sweep is chunked (chunk-invariant, hence no chunk knob
    here). That traffic rides in its OWN phases, serialized against
    the MXU work (separate fusions — XLA does not flash-rewrite
    attention), so it ADDS to the roofline rather than hiding under
    it. That additive term is what the Pallas kernel deletes."""
    chip = chip or detect_chip()
    b = _dtype_bytes(dtype)
    flops = 4.0 * batch * s_q * t * hq * d
    compute_ms = flops / (
        chip.bf16_tflops * 1e12 * 0.85 * mxu_efficiency(s_q, t, d)
    ) * 1e3
    kv_bytes = 2 * batch * t * hkv * d * b
    logits_bytes = 2 * 4 * batch * hq * s_q * t  # f32, write + read
    logits_ms = logits_bytes / (chip.hbm_gbps * 1e9) * 1e3
    mem_ms = kv_bytes / (chip.hbm_gbps * 1e9) * 1e3
    return max(compute_ms, mem_ms) + logits_ms


def choose_prefill_impl(
    s_q: int,
    t: int,
    hq: int,
    hkv: int,
    d: int,
    batch: int = 1,
    dtype=jnp.bfloat16,
    chip: Optional[ChipSpec] = None,
) -> str:
    """"flash" | "xla" for a LOCAL prefill sweep (the serve prefill-
    chunk / blockwise-prefill switch, layers.attention.gqa_attention).
    Shape support (native lane alignment) is the caller's gate
    (kernels.flash_prefill.supports_flash_prefill); this ranks cost
    only."""
    f = estimate_flash_prefill_ms(s_q, t, hq, hkv, d, batch, dtype, chip)
    x = estimate_xla_prefill_ms(s_q, t, hq, hkv, d, batch, dtype, chip)
    return "flash" if f <= x else "xla"


def estimate_sp_prefill_ms(
    s_loc: int,
    n: int,
    hq: int,
    hkv: int,
    d: int,
    batch: int = 1,
    dtype=jnp.bfloat16,
    chip: Optional[ChipSpec] = None,
    impl: str = "flash",
) -> float:
    """Pipeline roofline of the SP flash prefill
    (kernels/flash_prefill._fp_sp_kernel): per-segment ICI delivery vs
    per-segment flash fold, exposed = ramp + (n-1)*max(seg_ms, fold_ms)
    where ramp is the zero-wait LOCAL fold (the rank-offset swizzle —
    the first remote segment flies while it runs) and every remaining
    segment costs whichever of its delivery or its fold dominates.

    impl="ring" prices the lax.ppermute formulation instead: the XLA
    fold (logits materialization, estimate_xla_prefill_ms) per segment,
    with the same overlap structure credited to XLA's async collectives
    — the model separates the two by the fold term, not by distrusting
    XLA's overlap. Ranks candidates for choose_sp_prefill_impl /
    autotuner.prune_flash_prefill_configs; does not promise wall-clock."""
    chip = chip or detect_chip()
    b = _dtype_bytes(dtype)
    est = (estimate_flash_prefill_ms if impl == "flash"
           else estimate_xla_prefill_ms)
    fold_ms = est(s_loc, s_loc, hq, hkv, d, batch, dtype, chip)
    if n <= 1:
        return fold_ms
    seg_bytes = 2 * batch * s_loc * hkv * d * b
    seg_ms = seg_bytes / (ici_ring_bw_gbps(chip) * 1e9) * 1e3 \
        + chip.ici_latency_us * 1e-3
    return fold_ms + (n - 1) * max(seg_ms, fold_ms)


def choose_sp_prefill_impl(
    s_loc: int,
    n: int,
    hq: int,
    hkv: int,
    d: int,
    batch: int = 1,
    dtype=jnp.bfloat16,
    chip: Optional[ChipSpec] = None,
) -> str:
    """"flash" | "ring" — the autotuner-selectable SP prefill switch
    (kernels.flash_prefill.sp_prefill_attention). ring_attention stays
    the fallback whenever the model does not rank the kernel ahead."""
    f = estimate_sp_prefill_ms(s_loc, n, hq, hkv, d, batch, dtype, chip,
                               impl="flash")
    r = estimate_sp_prefill_ms(s_loc, n, hq, hkv, d, batch, dtype, chip,
                               impl="ring")
    return "flash" if f <= r else "ring"


# -- serving-plane step model (ISSUE 6 tentpole (c)) -------------------------


def estimate_serve_step_ms(
    num_layers: int,
    hidden: int,
    inter_loc: int,
    hq_loc: int,
    hkv_loc: int,
    head_dim: int,
    vocab_loc: int,
    n_tokens: int,
    kv_tokens: int = 0,
    dtype=jnp.bfloat16,
    chip: Optional[ChipSpec] = None,
    attn_impl: str = "flash",
) -> float:
    """Roofline of ONE mixed prefill+decode serve step
    (models/engine.make_serve_step) processing `n_tokens` real tokens
    (prefill-chunk columns + decode slots combined) against `kv_tokens`
    of live context across the batch.

    The term structure is what makes continuous batching pay: the
    per-step WEIGHT stream (the whole per-rank shard — the decode
    floor's dominant term at bs=1) is paid ONCE regardless of how many
    tokens ride the step, so packing prefill chunks beside decode slots
    amortizes it; the COMPUTE term grows with n_tokens and eventually
    flips the step compute-bound — the crossover the chunk chooser
    walks. KV/activation traffic ride along as minor terms.

    attn_impl prices the prefill-chunk attention: "flash" (the Pallas
    flash-prefill kernel — KV stream only) vs "xla" (the dense/scan
    formulation, which also writes+reads the f32 logits chunk). Bigger
    chunks grow the xla logits term quadratically, so the chooser's
    pick widens under "flash" — exactly the effect the device-side
    kernel buys the scheduler. Ranks scheduler choices; does not
    promise wall-clock."""
    chip = chip or detect_chip()
    b = _dtype_bytes(dtype)
    hqd, kwd = hq_loc * head_dim, hkv_loc * head_dim
    # the paid-once weight stream: the shared shard-footprint helper
    # (same matrices the mega decode ledger prices, lm_head included)
    w_bytes = weight_stream_bytes(num_layers, hidden, inter_loc,
                                  hq_loc, hkv_loc, head_dim, vocab_loc,
                                  dtype=dtype)
    kv_bytes = 2 * num_layers * kwd * kv_tokens * b
    act_bytes = n_tokens * num_layers * (4 * hidden + 3 * inter_loc) * b
    if attn_impl == "xla":
        # per-layer f32 logits chunk materializes (write + read)
        act_bytes += num_layers * 2 * 4 * hq_loc * n_tokens * kv_tokens
    mem_ms = (w_bytes + kv_bytes + act_bytes) / (chip.hbm_gbps * 1e9) * 1e3

    flops = 2.0 * n_tokens * (
        num_layers * (hidden * (hqd + 2 * kwd) + hqd * hidden
                      + 3 * hidden * inter_loc)
        + hidden * vocab_loc
    ) + 4.0 * n_tokens * kv_tokens * num_layers * hq_loc * head_dim
    # efficiency WITHOUT the short-m penalty: at small token counts the
    # step is weight-stream-bound and the MXU consumes rows as they
    # arrive (the measured decode step sits on the HBM floor, not a
    # short-m MXU cliff) — the m penalty would wrongly flip tiny steps
    # compute-bound and break the amortization story the chunk chooser
    # depends on
    compute_ms = flops / (
        chip.bf16_tflops * 1e12 * 0.85
        * mxu_efficiency(max(n_tokens, 1024), hidden, hidden)
    ) * 1e3
    return max(compute_ms, mem_ms)


# Per-step host dispatch tax of the host-loop serve path: one python
# step assembly + jit re-entry + host->device arg staging. A
# conservative constant for the LOCAL dispatch floor, set from a
# round-5 reading whose record is deleted (PR 24); not measured on
# today's code.
SERVE_DISPATCH_US = 250.0


def expected_spec_tokens(accept_rate: float, k: int) -> float:
    """Expected tokens emitted per spec-verify step under a per-token
    acceptance probability `accept_rate`: 1 (the bonus token) plus the
    expected accepted-prefix length of k geometric trials —
    sum_{i=0..k} p^i = (1 - p^(k+1)) / (1 - p). k=0 -> 1.0 exactly
    (spec off)."""
    p = min(max(accept_rate, 0.0), 1.0)
    if p >= 1.0:
        return float(k + 1)
    return (1.0 - p ** (k + 1)) / (1.0 - p)


def estimate_spec_step_ms(
    num_layers: int,
    hidden: int,
    inter_loc: int,
    hq_loc: int,
    hkv_loc: int,
    head_dim: int,
    vocab_loc: int,
    k: int,
    accept_rate: float,
    slots: int = 4,
    kv_tokens: int = 0,
    dtype=jnp.bfloat16,
    chip: Optional[ChipSpec] = None,
    attn_impl: str = "flash",
) -> float:
    """Per-EMITTED-TOKEN cost of spec-verify decode (ISSUE 14,
    triton_dist_tpu.spec), acceptance-rate-parameterized: one verify
    step runs the mixed-step roofline over slots * (k+1) tokens (every
    decoding slot carries its k drafts) plus the per-step host
    dispatch, and emits expected_spec_tokens(accept_rate, k) tokens
    per slot. k=0 degenerates EXACTLY to the plain decode step's
    per-token cost — the chooser's off-switch. While the step is
    weight-stream-bound the k extra columns are nearly free, so any
    nonzero acceptance wins; once compute-bound the wasted rejected
    columns price in — the crossover choose_spec_k walks."""
    step_ms = estimate_serve_step_ms(
        num_layers, hidden, inter_loc, hq_loc, hkv_loc, head_dim,
        vocab_loc, n_tokens=max(slots, 1) * (k + 1),
        kv_tokens=kv_tokens, dtype=dtype, chip=chip,
        attn_impl=attn_impl) + SERVE_DISPATCH_US * 1e-3
    return step_ms / expected_spec_tokens(accept_rate, k)


def choose_spec_k(
    num_layers: int,
    hidden: int,
    inter_loc: int,
    hq_loc: int,
    hkv_loc: int,
    head_dim: int,
    vocab_loc: int,
    accept_rate: float,
    slots: int = 4,
    kv_tokens: int = 0,
    dtype=jnp.bfloat16,
    chip: Optional[ChipSpec] = None,
    attn_impl: str = "flash",
    k_max: int = 8,
    min_gain: float = 0.02,
) -> int:
    """The draft width for `serve.Scheduler(spec=SpecConfig(k=...))`:
    the k in [0, k_max] minimizing the modeled per-emitted-token cost,
    but 0 (spec OFF) unless the winner beats plain decode by at least
    `min_gain` — speculative decode buys throughput with wasted
    columns, so a within-noise win is not worth the scheduling
    complexity. Monotone non-decreasing in accept_rate
    (tests/test_spec.py pins it): low acceptance keeps k at 0, high
    acceptance saturates toward k_max."""
    args = (num_layers, hidden, inter_loc, hq_loc, hkv_loc, head_dim,
            vocab_loc)
    kw = dict(slots=slots, kv_tokens=kv_tokens, dtype=dtype, chip=chip,
              attn_impl=attn_impl)
    base = estimate_spec_step_ms(*args, k=0, accept_rate=accept_rate,
                                 **kw)
    best_k, best_ms = 0, base
    for k in range(1, max(k_max, 0) + 1):
        ms = estimate_spec_step_ms(*args, k=k,
                                   accept_rate=accept_rate, **kw)
        if ms < best_ms:
            best_k, best_ms = k, ms
    return best_k if best_ms <= (1.0 - min_gain) * base else 0


# prefix-cache granularity: host-side trie cost per BLOCK per admission
# (hash + dict walk on the scheduler thread — measured class, not
# device work)
PREFIX_NODE_US = 2.0


def choose_prefix_block(
    num_layers: int,
    hidden: int,
    inter_loc: int,
    hq_loc: int,
    hkv_loc: int,
    head_dim: int,
    vocab_loc: int,
    page: int,
    t_max: int,
    prompt_len: Optional[int] = None,
    slots: int = 4,
    dtype=jnp.bfloat16,
    chip: Optional[ChipSpec] = None,
    attn_impl: str = "flash",
) -> int:
    """Token-block granularity for `serve.PrefixCache` (a multiple of
    the pool page): small blocks match more of a shared prefix (the
    expected truncation loss of block-aligned matching is ~block/2
    tokens of re-prefill) but cost more host trie work per admission
    (prompt_len / block nodes hashed + walked). The chooser minimizes
    the modeled per-admission total — truncation priced at the
    marginal prefill cost per token from the mixed-step roofline,
    trie work at PREFIX_NODE_US per block — over page multiples up to
    t_max. Fast steps (big models amortize nothing) push the block
    up; slow per-token prefill pushes it down to the page."""
    prompt_len = prompt_len or max(t_max // 2, page)
    args = (num_layers, hidden, inter_loc, hq_loc, hkv_loc, head_dim,
            vocab_loc)
    kw = dict(kv_tokens=prompt_len, dtype=dtype, chip=chip,
              attn_impl=attn_impl)
    # marginal prefill cost per token: slope of the mixed step between
    # 1 and 129 tokens (the weight stream cancels out of the slope)
    t1 = estimate_serve_step_ms(*args, n_tokens=max(slots, 1), **kw)
    t129 = estimate_serve_step_ms(*args, n_tokens=max(slots, 1) + 128,
                                  **kw)
    tok_us = max((t129 - t1) / 128.0 * 1e3, 1e-6)
    best, best_cost = page, None
    b = page
    while b <= min(t_max, prompt_len) or b == page:
        cost = (prompt_len / b) * PREFIX_NODE_US + (b / 2.0) * tok_us
        if best_cost is None or cost < best_cost:
            best, best_cost = b, cost
        b *= 2
    return best


def choose_prefill_chunk(
    num_layers: int,
    hidden: int,
    inter_loc: int,
    hq_loc: int,
    hkv_loc: int,
    head_dim: int,
    vocab_loc: int,
    slots: int = 4,
    kv_tokens: int = 0,
    dtype=jnp.bfloat16,
    chip: Optional[ChipSpec] = None,
    stall_budget: float = 2.0,
    candidates=(1, 2, 4, 8, 16, 32, 64, 128),
    attn_impl: str = "flash",
) -> int:
    """Model-guided prefill chunk size for the Scheduler: the largest
    candidate whose mixed step (one slot prefilling `chunk` tokens, the
    rest decoding) stays within `stall_budget` x the decode-only step —
    bigger chunks finish prefill (and thus TTFT) in fewer steps, but
    every extra chunk column delays EVERY in-flight decode slot's next
    token (TPOT), so the budget caps the decode stall a prefill may
    inject. While the step is weight-stream-bound the marginal chunk
    column is nearly free and the pick is large; once compute-bound the
    pick clamps. `attn_impl` prices the chunk's attention (see
    estimate_serve_step_ms — the flash kernel's missing logits term is
    what lets the pick stay wide at long contexts). Returns at least
    candidates[0]."""
    def step_ms(n_tokens):
        return estimate_serve_step_ms(
            num_layers, hidden, inter_loc, hq_loc, hkv_loc, head_dim,
            vocab_loc, n_tokens=n_tokens, kv_tokens=kv_tokens,
            dtype=dtype, chip=chip, attn_impl=attn_impl)

    return _largest_chunk_within(step_ms, slots, stall_budget, candidates)


def _largest_chunk_within(step_ms, slots: int, stall_budget: float = 2.0,
                          candidates=(1, 2, 4, 8, 16, 32, 64, 128)) -> int:
    """The largest candidate whose mixed step (one slot prefilling a
    chunk, the rest decoding) stays within `stall_budget` x the
    decode-only step, under a family's `step_ms(n_tokens)`."""
    base = step_ms(max(slots, 1))
    best = candidates[0]
    for c in sorted(candidates):
        if step_ms(c + max(slots - 1, 0)) <= stall_budget * base:
            best = c
    return best


# the share of the HBM peak at which XLA's grouped matmul streams the
# held experts' weights at a few rows an expert (v5e; PERF.md, PR 30)
_RAGGED_DOT_HBM_SHARE = 0.2


def estimate_hybrid_step_ms(cfg, n_tokens: int, kv_tokens: int = 0,
                            chip: Optional[ChipSpec] = None) -> float:
    """`estimate_serve_step_ms` for the hybrid family
    (models/hybrid.py), from ITS sizes and block kinds: the weight stream is every
    expert the chip holds (each is read when any token takes it, and a
    step's tokens take nearly all), the mixers by kind and the head;
    the operations are those of the experts a token is routed to HERE
    (top_k x held / experts), the shared expert, the router, the
    mixers' projections and attention over the full-attention blocks
    alone. A floor, not a forecast: against the v5e it was checked
    once (PERF.md, PR 30: 73-81 ms found at 1,024 rows where this
    gives 60, and 13 before the held experts' stream was priced at
    the rate `lax.ragged_dot` was measured to reach, a fifth of the
    HBM peak at 7 rows an expert). `choose_chunk_for` only compares
    its values across chunks; the step is bound by the weights at
    every candidate, so the largest wins."""
    chip = chip or detect_chip()
    b = _dtype_bytes(cfg.dtype)
    h = cfg.hidden_size
    mixers = cfg.mixer_kinds
    lf, lm = cfg.num_kv_layers, cfg.num_moe_layers
    lw = cfg.num_window_layers  # attend a tail and the chunk, no pages
    ll = cfg.num_layers - lf - lw
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    hq = cfg.num_q_heads
    held = cfg.num_experts_held
    expert = 3 * h * cfg.moe_intermediate_size
    shared = 3 * h * cfg.shared_expert_intermediate_size + h
    dense_mlp = 3 * h * cfg.intermediate_size
    router = h * cfg.num_experts
    # a mixer's projections by kind, and what an attention block reads
    # of a cached position and multiplies a (query, position) pair by
    rank = cfg.linear_gate_rank
    row = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    mixer = {
        "gdn": h * (2 * hk * dk + 2 * hv * dv + 2 * hv) + hv * dv * h,
        "kda": h * (3 * hv * dk + 2 * rank + hv) + 2 * rank * hv * dk
        + hv * dv * h,
        "gated_attn": h * 2 * cfg.head_dim * (hq + cfg.num_kv_heads)
        + hq * cfg.head_dim * h,
        "mla": h * (hq * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
                    + row) + cfg.kv_lora_rank * hq * (
            cfg.qk_nope_head_dim + cfg.v_head_dim) + hq * cfg.v_head_dim * h,
    }
    mixer["window_attn"] = mixer["global_attn"] = (
        h * cfg.head_dim * (hq + 2 * cfg.num_kv_heads)
        + hq * cfg.head_dim * h)
    m_inner = cfg.mamba_num_heads * cfg.mamba_head_dim
    mixer["mamba2"] = (h * (2 * m_inner + 2 * cfg.mamba_state_dim
                            + cfg.mamba_num_heads) + m_inner * h)
    mixers_w = sum(mixer[kind] for kind in mixers)
    if cfg.kv_lora_rank:
        kv_row, pair = row, hq * (row + cfg.kv_lora_rank)
    else:
        kv_row = 2 * cfg.num_kv_heads * cfg.head_dim
        pair = 2 * hq * cfg.head_dim
    w_params = (lm * (shared + router) + cfg.first_k_dense * dense_mlp
                + mixers_w + h * cfg.vocab_size)
    # a state-space block's state in a delta net's place (a pattern has
    # one of the two): heads x channels x state, and as many
    # multiply-adds a token as the delta rule's one product
    if "mamba2" in mixers:
        hv, dk, dv = (cfg.mamba_num_heads, cfg.mamba_state_dim,
                      cfg.mamba_head_dim)
    state = ll * hv * dk * dv * 4 * 2  # read and written, every slot's
    mem_ms = (lm * held * expert * b / _RAGGED_DOT_HBM_SHARE + w_params * b
              + lf * kv_row * kv_tokens * b
              + state) / (chip.hbm_gbps * 1e9) * 1e3
    routed = (cfg.num_experts_per_tok * held / cfg.num_experts * expert
              if lm else 0.0)
    per_token = (lm * (routed + shared + router)
                 + cfg.first_k_dense * dense_mlp + mixers_w
                 + h * cfg.vocab_size)
    flops = 2.0 * n_tokens * per_token \
        + 2.0 * n_tokens * kv_tokens * lf * pair \
        + 2.0 * n_tokens * min(kv_tokens, cfg.sliding_window) * lw * pair \
        + 4.0 * n_tokens * ll * hv * dk * dv
    compute_ms = flops / (
        chip.bf16_tflops * 1e12 * 0.85
        * mxu_efficiency(max(n_tokens, 1024), h, h)) * 1e3
    return max(compute_ms, mem_ms)


def choose_chunk_for(cfg, world: int, slots: int, kv_tokens: int,
                     attn_impl: str = "flash") -> int:
    """The Scheduler's default chunk for a model configuration, priced
    from the sizes of ITS family: the dense formula for the dense
    family, `estimate_hybrid_step_ms` for the hybrid one."""
    if cfg.is_hybrid:
        return _largest_chunk_within(
            lambda t: estimate_hybrid_step_ms(cfg, t, kv_tokens), slots)
    return choose_prefill_chunk(
        cfg.num_layers, cfg.hidden_size, cfg.intermediate_size // world,
        cfg.num_q_heads // world, cfg.num_kv_heads // world, cfg.head_dim,
        cfg.vocab_size // world, slots=slots, kv_tokens=kv_tokens,
        dtype=cfg.dtype, attn_impl=attn_impl)


def estimate_ag_gemm_ms(
    m: int,
    k: int,
    n_cols: int,
    world: int,
    dtype=jnp.bfloat16,
    chip: Optional[ChipSpec] = None,
) -> float:
    """Fused AG+GEMM lower bound: the overlap hides whichever of comm /
    compute is shorter (ref uses this shape of bound to decide fusion is
    worth it, comm_perf_model.py:94-130)."""
    chip = chip or detect_chip()
    gemm = estimate_gemm_ms(m, n_cols, k, dtype, chip)
    ag = estimate_ag_ms(m // max(world, 1) * k * _dtype_bytes(dtype), world,
                        chip)
    return max(gemm, ag) + 0.1 * min(gemm, ag)
