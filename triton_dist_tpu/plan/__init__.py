"""triton_dist_tpu.plan — graph-level overlap autofusion (ROADMAP item 5).

The reference's thesis is that compute/communication pairing is a
*compiler decision*: every fused pairing this repo ships (AG+GEMM,
GEMM+RS, GEMM+AR, the grouped-GEMM MoE pipeline, the SP flash prefill,
the quantized wire) used to be hand-wired at a specific call site in
`layers/` and `models/dense.py`, with the `perf_model` choosers
consulted ad hoc. This package is the ONE planning pass over all of
them (cf. arXiv 2305.06942's fused computation-collective rewriting and
ML-Triton's multi-level lowering, arXiv 2503.14985):

  ir.py       a small explicit layer-IR — op nodes (gemm / grouped-gemm
              / attention / norm / collective) with shapes, dtypes,
              sharding axis, and wire-format eligibility — plus builders
              that emit it from the dense/MoE forward structure.
  planner.py  pattern-matches producer -> collective -> consumer triples
              in the IR and prices fused-vs-sequential, wire format,
              prefill impl, tile configs, and EP chunking per triple —
              the existing `perf_model` estimators and `autotuner`
              pruners stay the pricing primitives; the planner owns the
              composition behind ONE `plan_forward(ir, world, rig)`.
  execute.py  routes the model forward through the Plan: the layer MODES
              registries (tp_attn / tp_mlp / tp_moe) are the rewrite
              targets, so `models/dense.py` carries no hand
              fused-vs-sequential branches.

Every fused rewrite must be backed by its registered `@verify.protocol`
model; a triple whose fusion has no shipped protocol skeleton falls
back to the sequential lowering LOUDLY (a warnings.warn the tests pin).
The acceptance oracle is the house discipline: planned execution is
bit-identical to the hand-routed path it selects (tier-1-pinned), and a
new naively-wired model config gets fused paths with zero layer code.

See docs/performance.md "Fusion planner" for the triple classification,
decision inputs, and fallback rules; scripts/plan_report.py renders a
plan with per-triple pricing.
"""

from triton_dist_tpu.plan.ir import (  # noqa: F401
    LayerIR,
    OpNode,
    Triple,
    build_dense_ir,
    find_triples,
)
from triton_dist_tpu.plan.planner import (  # noqa: F401
    PATTERN_PROTOCOLS,
    SEQ_SHARDED_MODES,
    Plan,
    TripleDecision,
    plan_dense_forward,
    plan_ep_chunks,
    plan_forward,
    route_prefill_impl,
)
from triton_dist_tpu.plan.execute import (  # noqa: F401
    attn_fwd,
    ffn_fwd,
    gather_tokens,
    shard_tokens,
)
