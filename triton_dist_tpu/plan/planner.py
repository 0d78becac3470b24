"""The fusion planner: price the candidate lowerings of one LayerIR and
emit a `Plan` (plan/__init__ doc; ROADMAP item 5).

The planner owns COMPOSITION, not pricing: every number it compares
comes from the existing `perf_model` estimators (estimate_ag_gemm_ms,
estimate_ag_ms/rs/ar, estimate_gemm_ms, choose_wire_format,
choose_prefill_impl, choose_ep_chunks) and the `autotuner` pruners —
those stay the single sources of truth. What used to be scattered as
hand `mode=` wiring in `models/dense.py` and `layers/tp_moe.py` is here
one decision per matched producer -> collective -> consumer triple:

  lowering   "dist" fuses AG+GEMM / GEMM+RS, "xla" runs the sequential
             lax reference, "ar" elides the gather (replicated
             activations) and fuses the reduction as GEMM+AR, and the
             MoE "fused" pipeline runs the one-kernel grouped path.
  verify     a fusion is only CHOSEN when its transport skeleton has a
             shipped `@verify.protocol` model (PATTERN_PROTOCOLS);
             otherwise the triple falls back to sequential with a
             warnings.warn the tests pin. A forced legacy mode string
             is the caller's contract and is honored bit-for-bit.
  wire       per-collective via choose_wire_format under the plan's
             error budget (the default budget 0.0 forces native wire,
             which is what keeps planned execution bit-identical to the
             hand path).
  configs    the autotuner's top-1 pruned tile config is recorded per
             fused triple as the pricing witness. What LAUNCHES is a
             separate decision: a MEASURED winner from the persistent
             tune cache (autotuner.TuneCache — same rig, shape bucket,
             dtype, world and wire only) lands in
             TripleDecision.applied_config and plan/execute threads it
             into the kernel call, re-validated by the launch VMEM
             gates (stale entries degrade loudly to the default). With
             an empty cache every applied_config is "" and execution
             compiles exactly the legacy default-tile program, so the
             bit-identity oracle still gates the unoverridden world;
             overridden launches are gated by the epsilon-band oracle
             (verify/epsilon.py) instead — tile overrides reassociate
             the fold order, so bitwise equality is the wrong contract
             there.

`plan_dense_forward` memoizes on the hashable (cfg, geometry, mode)
tuple, so the model forward, `models/engine.Engine`, the serve
`Scheduler`, and `mega.schedule_graph` all hold the SAME Plan object
for the same step shape — serving and one-shot forwards agree
on pairings by construction.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import warnings
from typing import Optional, Tuple

from triton_dist_tpu.plan.ir import LayerIR, build_dense_ir, find_triples

# The two sequence-sharded lowerings: forward slices tokens by rank on
# entry and regathers before the head. This was models/dense.py's
# inline `mode in ("dist", "xla")` predicate — now THE routing fact,
# owned by the planner and consumed via Plan.seq_sharded.
SEQ_SHARDED_MODES = ("dist", "xla")

# fusion pattern -> the @verify.protocol skeleton covering its
# transport. The grouped-GEMM (MoE) patterns ride the dense skeletons:
# the verified property is the ring-AG / ring-RS HB-graph, which the
# grouped variants share (kernels/allgather_group_gemm.py builds on the
# same per-step semaphore ladder allgather_gemm ships).
PATTERN_PROTOCOLS = {
    "ag+gemm": "allgather_gemm",
    "ag+grouped_gemm": "allgather_gemm",
    "gemm+rs": "gemm_reduce_scatter",
    "grouped_gemm+rs": "gemm_reduce_scatter",
    "gemm+ar": "allreduce",
    "a2a+grouped_gemm": "ep_dispatch_chunked",
}

# (pattern, site-prefix) -> the fused kernel plan/execute can rewrite
# to, per lowering family. The "head" site is deliberately absent:
# the logits path is numerics-critical (sampling reads it bitwise) and
# stays sequential by design.
_DIST_KERNELS = {
    ("ag+gemm", "attn"): "ag_gemm",
    ("ag+gemm", "mlp"): "ag_gemm",
    ("ag+grouped_gemm", "moe"): "ag_group_gemm",
    ("gemm+rs", "attn"): "gemm_rs",
    ("gemm+rs", "mlp"): "gemm_rs",
    ("grouped_gemm+rs", "moe"): "moe_reduce_rs",
}
_AR_KERNELS = {
    ("gemm+rs", "attn"): "gemm_ar",
    ("gemm+rs", "mlp"): "gemm_ar",
}
_FUSED_MOE_KERNELS = {
    ("ag+grouped_gemm", "moe"): "fused_ag_moe_up",
    ("grouped_gemm+rs", "moe"): "fused_moe_down_combine_rs",
}

_DENSE_MODES = ("dist", "ar", "xla")


@dataclasses.dataclass(frozen=True)
class TripleDecision:
    """One collective site's lowering under the chosen mode.

    lowered   "ag+gemm" | "gemm+rs" | "gemm+ar" | "sequential" |
              "elided" — what the site becomes.
    kernel    the fused kernel (or lax primitive) the site lowers to.
    protocol  the shipped verify skeleton backing a fused pick (None
              for sequential lowerings).
    est_fused_ms / est_seq_ms   both prices, always recorded, so the
              report can show the margin the decision rests on.
    config    autotuner top-1 tile config (pricing witness; see module
              doc).
    applied_config   the config the launch actually overrides with
              ("" = the kernel's own default tiles). Only a MEASURED
              winner from the persistent tune cache lands here
              (autotuner.TuneCache, same rig + shape-bucket + wire
              only), re-validated against the launch-fit gates at plan
              time — the model-ranked witness never launches un-measured,
              so an empty cache compiles exactly the legacy program.
    config_source    "" (default tiles) | "cache" (measured winner,
              provenance in the cache entry's round stamp).
    """

    site: str
    pattern: str
    lowered: str
    fused: bool
    kernel: str
    protocol: Optional[str]
    wire: str
    est_fused_ms: float
    est_seq_ms: float
    config: str = ""
    reason: str = ""
    applied_config: str = ""
    config_source: str = ""

    @property
    def chosen_ms(self) -> float:
        return self.est_fused_ms if self.fused else self.est_seq_ms


@dataclasses.dataclass(frozen=True)
class Plan:
    """The one object every consumer routes through (module doc).

    mode       the attention + dense-MLP lowering ("dist"|"xla"|"ar").
    moe_mode   the MoE FFN lowering ("dist"|"xla"|"ar"|"fused").
    seq_sharded  whether the forward slices tokens by rank on entry
               (mode in SEQ_SHARDED_MODES) — consumed by
               plan/execute.shard_tokens / gather_tokens.
    attn_impl  forced prefill impl ("xla"|"pallas") or None = the
               per-shape `route_prefill_impl` decision at the call
               site (still the planner's single predicate).
    """

    plan_id: str
    key: str
    world: int
    chip: str
    requested: str
    mode: str
    moe_mode: str
    seq_sharded: bool
    is_moe: bool
    attn_impl: Optional[str]
    decisions: Tuple[TripleDecision, ...]
    est_layer_ms: float
    mega_strategy: str = "least_loaded"
    # measured flash-prefill KV page height from the tune cache (None =
    # the kernel's default block; plan/execute threads it into the
    # attention prefill fold). attn_block_source mirrors
    # TripleDecision.config_source.
    attn_block: Optional[int] = None
    attn_block_source: str = ""

    @property
    def ffn_mode(self) -> str:
        """The mode string the FFN layer call executes under."""
        return self.moe_mode if self.is_moe else self.mode

    def fused_sites(self) -> Tuple[str, ...]:
        return tuple(d.site for d in self.decisions if d.fused)

    def applied_configs(self) -> dict:
        """site -> (applied_config, source) for every decision that
        launches a non-default config (plan_report's applied_config
        column; Scheduler.metrics surfaces the count)."""
        out = {d.site: (d.applied_config, d.config_source)
               for d in self.decisions if d.applied_config}
        if self.attn_block is not None:
            out["attn.core"] = (f"FlashPrefillConfig(block={self.attn_block})",
                                self.attn_block_source)
        return out

    def launch_config(self, site: str):
        """The parsed config OBJECT a site launches with, or None for
        the kernel default — the single accessor plan/execute threads
        into the layer entry points."""
        for d in self.decisions:
            if d.site == site and d.applied_config:
                from triton_dist_tpu import autotuner as at

                return at.parse_config(_config_family(d.kernel),
                                       d.applied_config)
        return None


@functools.lru_cache(maxsize=1)
def _shipped_protocols() -> frozenset:
    from triton_dist_tpu.verify import registry

    return frozenset(registry.load_shipped().keys())


def _resolve_chip(rig):
    from triton_dist_tpu import perf_model as pm

    if rig is None:
        return pm.detect_chip()
    if isinstance(rig, pm.ChipSpec):
        return rig
    if rig in pm.CHIPS:
        return pm.CHIPS[rig]
    for spec in pm.CHIPS.values():
        if spec.name == rig:
            return spec
    raise KeyError(f"unknown rig {rig!r}; expected one of "
                   f"{sorted(set(s.name for s in pm.CHIPS.values()))}")


def _top_config(pattern: str, cons_or_prod, world: int, chip) -> str:
    """The autotuner's best tile config for a fused triple (top_n=1),
    recorded as the pricing witness. Never fatal: an unpriceable shape
    returns ''."""
    from triton_dist_tpu import autotuner as at

    node = cons_or_prod
    try:
        if pattern in ("ag+gemm", "ag+grouped_gemm"):
            picks = at.prune_ag_gemm_configs(
                node.m, node.k, node.n, dtype=node.dtype, chip=chip,
                top_n=1)
        elif pattern in ("gemm+rs", "grouped_gemm+rs"):
            picks = at.prune_gemm_rs_local_configs(
                node.m, node.k, node.n, dtype=node.dtype, chip=chip,
                top_n=1)
        else:
            return ""
        return str(picks[0]) if picks else ""
    except Exception:  # noqa: BLE001 — pricing witness only; never block planning
        return ""


def _config_family(kernel: str) -> str:
    """Fused-kernel name -> the tune-cache family whose config class it
    launches with (the grouped variants ride the dense families' config
    dataclasses)."""
    if kernel in ("ag_gemm", "ag_group_gemm", "fused_ag_moe_up"):
        return "ag_gemm"
    if kernel in ("gemm_rs", "moe_reduce_rs", "fused_moe_down_combine_rs"):
        return "gemm_rs"
    if kernel == "gemm_ar":
        return "gemm_ar"
    return kernel


def _cached_config(kernel: str, node, world: int, chip, wire: str):
    """Consult the persistent tune cache for a measured winner at this
    fused site: same kernel family, shape bucket, dtype, world, wire
    format AND rig only (autotuner.TuneCache — measured beats modeled,
    never across rigs). A hit is re-validated against the launch-fit
    gates with the SAME VMEM accounting the pruner admits configs by, so
    a stale entry (code moved, chip changed) degrades LOUDLY to the
    default tiles — never to a Mosaic allocation failure. Returns
    (applied_config_repr, source): ("", "") = launch the default."""
    from triton_dist_tpu import autotuner as at

    family = _config_family(kernel)
    if min(node.m, node.k, node.n) <= 0:
        # degenerate geometry (e.g. fewer heads than ranks shards a
        # projection to zero columns) — nothing to tune, and the fit
        # gates divide by these dims
        return "", ""
    if family in ("ag_gemm", "gemm_rs", "gemm_ar"):
        bucket = at.shape_bucket(node.m, node.k, node.n)
    else:
        return "", ""
    entry = at.active_tune_cache().lookup(
        family, bucket, node.dtype, world, wire,
        at.rig_name(chip, world))
    if entry is None:
        return "", ""
    try:
        cfg = at.parse_config(family, entry["config"])
    except ValueError as e:
        warnings.warn(
            f"plan: tune-cache entry for {node.name} is unparseable "
            f"({e}); launching default tiles", stacklevel=2)
        return "", ""
    if family == "ag_gemm":
        ok = at.ag_gemm_config_fits(cfg, node.m, node.k, node.n,
                                    dtype=node.dtype, chip=chip)
    elif world <= 1:
        # the world=1 local blocked-matmul regime is what the sweeps
        # measure; the ring regimes at world>1 fit their own tiles
        ok = at.gemm_rs_local_config_fits(cfg, node.m, node.k, node.n,
                                          dtype=node.dtype, chip=chip)
    else:
        ok = True
    if not ok:
        warnings.warn(
            f"plan: cached {family} config {entry['config']!r} for "
            f"{node.name} no longer passes the launch VMEM gate at "
            f"(m={node.m}, k={node.k}, n={node.n}); launching default "
            "tiles (stale tune cache — re-run the bench sweep)",
            stacklevel=2)
        return "", ""
    return entry["config"], "cache"


def _wire_name(node, world: int, chip, error_budget: float,
               collective: str) -> str:
    if not node.wire_eligible or world <= 1:
        return "native"
    from triton_dist_tpu.perf_model import choose_wire_format

    fmt = choose_wire_format(node.bytes, world, dtype=node.dtype,
                             error_budget=error_budget,
                             collective=collective, chip=chip)
    return getattr(fmt, "kind", str(fmt))


def _decide(ir: LayerIR, tri, mode: str, moe_mode: str, world: int,
            chip, shipped, error_budget: float, forced: bool):
    """One TripleDecision under the (mode, moe_mode) lowering pair."""
    from triton_dist_tpu import perf_model as pm

    nodes = ir.nodes
    node = nodes[tri.collective]
    # the kernel family is the COMPUTE op's (the MoE block's gather is
    # named mlp.ag but feeds moe.up — the grouped kernels own it)
    comp = (nodes[tri.consumer] if tri.consumer >= 0
            else nodes[tri.producer] if tri.producer >= 0 else node)
    site = comp.name.split(".")[0]
    site_mode = moe_mode if site == "moe" else mode
    dtype = node.dtype

    def seq(lowered, kernel, f_ms, s_ms, reason, wire="native",
            config=""):
        return TripleDecision(site=node.name, pattern=tri.pattern,
                              lowered=lowered, fused=False,
                              kernel=kernel, protocol=None, wire=wire,
                              est_fused_ms=f_ms, est_seq_ms=s_ms,
                              config=config, reason=reason)

    def fused(lowered, kernel, proto, f_ms, s_ms, reason, wire,
              config, comp_node=None):
        if proto not in shipped and not forced:
            warnings.warn(
                f"plan: fusion {tri.pattern!r} at {node.name} has no "
                f"shipped verify protocol {proto!r}; falling back to "
                f"sequential", stacklevel=2)
            return seq("sequential", "lax." + (node.collective or "?"),
                       f_ms, s_ms,
                       f"unverified fusion (protocol {proto!r} not "
                       f"shipped)", wire=wire)
        if proto not in shipped:
            reason += f" [forced: protocol {proto!r} not shipped]"
            warnings.warn(
                f"plan: forced mode keeps unverified fusion "
                f"{tri.pattern!r} at {node.name} (protocol {proto!r} "
                f"not shipped)", stacklevel=2)
        applied, source = ("", "") if comp_node is None else \
            _cached_config(kernel, comp_node, world, chip, wire)
        return TripleDecision(site=node.name, pattern=tri.pattern,
                              lowered=lowered, fused=True,
                              kernel=kernel, protocol=proto, wire=wire,
                              est_fused_ms=f_ms, est_seq_ms=s_ms,
                              config=config, reason=reason,
                              applied_config=applied,
                              config_source=source)

    if tri.pattern == "unknown":
        coll_ms = (pm.estimate_ag_ms(node.bytes, world, chip)
                   if node.collective == "all_gather"
                   else pm.estimate_ar_ms(node.bytes, world, chip))
        if node.wire_eligible:
            # a fusable-looking site the matcher could not pair: the
            # loud-fallback contract (tests pin this warning)
            warnings.warn(
                f"plan: unmatched collective {node.name} "
                f"({node.collective}); lowering sequentially",
                stacklevel=2)
            reason = "unmatched collective: sequential fallback"
        else:
            reason = "terminal numerics-critical collective"
        return seq("sequential", "lax." + (node.collective or "?"),
                   coll_ms, coll_ms, reason)

    wire = _wire_name(
        node, world, chip, error_budget,
        "allgather" if node.collective == "all_gather" else "allreduce")

    if tri.pattern.startswith("ag+"):
        cons = nodes[tri.consumer]
        gemm_ms = pm.estimate_gemm_ms(cons.m, cons.n, cons.k,
                                      dtype=dtype, chip=chip)
        ag_ms = pm.estimate_ag_ms(node.bytes, world, chip)
        s_ms = ag_ms + gemm_ms
        if cons.kind == "gemm":
            f_ms = pm.estimate_ag_gemm_ms(cons.m, cons.k, cons.n,
                                          world, dtype=dtype, chip=chip)
        else:
            # grouped consumer: the gather moves tokens, not
            # token*top_k rows — bound it from the node's own payload
            f_ms = max(gemm_ms, ag_ms) + 0.1 * min(gemm_ms, ag_ms)
        if site_mode == "ar":
            return TripleDecision(
                site=node.name, pattern=tri.pattern, lowered="elided",
                fused=False, kernel="none", protocol=None,
                wire="native", est_fused_ms=gemm_ms, est_seq_ms=gemm_ms,
                reason="replicated activations: no gather under ar")
        if site_mode == "xla":
            return seq("sequential", "lax.all_gather", f_ms, s_ms,
                       "xla lowering is the sequential reference",
                       wire=wire)
        kernels = (_FUSED_MOE_KERNELS if site_mode == "fused"
                   else _DIST_KERNELS)
        kernel = kernels.get((tri.pattern, site))
        if kernel is None:
            return seq("sequential", "lax.all_gather", f_ms, s_ms,
                       "no fused rewrite for this site", wire=wire)
        cfgstr = _top_config(tri.pattern, cons, world, chip)
        return fused("ag+" + cons.kind, kernel,
                     PATTERN_PROTOCOLS[tri.pattern], f_ms, s_ms,
                     f"overlap hides min(comm, compute): "
                     f"{f_ms:.3f}ms vs {s_ms:.3f}ms sequential",
                     wire, cfgstr, comp_node=cons)

    if tri.pattern.endswith("+rs") or tri.pattern.endswith("+ar"):
        prod = nodes[tri.producer]
        gemm_ms = pm.estimate_gemm_ms(prod.m, prod.n, prod.k,
                                      dtype=dtype, chip=chip)
        rs_ms = pm.estimate_rs_ms(node.bytes, world, chip)
        ar_ms = pm.estimate_ar_ms(node.bytes, world, chip)
        if site_mode == "ar":
            s_ms = gemm_ms + ar_ms
            f_ms = max(gemm_ms, ar_ms) + 0.1 * min(gemm_ms, ar_ms)
            kernel = _AR_KERNELS.get((tri.pattern, site))
            if kernel is None:
                # the MoE ar path reduces with a plain psum today
                return seq("sequential", "lax.psum", f_ms, s_ms,
                           "no fused gemm+ar rewrite for this site",
                           wire=wire)
            cfgstr = _top_config(tri.pattern, prod, world, chip)
            return fused("gemm+ar", kernel, PATTERN_PROTOCOLS["gemm+ar"],
                         f_ms, s_ms,
                         f"replicated lowering fuses the reduction: "
                         f"{f_ms:.3f}ms vs {s_ms:.3f}ms sequential",
                         wire, cfgstr, comp_node=prod)
        s_ms = gemm_ms + rs_ms
        f_ms = max(gemm_ms, rs_ms) + 0.1 * min(gemm_ms, rs_ms)
        if site_mode == "xla":
            return seq("sequential", "lax.psum_scatter", f_ms, s_ms,
                       "xla lowering is the sequential reference",
                       wire=wire)
        kernels = (_FUSED_MOE_KERNELS if site_mode == "fused"
                   else _DIST_KERNELS)
        kernel = kernels.get((tri.pattern, site))
        if kernel is None:
            return seq("sequential", "lax.psum_scatter", f_ms, s_ms,
                       "no fused rewrite for this site", wire=wire)
        cfgstr = _top_config(tri.pattern, prod, world, chip)
        return fused(tri.pattern, kernel,
                     PATTERN_PROTOCOLS[tri.pattern], f_ms, s_ms,
                     f"overlap hides min(comm, compute): "
                     f"{f_ms:.3f}ms vs {s_ms:.3f}ms sequential",
                     wire, cfgstr, comp_node=prod)

    # a2a+grouped_gemm (the EP plane) and anything future: the EP
    # chunked pipeline is planned by plan_ep_chunks; in a layer IR it
    # lowers sequentially here
    coll_ms = pm.estimate_a2a_ms(node.bytes, world, chip=chip) \
        if hasattr(pm, "estimate_a2a_ms") else 0.0
    return seq("sequential", "lax.all_to_all", coll_ms, coll_ms,
               "EP transport planned by plan_ep_chunks", wire=wire)


def _decisions_for(ir, triples, mode, moe_mode, world, chip, shipped,
                   error_budget, forced):
    return tuple(_decide(ir, t, mode, moe_mode, world, chip, shipped,
                         error_budget, forced) for t in triples)


# norm/residual passes over the token rows per block: ~2 rms_norms and
# ~2 residual adds, each streaming read+read+write of (rows, H)
_ELEMENTWISE_PASSES = 12


def _elementwise_ms(ir: LayerIR, mode: str, world: int, chip) -> float:
    """The replicated-lowering tax the collectives ledger cannot see:
    sequence-sharded modes run norms + residuals on m/n rows, "ar"
    runs them on all m rows on every rank. This is the term that makes
    "ar" the decode pick and "dist" the prefill pick — exactly the
    engine's hand defaults."""
    from triton_dist_tpu.plan.ir import _dtype_bytes

    h = next((nd.k for nd in ir.nodes if nd.kind == "gemm"), 0)
    if not h:
        return 0.0
    rows = ir.tokens if mode == "ar" else ir.tokens // max(world, 1)
    nbytes = rows * h * _dtype_bytes(ir.nodes[0].dtype)
    return nbytes * _ELEMENTWISE_PASSES / (chip.hbm_gbps * 1e9) * 1e3


def plan_forward(ir: LayerIR, world: Optional[int] = None,
                 rig=None, mode: str = "auto",
                 attn_impl: Optional[str] = None,
                 error_budget: float = 0.0) -> Plan:
    """THE planning pass (ISSUE: one `plan_forward(ir, world, rig)`).

    mode "auto" prices the candidate lowerings and picks the cheapest;
    a legacy mode string ("dist" | "xla" | "ar" | MoE "fused") is a
    constraint honored exactly — that is the bit-identity contract with
    the hand-routed paths. Token counts not divisible by `world`
    restrict candidates to "ar" (the sequence-sharded lowerings slice
    tokens by rank). error_budget feeds choose_wire_format per
    collective; the default 0.0 forces native wire (bitwise execution).
    """
    world = ir.world if world is None else world
    chip = _resolve_chip(rig)
    shipped = _shipped_protocols()
    forced = mode != "auto"

    if mode == "fused" and not ir.is_moe:
        raise ValueError("mode='fused' is the MoE one-kernel pipeline; "
                         f"IR {ir.key} is dense")
    if mode == "auto":
        cands = (_DENSE_MODES if ir.tokens % max(world, 1) == 0
                 else ("ar",))
        scored = []
        for m in cands:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                ds = _decisions_for(ir, find_triples(ir), m, m, world,
                                    chip, shipped, error_budget, False)
            scored.append((sum(d.chosen_ms for d in ds)
                           + _elementwise_ms(ir, m, world, chip), m))
        # stable min: candidate order breaks ties toward "dist"
        picked = min(scored, key=lambda t: t[0])[1]
        chosen_mode, chosen_moe = picked, picked
    elif mode == "fused":
        # the one-kernel MoE pipeline is sequence-sharded; attention
        # rides the dist lowering beside it
        chosen_mode, chosen_moe = "dist", "fused"
    else:
        if mode not in _DENSE_MODES:
            raise ValueError(f"unknown mode {mode!r}; expected one of "
                             f"{_DENSE_MODES + ('fused', 'auto')}")
        chosen_mode, chosen_moe = mode, mode

    triples = find_triples(ir)
    decisions = _decisions_for(ir, triples, chosen_mode, chosen_moe,
                               world, chip, shipped, error_budget,
                               forced)
    est = (sum(d.chosen_ms for d in decisions)
           + _elementwise_ms(ir, chosen_mode, world, chip))
    attn_block, blk_source = _cached_attn_block(ir, world, chip)
    # applied configs enter the plan id: a cache hit compiles a
    # DIFFERENT program than the default plan, so the stamp every
    # consumer carries (Scheduler.metrics, mega Schedule) must move too
    pid = hashlib.sha1(repr((
        ir.key, world, chip.name, mode, chosen_mode, chosen_moe,
        attn_impl, error_budget,
        tuple((d.site, d.applied_config) for d in decisions
              if d.applied_config),
        attn_block,
    )).encode()).hexdigest()[:12]
    return Plan(plan_id=pid, key=ir.key, world=world, chip=chip.name,
                requested=mode, mode=chosen_mode, moe_mode=chosen_moe,
                seq_sharded=chosen_mode in SEQ_SHARDED_MODES,
                is_moe=ir.is_moe, attn_impl=attn_impl,
                decisions=decisions, est_layer_ms=est,
                attn_block=attn_block, attn_block_source=blk_source)


def _cached_attn_block(ir: LayerIR, world: int, chip):
    """Measured flash-prefill KV page height for this step shape, from
    the tune cache (same rig + shape-bucket only), re-validated against
    the kernel's fit_block + VMEM gate. (None, "") = kernel default."""
    from triton_dist_tpu import autotuner as at

    attn = next((nd for nd in ir.nodes if nd.kind == "attention"), None)
    if attn is None:
        return None, ""
    meta = dict(attn.meta or ())
    s_q, t = meta.get("s_q", 0), meta.get("t", 0)
    hq, hkv, d = meta.get("hq", 0), meta.get("hkv", 0), meta.get("d", 0)
    if not (s_q > 1 and t and hq and hkv and d):
        return None, ""  # decode / malformed meta: nothing to prefill
    entry = at.active_tune_cache().lookup(
        "flash_prefill", at.shape_bucket(s_q, t, hq, hkv, d),
        attn.dtype, world, "native", at.rig_name(chip, world))
    if entry is None:
        return None, ""
    try:
        cfg = at.parse_config("flash_prefill", entry["config"])
    except ValueError as e:
        warnings.warn(
            f"plan: tune-cache flash_prefill entry is unparseable "
            f"({e}); launching default block", stacklevel=2)
        return None, ""
    if not at.flash_prefill_config_fits(cfg, s_q, t, hq, hkv, d,
                                        dtype=attn.dtype,
                                        batch=meta.get("batch", 1),
                                        chip=chip):
        warnings.warn(
            f"plan: cached flash_prefill block {cfg.block} no longer "
            f"passes the launch VMEM gate at (s_q={s_q}, t={t}); "
            "launching default block (stale tune cache)", stacklevel=2)
        return None, ""
    return int(cfg.block), "cache"


@functools.lru_cache(maxsize=512)
def _plan_dense_cached(cfg, batch, seq, world, mode, attn_impl, kv_len,
                       rig, error_budget, tune_gen):
    ir = build_dense_ir(cfg, batch, seq, world, kv_len=kv_len)
    return plan_forward(ir, world=world, rig=rig, mode=mode,
                        attn_impl=attn_impl, error_budget=error_budget)


def plan_dense_forward(cfg, batch: int, seq: int, world: int,
                       mode: str = "auto",
                       attn_impl: Optional[str] = None,
                       kv_len: Optional[int] = None,
                       rig: Optional[str] = None,
                       error_budget: float = 0.0) -> Plan:
    """Plan one `models/dense.forward` step shape. Memoized on the
    hashable ModelConfig + geometry, so every consumer of the same step
    shape holds the SAME Plan object (module doc) and planning inside a
    traced function costs a dict lookup. The tune-cache generation
    enters the memo key: a plan built before the cache was populated
    (or swapped by a test/bench arm) never masks a measured winner."""
    from triton_dist_tpu import autotuner as at

    if rig is None:
        rig = _resolve_chip(None).name
    return _plan_dense_cached(cfg, batch, seq, world, mode, attn_impl,
                              kv_len, rig, error_budget,
                              at.tune_cache_generation())


def plan_ep_chunks(m: int, hidden: int, inter: int, e_loc: int, n: int,
                   top_k: int, capacity: Optional[int] = None,
                   dtype=None, payload_dtype=None, chip=None,
                   overlap: bool = False) -> int:
    """ONE EP chunking entry (the a2a+grouped_gemm plane):
    `layers/ep_moe.py`'s n_chunks auto path routes here so the planner
    owns the composition; `perf_model.choose_ep_chunks` stays the
    pricing primitive. A measured winner in the tune cache (kernel
    "ep_moe", same rig + shape bucket) beats the modeled pick — the
    chunk count is re-fitted by the kernel's own fit_chunks at launch,
    so a stale entry degrades to a legal schedule, never a crash."""
    import jax.numpy as jnp

    from triton_dist_tpu import autotuner as at
    from triton_dist_tpu.perf_model import choose_ep_chunks

    entry = at.active_tune_cache().lookup(
        "ep_moe", at.shape_bucket(m, hidden, inter, e_loc, top_k),
        jnp.bfloat16 if dtype is None else dtype, n, "native",
        at.rig_name(chip, n))
    if entry is not None:
        try:
            return int(at.parse_config("ep_moe", entry["config"]).n_chunks)
        except ValueError as e:
            warnings.warn(
                f"plan: tune-cache ep_moe entry is unparseable ({e}); "
                "using the modeled chunk count", stacklevel=2)
    return choose_ep_chunks(
        m, hidden, inter, e_loc, n, top_k, capacity=capacity,
        dtype=jnp.bfloat16 if dtype is None else dtype,
        payload_dtype=payload_dtype, chip=chip, overlap=overlap)


def route_gated_attention(b: int, s: int, t: int, hq: int, hkv: int,
                          d: int, dtype,
                          v_prefix: Optional[int] = None) -> str:
    """The attention implementation of the hybrid family's softmax
    blocks (models/hybrid.py), decided once when its serve step is
    built and never left to fall through: the gated full attention
    (`hq` / `hkv` heads of size `d`), and with `v_prefix` the latent
    one (layers/latent_attn.py: `hq` query heads of width `d` over ONE
    shared head whose first `v_prefix` columns are the values, the
    heads stacked into the rows and each latent page read once). On
    the chip that is the Pallas flash-prefill kernel ("pallas") or an
    error: a head shape the kernel does not take, per-grid-step
    residents over the VMEM ceiling, or a step of one query row (which
    `gqa_attention` never hands to the kernel) would otherwise compile
    a dense (S, T) float32 logits chain in silence. Under the
    interpreter (the CPU mesh of the tests) it is the XLA formulation,
    as for every auto route (`flash_prefill_native_ok`)."""
    from triton_dist_tpu.kernels.flash_prefill import (
        flash_prefill_fits,
        supports_flash_prefill,
    )
    from triton_dist_tpu.lang import use_interpret

    if use_interpret():
        return "xla"
    what = (f"{hq} q / {hkv} kv heads of size {d}" if v_prefix is None
            else f"{hq} q heads over a latent row of {d} with {v_prefix} "
                 "value columns")
    if s < 2:
        raise NotImplementedError(
            "one query row never reaches flash-prefill (gqa_attention "
            "takes it through the dense chain), and the hybrid family's "
            "attention blocks have no other route on the chip: its serve "
            "step keeps the chunk's width (Engine.serve_widths)")
    if not supports_flash_prefill(hq, hkv, d, v_prefix=v_prefix):
        raise NotImplementedError(
            f"flash-prefill does not take {what}, and the hybrid family's "
            "attention blocks have no other route on the chip")
    if not flash_prefill_fits(s, t, hq, hkv, d, dtype=dtype,
                              v_prefix=v_prefix):
        raise NotImplementedError(
            f"flash-prefill's residents for {s} rows of {what} over {t} "
            "positions pass the VMEM ceiling, and the hybrid family's "
            "attention blocks have no other route on the chip")
    return "pallas"


def route_hybrid_attention(cfg, b: int, s: int, t: int) -> str:
    """The one routing decision of a hybrid configuration's serve
    step: its attention blocks' implementation, from the widths of
    their kind (a latent family's one page array is its one head; a
    head narrower than a lane tile reaches the kernel as wide as its
    page keeps it, `ModelConfig.page_head_dim`)."""
    if cfg.kv_lora_rank:
        (hkv, d), = cfg.page_arrays
        return route_gated_attention(b, s, t, cfg.num_q_heads, hkv, d,
                                     cfg.dtype, v_prefix=cfg.kv_lora_rank)
    return route_gated_attention(b, s, t, cfg.num_q_heads,
                                 cfg.num_kv_heads, cfg.page_head_dim,
                                 cfg.dtype)


def route_window_attention(cfg, b: int, s: int) -> str:
    """The route of a hybrid configuration's WINDOW blocks
    (layers/gqa_attn.py `window_attn_fwd`): the same kernel under the
    same checks as its global blocks, over the `sliding_window + s`
    positions a step attends (the slot's tail and the chunk) and never
    more; the kernel takes the window's lower bound itself
    (`flash_prefill_local(window=)`)."""
    assert cfg.sliding_window > 0
    return route_gated_attention(b, s, cfg.sliding_window + s,
                                 cfg.num_q_heads, cfg.num_kv_heads,
                                 cfg.head_dim, cfg.dtype)


def route_prefill_impl(b: int, s: int, t: int, hq: int, hkv: int,
                       d: int, dtype) -> str:
    """THE prefill-impl routing predicate ("pallas" | "xla"): native
    gate (kernels.flash_prefill.flash_prefill_native_ok — interpret
    stays xla for CPU bit-stability), the VMEM-fit gate, then the
    perf-model pick (perf_model.choose_prefill_impl). Moved here from
    layers/attention.py so the planner owns every impl decision;
    `layers.attention._route_prefill_impl` delegates."""
    from triton_dist_tpu.kernels.flash_prefill import (
        flash_prefill_fits,
        flash_prefill_native_ok,
    )

    if not flash_prefill_native_ok(hq, hkv, d):
        return "xla"
    if not flash_prefill_fits(s, t, hq, hkv, d, dtype=dtype):
        # per-grid-step state beyond the VMEM ceiling: the blockwise
        # xla path handles arbitrarily long context; auto must never
        # route into a Mosaic allocation failure
        return "xla"
    from triton_dist_tpu.perf_model import choose_prefill_impl

    return ("pallas" if choose_prefill_impl(s, t, hq, hkv, d, batch=b,
                                            dtype=dtype) == "flash"
            else "xla")
