"""Expert layer of a chip that HOLDS some of the experts whole.

Expert parallelism as one chip sees it: the router scores all
`n_experts`, each token takes its `top_k` with their weights divided
by their sum, and this chip computes the part of the sum that falls on
the `held` experts it stores, `offset` being the first one's id. A
(token, choice) pair whose expert is absent adds nothing here: its
term is the holder's to compute and the exchange's to bring, and no
code stands in for either. A shared expert is computed for every token
on every chip. On one chip there is no exchange at all.

The router's FORM is the configuration's (`RouterForm`): scores by
softmax over the experts or by sigmoid, each on its own; a bias
(`router_bias`, a parameter) added for the choice and not for the
weight; the chosen weights multiplied by a scale after the division;
the shared expert under a sigmoid gate (`w_sgate`) or, with no such
parameter, under none.

The held experts' products are the grouped matmul
(`kernels.grouped_gemm`: on the chip the Pallas kernel
`_moe_gmm_kernel`, which streams each non-empty group's weights once
a row tile; `lax.ragged_dot` under the interpreter) over the pairs
sorted by expert; absent pairs and padding rows sort behind every
group, are multiplied by nothing and hold nothing a caller may read
(the kernel visits no tile behind the groups). The combine
(`kernels.moe_combine`: on the chip the Pallas kernel
`_moe_combine_kernel`, which reads each of the groups' rows once and
no row behind them; the jnp un-sort of every pair under the
interpreter) sums each token's rows in the order of its choices.

  w_router (H, E) · w_gate_up (held, H, 2 I) gate | up · w_down
  (held, I, H) · ws_gate_up (H, 2 Is) · ws_down (Is, H) · w_sgate (H,)
  or None · router_bias (E,) or None

A model that scans its layers hands the expert stacks of ALL layers,
(layers, held, ...), and `layer`, the traced index of this one: the
grouped matmul then runs over layers x held groups of which only this
layer's are not empty, so that no layer's experts are sliced out of
the stack (a slice that feeds a grouped matmul is a copy of every
expert's weights, every step).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from triton_dist_tpu.kernels.grouped_gemm import (
    grouped_gemm,
    grouped_gemm_tile_rows,
)
from triton_dist_tpu.kernels.moe_combine import moe_combine
from triton_dist_tpu.kernels.moe_utils import (
    silu_mul,
    sort_by_expert,
    topk_routing,
)
from triton_dist_tpu.layers.parts import part


class HeldMoEParams(NamedTuple):
    w_router: jax.Array
    w_gate_up: jax.Array
    w_down: jax.Array
    ws_gate_up: jax.Array
    ws_down: jax.Array
    w_sgate: Optional[jax.Array] = None
    router_bias: Optional[jax.Array] = None


class RouterForm(NamedTuple):
    score: str = "softmax"  # or "sigmoid"
    scale: float = 1.0


def swiglu_fwd(x, w_gate_up, w_down):
    """down(silu(gate) * up) of one SwiGLU MLP, float32: the shared
    expert here, and a hybrid model's dense block."""
    h = jnp.dot(x, w_gate_up, preferred_element_type=jnp.float32)
    return jnp.dot(silu_mul(h).astype(x.dtype), w_down,
                   preferred_element_type=jnp.float32)


def held_moe_fwd(x, valid, p: HeldMoEParams, top_k: int, offset: int,
                 layer=None, router: RouterForm = RouterForm()):
    """x (M, H); valid (M,) bool, the rows that are real tokens.
    Returns (y (M, H), pairs_here, pairs_absent): the held experts'
    part plus the shared expert, and how many of the valid rows'
    (token, choice) pairs fell on a held and on an absent expert.
    With `layer`, the expert stacks are all layers' (module doc)."""
    return held_moe_counted(x, valid, p, top_k, offset, layer, router)[:3]


def held_moe_counted(x, valid, p: HeldMoEParams, top_k: int, offset: int,
                     layer=None, router: RouterForm = RouterForm()):
    """`held_moe_fwd` and three more results: `tile_rows`, the rows of
    the tiles its two grouped matmuls visit (`grouped_gemm_tile_rows`;
    `pairs_here` x 2 over it is the share of multiplied rows that are
    real, 0 rows on the `ragged_dot` route); `combine_rows`, the rows of
    the second product the combine read (`moe_combine`: `pairs_here`
    on the kernel's route, every pair on the jnp one); `combine_pairs`,
    the M x top_k pairs it combined."""
    # the parts (layers/parts.py) are named where the work stands: no
    # operation moved for a name's sake
    m, _ = x.shape
    w_gate_up, w_down = p.w_gate_up, p.w_down
    held = w_gate_up.shape[-3]
    with part("moe.route"):
        logits = jnp.dot(x.astype(jnp.float32),
                         p.w_router.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        weights, ids = topk_routing(logits, top_k, score=router.score,
                                    bias=p.router_bias, scale=router.scale)
        local = ids - offset
        held_here = (local >= 0) & (local < held)
        here = held_here & valid[:, None]
    with part("moe.dispatch"):
        # one group behind the held experts takes what is not computed
        # here
        sort = sort_by_expert(jnp.where(here, local, held), held + 1)
        sizes = sort.group_sizes[:held]
        n_here = jnp.sum(sizes)
        if layer is not None:
            layers = w_gate_up.shape[0]
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((layers * held,), sizes.dtype), sizes,
                (layer * held,))
            w_gate_up = w_gate_up.reshape((-1,) + w_gate_up.shape[2:])
            w_down = w_down.reshape((-1,) + w_down.shape[2:])
    pairs = m * top_k
    with part("moe.route"):
        mine = jnp.where(here, weights, 0.0)

    # the held experts' weighted sum; each token's terms are summed in
    # the order of its choices, so a row's result is the same bit for
    # bit whatever else rides the step
    with part("moe.dispatch"):
        xs = x[sort.token_idx]
    with part("moe.experts"):
        h = grouped_gemm(xs, w_gate_up, sizes)
        act = silu_mul(h).astype(x.dtype)
        y = grouped_gemm(act, w_down, sizes)  # in the model's dtype
    with part("moe.dispatch"):  # the visits' metadata, counted
        tile_rows = (grouped_gemm_tile_rows(xs, w_gate_up, sizes)
                     + grouped_gemm_tile_rows(act, w_down, sizes))
    with part("moe.combine"):
        out, combine_rows = moe_combine(y, sort.unsort_idx, n_here, mine)

    with part("moe.shared"):
        shared = swiglu_fwd(x, p.ws_gate_up, p.ws_down)
        if p.w_sgate is not None:
            shared = jax.nn.sigmoid(jnp.dot(
                x.astype(jnp.float32), p.w_sgate.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST))[:, None] * shared
    with part("moe.combine"):
        out = out + shared
    with part("moe.route"):
        pairs_here = jnp.sum(here, dtype=jnp.int32)
        pairs_absent = jnp.sum(valid[:, None] & ~held_here, dtype=jnp.int32)
    with part("moe.combine"):
        return (out.astype(x.dtype), pairs_here, pairs_absent, tile_rows,
                combine_rows, jnp.int32(pairs))
