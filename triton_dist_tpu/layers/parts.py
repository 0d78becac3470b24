"""The parts of a served step, as names on the device's work.

`part(name)` is `jax.named_scope("tdt." + name)`: every operation
traced inside it carries the name in its `op_name` metadata, through
the compiler's fusions (a fusion takes its root's) and into a profile's
events, where `scripts/trace_report.py --device-parts` reads it back.
A scope exists at trace time only: the compiled program is the same
instruction for instruction (PERF.md, PR 40), and JAX's persistent
compile cache leaves metadata out of its key, so an executable cached
before a scope was written is loaded without it.

`PARTS` is the closed list. It partitions the step: a name is opened
where its work is stated and nowhere else, and the innermost one an
operation lies in is its part. It lives beside the layers because
`layers/` cannot import `models/`.

  embed         the token rows' embedding
  pool.gather   what a slot carries, read: a layer's pages through the
                table (`KVCache.layer_view`), a block's row of the
                recurrent and convolution state, of the window tails
  pool.scatter  the same, written: the step's rows into their pages
                (`KVCache.scatter_step`), the tails shifted, the state
                and tails restacked for the pool
  attn.proj     attention's projections in (with the block's norm
                before them) and out (with the output gate)
  attn.core     head norms, rotary, the rows laid into the view, the
                attention itself (`_fp_local_kernel` or XLA's chain)
  mixer.proj    a delta-net or state-space mixer's projections in
                (with the block's norm) and out (with its gated norm)
  mixer.conv    its causal convolution and the carried inputs
  mixer.rule    the delta rule proper (gates, L2 norms, the chunked
                products, the scan over the state) or the selective
                scan (decays, the chunk's products, the state's update)
  ffn.dense     a SwiGLU MLP with the norm before it
  moe.route     the norm before an expert block, router scores, the
                top-k, which pairs are held here
  moe.dispatch  the sort by expert, group sizes, the sorted rows'
                gather, the visits' metadata
  moe.experts   the two grouped products and `silu_mul` between them
  moe.combine   the un-sort, the weights, the sum, the residual
  moe.shared    the shared expert and its gate
  head          the rows the head reads, the final norm, the projection
  sample        greedy and sampled tokens from the logits
"""

from __future__ import annotations

import jax

PREFIX = "tdt."
PARTS = (
    "embed", "pool.gather", "pool.scatter", "attn.proj", "attn.core",
    "mixer.proj", "mixer.conv", "mixer.rule", "ffn.dense", "moe.route",
    "moe.dispatch", "moe.experts", "moe.combine", "moe.shared", "head",
    "sample",
)
_KNOWN = frozenset(PARTS)


def part(name: str):
    """The scope of one part (a context manager, trace time only)."""
    if name not in _KNOWN:
        raise ValueError(f"{name!r} is no part of the step: {PARTS}")
    return jax.named_scope(PREFIX + name)
