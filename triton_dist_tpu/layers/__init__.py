"""Model layers — parallelism strategies over the kernel library.

TPU-native analog of the reference's layer zoo
(ref: python/triton_dist/layers/nvidia/: TP_Attn, TP_MLP, TP_MoE,
EPAll2AllLayer, SpGQAFlashDecodeAttention, CommOp). Layers are pure
per-device functions designed to run inside `jax.shard_map` with params as
pytrees — the functional JAX idiom replacing the reference's stateful torch
modules; each carries the same three-mode switch (xla / dist / ar).
"""

from triton_dist_tpu.layers.norm import rms_norm  # noqa: F401
from triton_dist_tpu.layers.rope import rope_table, apply_rope  # noqa: F401
from triton_dist_tpu.layers.attention import (  # noqa: F401
    gqa_attention,
    gqa_attention_blockwise,
)
from triton_dist_tpu.layers.tp_mlp import (  # noqa: F401
    TPMLPParams,
    tp_mlp_fwd,
    tp_mlp_xla_fwd,
    tp_mlp_dist_fwd,
    tp_mlp_ar_fwd,
)
from triton_dist_tpu.layers.tp_attn import (  # noqa: F401
    TPAttnParams,
    TPAttnSpec,
    tp_attn_fwd,
    tp_attn_xla_fwd,
    tp_attn_dist_fwd,
    tp_attn_ar_fwd,
)
from triton_dist_tpu.layers.p2p import PPCommOp, pp_schedule_fwd  # noqa: F401
from triton_dist_tpu.layers.tp_moe import TPMoEParams, tp_moe_fwd  # noqa: F401
from triton_dist_tpu.layers.ep_moe import (  # noqa: F401
    EPMoEParams,
    ep_moe_fwd,
    ep_moe_ref,
)
from triton_dist_tpu.layers.sp_flash_decode import (  # noqa: F401
    SpDecodeParams,
    SpDecodeSpec,
    sp_cache_write,
    sp_decode_attn_fwd,
)
from triton_dist_tpu.layers.gated_delta_net import (  # noqa: F401
    GDNParams,
    GDNSpec,
    gated_delta_net_fwd,
)
from triton_dist_tpu.layers.mamba2 import (  # noqa: F401
    Mamba2Params,
    Mamba2Spec,
    mamba2_fwd,
)
from triton_dist_tpu.layers.gated_attn import (  # noqa: F401
    GatedAttnParams,
    GatedAttnSpec,
    gated_attn_fwd,
)
from triton_dist_tpu.layers.held_moe import (  # noqa: F401
    HeldMoEParams,
    held_moe_counted,
    held_moe_fwd,
)
