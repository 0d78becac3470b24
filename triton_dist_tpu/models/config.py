"""Model configuration.

TPU-native analog of the reference's ModelConfig
(ref: python/triton_dist/models/config.py:31). Carries the Qwen3-dense
geometry plus TPU partitioning knobs. Presets mirror the models the
reference benchmarks (Qwen3-8B/32B, e2e_dense.md).
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    vocab_size: int = 151_936
    hidden_size: int = 5120
    intermediate_size: int = 25_600
    num_layers: int = 64
    num_q_heads: int = 64
    num_kv_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 1_000_000.0
    rms_eps: float = 1e-6
    max_positions: int = 4096
    dtype: str = "bfloat16"
    # qk-norm (Qwen3 applies rmsnorm over head_dim to q and k)
    use_qk_norm: bool = True
    tie_word_embeddings: bool = False
    # MoE (0 experts = dense; ref: models/qwen_moe.py Qwen3MoE)
    num_experts: int = 0
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 0
    # Hybrid layer pattern (Qwen3-Next; models/qwen3_next.py). Block i is
    # gated full attention where (i + 1) % full_attention_interval == 0
    # and a gated delta net otherwise; 0 = every block the dense
    # family's attention. The hybrid family also means: RMSNorm with
    # gain (1 + w), an output gate on attention, rotary over
    # `partial_rotary_factor` of the head, a router over all
    # `num_experts` of which this chip holds `experts_held` from
    # `expert_offset` on (0 held = all), and a shared expert.
    full_attention_interval: int = 0
    partial_rotary_factor: float = 1.0
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    shared_expert_intermediate_size: int = 0
    experts_held: int = 0
    expert_offset: int = 0

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_hybrid(self) -> bool:
        return self.full_attention_interval > 0

    @property
    def num_experts_held(self) -> int:
        """Experts this chip stores (all of them unless told)."""
        return self.experts_held or self.num_experts

    @property
    def num_kv_layers(self) -> int:
        """Blocks that keep keys and values (pages in the serve pool)."""
        if not self.is_hybrid:
            return self.num_layers
        return self.num_layers // self.full_attention_interval

    # The published presets fix every WIDTH; depth (`num_layers`) is the
    # one cut a single chip may force, so it is the presets' only
    # geometry parameter — everything else in **kw is a non-geometry
    # knob (max_positions, dtype, ...).

    @staticmethod
    def qwen3_32b(num_layers: int = 64, **kw) -> "ModelConfig":
        """Qwen3-32B geometry (the reference's headline e2e model,
        ref: docs/getting-started/e2e/e2e_dense.md)."""
        return ModelConfig(
            vocab_size=151_936, hidden_size=5120, intermediate_size=25_600,
            num_layers=num_layers, num_q_heads=64, num_kv_heads=8,
            head_dim=128, **kw,
        )

    @staticmethod
    def qwen3_8b(num_layers: int = 36, **kw) -> "ModelConfig":
        return ModelConfig(
            vocab_size=151_936, hidden_size=4096, intermediate_size=12_288,
            num_layers=num_layers, num_q_heads=32, num_kv_heads=8,
            head_dim=128, **kw,
        )

    @staticmethod
    def qwen3_30b_a3b(num_layers: int = 48, **kw) -> "ModelConfig":
        """Qwen3-30B-A3B MoE geometry (the reference's Qwen3MoE model,
        ref: models/qwen_moe.py:50-206)."""
        return ModelConfig(
            vocab_size=151_936, hidden_size=2048, intermediate_size=6144,
            num_layers=num_layers, num_q_heads=32, num_kv_heads=4,
            head_dim=128, num_experts=128, num_experts_per_tok=8,
            moe_intermediate_size=768, **kw,
        )

    @staticmethod
    def qwen3_next_80b(num_layers: int = 48, **kw) -> "ModelConfig":
        """Qwen3-Next-80B-A3B geometry: periods of three gated-delta-net
        blocks and one gated-attention block, 512 experts of width 512
        with 10 a token plus a shared one. What one chip of an
        expert-parallel group holds is `experts_held` / `expert_offset`
        and a `vocab_size` slice in **kw."""
        defaults = dict(
            vocab_size=151_936, hidden_size=2048, intermediate_size=5120,
            num_q_heads=16, num_kv_heads=2, head_dim=256,
            rope_theta=10_000_000.0, num_experts=512,
            num_experts_per_tok=10, moe_intermediate_size=512,
            full_attention_interval=4, partial_rotary_factor=0.25,
            linear_num_key_heads=16, linear_num_value_heads=32,
            linear_key_head_dim=128, linear_value_head_dim=128,
            linear_conv_kernel_dim=4, shared_expert_intermediate_size=512,
        )
        defaults.update(kw)
        return ModelConfig(num_layers=num_layers, **defaults)

    @staticmethod
    def tiny_next(**kw) -> "ModelConfig":
        """Test-scale hybrid config: one period, 8 experts."""
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_layers=4, num_q_heads=4, num_kv_heads=2, head_dim=32,
            rope_theta=10_000_000.0, max_positions=64, dtype="float32",
            num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
            full_attention_interval=4, partial_rotary_factor=0.25,
            linear_num_key_heads=2, linear_num_value_heads=4,
            linear_key_head_dim=16, linear_value_head_dim=16,
            linear_conv_kernel_dim=4, shared_expert_intermediate_size=32,
        )
        defaults.update(kw)
        return ModelConfig(**defaults)

    @staticmethod
    def tiny_moe(**kw) -> "ModelConfig":
        """Test-scale MoE config."""
        defaults = dict(
            vocab_size=256, hidden_size=128, intermediate_size=256,
            num_layers=2, num_q_heads=16, num_kv_heads=8, head_dim=32,
            max_positions=64, dtype="float32",
            num_experts=4, num_experts_per_tok=2, moe_intermediate_size=64,
        )
        defaults.update(kw)
        return ModelConfig(**defaults)

    @staticmethod
    def tiny(**kw) -> "ModelConfig":
        """Test-scale config (CPU-mesh parity tests)."""
        defaults = dict(
            vocab_size=256, hidden_size=128, intermediate_size=256,
            num_layers=2, num_q_heads=16, num_kv_heads=8, head_dim=32,
            max_positions=64, dtype="float32",
        )
        defaults.update(kw)
        return ModelConfig(**defaults)
