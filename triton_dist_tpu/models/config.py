"""Model configuration.

TPU-native analog of the reference's ModelConfig
(ref: python/triton_dist/models/config.py:31). Carries the Qwen3-dense
geometry plus TPU partitioning knobs. Presets mirror the models the
reference benchmarks (Qwen3-8B/32B, e2e_dense.md).

A configuration is one of two families. The dense family (every block
grouped-query attention and an MLP, or experts sliced across TP:
models/dense.py) is every configuration that states no layer pattern.
The hybrid family (models/hybrid.py) is one whose blocks are data: a
mixer kind and an FFN kind a block, read off `mixer_kinds` /
`ffn_kinds`, stated as an interval (`qwen3_next_80b`, `tiny_next`), as
two lists (`kimi_linear_48b`, `tiny_kimi`) or as the source's own
`layer_types` list (`k_exaone_236b`, `tiny_exaone`: window and global
attention; `granite_4_h_micro`, `tiny_granite`: Mamba-2 and attention
with a dense MLP; `granite_4_h_small`, `tiny_granite_moe`: the same with
experts); what a page of its cache keeps of a token is `page_arrays`.
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
    # The hybrid family (models/hybrid.py): every block is a MIXER kind
    # and an FFN kind, and the pattern is data. Three ways to state it:
    # an interval (Qwen3-Next: block i is gated full attention where
    # (i + 1) % full_attention_interval == 0 and a scalar-gated delta
    # net otherwise), two lists that count from 1 as the source does
    # (Kimi-Linear: `kda_layers` channel-gated delta nets,
    # `full_attn_layers` latent attention without rotary), or the
    # source's `layer_types`, one name a block (K-EXAONE:
    # "sliding_attention" is grouped-query attention with rotary over
    # the last `sliding_window` positions, "full_attention" the same
    # heads over every position and WITHOUT rotary; Granite 4.0-H:
    # "mamba" is a Mamba-2 state-space mixer, "attention" the
    # full-attention kind again); the first
    # `first_k_dense` blocks have a dense MLP of `intermediate_size`,
    # the others experts (`first_k_dense == num_layers`: no expert
    # layer at all). 0 / () = every block the dense family's.
    # The family also means: a router over all `num_experts` of which
    # this chip holds `experts_held` from `expert_offset` on (0 held =
    # all), and a shared expert. What differs between its members is
    # stated below, each at the interval form's value.
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
    kda_layers: tuple = ()
    full_attn_layers: tuple = ()
    layer_types: tuple = ()
    sliding_window: int = 0
    first_k_dense: int = 0
    # latent attention: the cache holds kv_lora_rank + qk_rope_head_dim
    # values a token, from which every head's keys and values come
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # the channel-gated delta net's two low-rank gates
    linear_gate_rank: int = 0
    # RMSNorm's gain is (1 + w) (True) or w
    norm_zero_centred: bool = True
    # the router's form (layers/held_moe.py): scores by softmax or by
    # sigmoid, a bias added for the CHOICE alone, the chosen weights
    # divided by their sum and multiplied by the scale; the shared
    # expert under a sigmoid gate or under none
    router_score: str = "softmax"
    router_bias: bool = False
    routed_scaling_factor: float = 1.0
    shared_expert_gate: bool = True
    # the Mamba-2 mixer (layers/mamba2.py): heads of `mamba_head_dim`
    # channels over a state of `mamba_state_dim` a channel, one group
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    mamba_state_dim: int = 0
    mamba_conv_kernel_dim: int = 4
    # Granite's four multipliers, each 1 (not multiplied in) where the
    # source has none: x = m_e E[tokens]; x += m_r Layer(norm(x));
    # logits / m_l; `attention_multiplier` is the softmax scale in
    # head_dim ** -0.5's place (0 = that)
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    attention_multiplier: float = 0.0

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_hybrid(self) -> bool:
        return (self.full_attention_interval > 0 or bool(self.kda_layers)
                or bool(self.layer_types))

    @property
    def mixer_kinds(self) -> tuple:
        """The hybrid family's mixer of each block, in order: "gdn"
        (scalar-gated delta net), "kda" (channel-gated), "gated_attn",
        "mla" (latent attention), "window_attn" / "global_attn"
        (grouped-query attention over the last `sliding_window`
        positions with rotary, over all of them without), "mamba2"
        (a state-space mixer)."""
        if self.layer_types:
            kinds = {"sliding_attention": "window_attn",
                     "full_attention": "global_attn",
                     "attention": "global_attn", "mamba": "mamba2"}
            assert len(self.layer_types) == self.num_layers, (
                "layer_types must name every block once")
            assert (self.sliding_window > 0
                    or "sliding_attention" not in self.layer_types)
            return tuple(kinds[t] for t in self.layer_types)
        if self.kda_layers:
            kda, full = set(self.kda_layers), set(self.full_attn_layers)
            assert not kda & full and kda | full == set(
                range(1, self.num_layers + 1)), (
                "kda_layers and full_attn_layers must name every block "
                "once, counting from 1")
            return tuple("kda" if i + 1 in kda else "mla"
                         for i in range(self.num_layers))
        per = self.full_attention_interval
        return tuple("gated_attn" if (i + 1) % per == 0 else "gdn"
                     for i in range(self.num_layers))

    @property
    def ffn_kinds(self) -> tuple:
        """ "dense" for the leading `first_k_dense` blocks, "moe" after."""
        return tuple("dense" if i < self.first_k_dense else "moe"
                     for i in range(self.num_layers))

    @property
    def num_moe_layers(self) -> int:
        return self.num_layers - self.first_k_dense

    @property
    def page_head_dim(self) -> int:
        """The width a page keeps a kv head in. A `global_attn` block's
        head is padded with zeros to whole 128-value lanes, as a latent
        row is (the chip's kernels take no narrower slice of a page:
        `supports_flash_prefill`, d % 128), and the block pads q, k and
        v to match (`GQAttnSpec.store`); from the head size alone, so a
        head of whole lanes is kept as it is. Every other kind keeps
        the head's own width."""
        if self.layer_types and "global_attn" in self.mixer_kinds:
            return -(-self.head_dim // 128) * 128
        return self.head_dim

    @property
    def page_arrays(self) -> tuple:
        """(heads, width) of each array a page layer keeps a token in:
        keys and values a kv head (`page_head_dim` wide), or ONE latent
        row (kv_lora_rank | qk_rope_head_dim, padded with zeros to
        whole 128-value lanes: the chip's kernels take no narrower
        slice of a page) from which both come."""
        if self.kv_lora_rank:
            row = self.kv_lora_rank + self.qk_rope_head_dim
            return ((1, -(-row // 128) * 128),)
        return ((self.num_kv_heads, self.page_head_dim),) * 2

    @property
    def kv_bytes_per_token(self) -> int:
        """Pool bytes of one position in one page layer."""
        import numpy as np

        return sum(h * w for h, w in self.page_arrays) \
            * np.dtype(self.dtype).itemsize

    @property
    def num_experts_held(self) -> int:
        """Experts this chip stores (all of them unless told)."""
        return self.experts_held or self.num_experts

    @property
    def num_kv_layers(self) -> int:
        """Blocks that keep keys and values (pages in the serve pool)."""
        if not self.is_hybrid:
            return self.num_layers
        return sum(k in ("gated_attn", "mla", "global_attn")
                   for k in self.mixer_kinds)

    @property
    def num_window_layers(self) -> int:
        """Blocks that keep a fixed per-slot tail of the last
        `sliding_window` keys and values, and no pages."""
        if not self.layer_types:
            return 0
        return self.mixer_kinds.count("window_attn")

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
    def kimi_linear_48b(**kw) -> "ModelConfig":
        """Kimi-Linear-48B-A3B geometry: 27 blocks, `K K K M` six times
        and `K K M` (K a channel-gated delta net of 32 heads x 128, M
        latent attention without rotary: 32 heads over a 512 + 64
        latent row), block 1 a dense MLP of 9,216, the others 256
        experts of width 1,024 with 8 a token under a sigmoid router
        with a selection bias, scaled by 2.446, plus an ungated shared
        expert. What one chip of an expert-parallel group holds is
        `experts_held` / `expert_offset` in **kw."""
        full = (4, 8, 12, 16, 20, 24, 27)
        defaults = dict(
            vocab_size=163_840, hidden_size=2304, intermediate_size=9216,
            num_layers=27, num_q_heads=32, num_kv_heads=32, head_dim=72,
            rope_theta=10_000.0, rms_eps=1e-5, num_experts=256,
            num_experts_per_tok=8, moe_intermediate_size=1024,
            shared_expert_intermediate_size=1024,
            kda_layers=tuple(i for i in range(1, 28) if i not in full),
            full_attn_layers=full, first_k_dense=1, kv_lora_rank=512,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            linear_num_key_heads=32, linear_num_value_heads=32,
            linear_key_head_dim=128, linear_value_head_dim=128,
            linear_conv_kernel_dim=4, linear_gate_rank=128,
            norm_zero_centred=False, router_score="sigmoid",
            router_bias=True, routed_scaling_factor=2.446,
            shared_expert_gate=False,
        )
        defaults.update(kw)
        return ModelConfig(**defaults)

    @staticmethod
    def tiny_kimi(**kw) -> "ModelConfig":
        """Test-scale Kimi-Linear pattern: a leading dense block, one
        whole period and a short last one (`K K K M K M`), 8 experts."""
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=96,
            num_layers=6, num_q_heads=4, num_kv_heads=4, head_dim=16,
            rms_eps=1e-5, max_positions=64, dtype="float32",
            num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
            shared_expert_intermediate_size=32,
            kda_layers=(1, 2, 3, 5), full_attn_layers=(4, 6),
            first_k_dense=1, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16,
            linear_num_key_heads=4, linear_num_value_heads=4,
            linear_key_head_dim=16, linear_value_head_dim=16,
            linear_conv_kernel_dim=4, linear_gate_rank=8,
            norm_zero_centred=False, router_score="sigmoid",
            router_bias=True, routed_scaling_factor=2.446,
            shared_expert_gate=False,
        )
        defaults.update(kw)
        return ModelConfig(**defaults)

    @staticmethod
    def k_exaone_236b(num_layers: int = 48, **kw) -> "ModelConfig":
        """K-EXAONE-236B-A23B geometry: `L L L G` twelve times (L
        grouped-query attention over the last 128 positions with
        rotary, G over every position without; 64 q / 8 kv heads of
        128, q and k RMS-normalised a head), block 0 a dense MLP of
        18,432, the others 128 experts of width 2,048 with 8 a token
        under a sigmoid router with a selection bias, scaled by 2.5,
        plus an ungated shared expert. A cut depth keeps the pattern's
        first `num_layers` blocks; what one chip of an expert-parallel
        group holds is `experts_held` / `expert_offset` and a
        `vocab_size` slice in **kw."""
        types = ("sliding_attention",) * 3 + ("full_attention",)
        defaults = dict(
            vocab_size=153_600, hidden_size=6144, intermediate_size=18_432,
            num_q_heads=64, num_kv_heads=8, head_dim=128,
            rope_theta=1_000_000.0, rms_eps=1e-5, num_experts=128,
            num_experts_per_tok=8, moe_intermediate_size=2048,
            shared_expert_intermediate_size=2048,
            layer_types=(types * 12)[:num_layers], sliding_window=128,
            first_k_dense=1, norm_zero_centred=False,
            router_score="sigmoid", router_bias=True,
            routed_scaling_factor=2.5, shared_expert_gate=False,
        )
        defaults.update(kw)
        return ModelConfig(num_layers=num_layers, **defaults)

    @staticmethod
    def tiny_exaone(**kw) -> "ModelConfig":
        """Test-scale K-EXAONE pattern: a leading dense block and two
        whole periods (`L L L G L L L G`), a window of 8, 8 experts."""
        types = ("sliding_attention",) * 3 + ("full_attention",)
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=96,
            num_layers=8, num_q_heads=4, num_kv_heads=2, head_dim=16,
            rope_theta=1_000_000.0, rms_eps=1e-5, max_positions=64,
            dtype="float32", num_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=32, shared_expert_intermediate_size=32,
            layer_types=types * 2, sliding_window=8, first_k_dense=1,
            norm_zero_centred=False, router_score="sigmoid",
            router_bias=True, routed_scaling_factor=2.5,
            shared_expert_gate=False,
        )
        defaults.update(kw)
        return ModelConfig(**defaults)

    @staticmethod
    def granite_4_h_micro(**kw) -> "ModelConfig":
        """granite-4.0-h-micro geometry, whole: 40 blocks, `M x5 A`,
        `M x9 A` three times, `M x4` (M a Mamba-2 mixer of 64 heads x
        64 over a state of 128, A grouped-query attention over every
        position without rotary or q/k norm, 32 q / 8 kv heads of 64
        at the scale 1/64), a dense SwiGLU of 8,192 in EVERY block (no
        expert layer), tied embeddings, norms with the gain w and
        Granite's four multipliers."""
        m, a = "mamba", "attention"
        defaults = dict(
            vocab_size=100_352, hidden_size=2048, intermediate_size=8192,
            num_layers=40, num_q_heads=32, num_kv_heads=8, head_dim=64,
            rms_eps=1e-5, use_qk_norm=False, tie_word_embeddings=True,
            layer_types=((m,) * 5 + (a,) + ((m,) * 9 + (a,)) * 3
                         + (m,) * 4),
            first_k_dense=40, norm_zero_centred=False,
            mamba_num_heads=64, mamba_head_dim=64, mamba_state_dim=128,
            mamba_conv_kernel_dim=4, embedding_multiplier=12.0,
            residual_multiplier=0.22, logits_scaling=8.0,
            attention_multiplier=0.015625,
        )
        defaults.update(kw)
        return ModelConfig(**defaults)

    @staticmethod
    def tiny_granite(**kw) -> "ModelConfig":
        """Test-scale Granite 4.0-H pattern: a short first period, two
        whole ones and a last without attention (`M A`, `M M A` twice,
        `M`), heads of 16 (a page keeps them 128 wide), a state of
        16."""
        m, a = "mamba", "attention"
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=96,
            num_layers=9, num_q_heads=4, num_kv_heads=2, head_dim=16,
            rms_eps=1e-5, max_positions=64, dtype="float32",
            use_qk_norm=False, tie_word_embeddings=True,
            layer_types=(m, a) + (m, m, a) * 2 + (m,), first_k_dense=9,
            norm_zero_centred=False, mamba_num_heads=8, mamba_head_dim=16,
            mamba_state_dim=16, mamba_conv_kernel_dim=4,
            embedding_multiplier=12.0, residual_multiplier=0.22,
            logits_scaling=8.0, attention_multiplier=0.0625,
        )
        defaults.update(kw)
        return ModelConfig(**defaults)

    @staticmethod
    def granite_4_h_small(num_layers: int = 40, **kw) -> "ModelConfig":
        """granite-4.0-h-small geometry: 40 blocks, `M x5 A`, `M x9 A`
        three times, `M x4` (M a Mamba-2 mixer of 128 heads x 64 over a
        state of 128, A grouped-query attention over every position
        without rotary or q/k norm, 32 q / 8 kv heads of 128 at the
        scale 1/128); in EVERY block 72 experts of width 768, 10 a
        token under a softmax router (the top 10 logits through a
        softmax: the same weights as a softmax over all 72, its top 10
        divided by their sum), and an ungated shared MLP of 1,536; tied
        embeddings, norms with the gain w and Granite's four
        multipliers. No block is dense: `intermediate_size` is the
        shared MLP's and unused. A cut depth keeps the pattern's first
        `num_layers` blocks; what one chip of an expert-parallel group
        holds is `experts_held` / `expert_offset` and a `vocab_size`
        slice in **kw."""
        m, a = "mamba", "attention"
        types = (m,) * 5 + (a,) + ((m,) * 9 + (a,)) * 3 + (m,) * 4
        defaults = dict(
            vocab_size=100_352, hidden_size=4096, intermediate_size=1536,
            num_q_heads=32, num_kv_heads=8, head_dim=128, rms_eps=1e-5,
            use_qk_norm=False, tie_word_embeddings=True,
            layer_types=types[:num_layers], first_k_dense=0,
            norm_zero_centred=False, num_experts=72,
            num_experts_per_tok=10, moe_intermediate_size=768,
            shared_expert_intermediate_size=1536, router_score="softmax",
            shared_expert_gate=False, mamba_num_heads=128,
            mamba_head_dim=64, mamba_state_dim=128,
            mamba_conv_kernel_dim=4, embedding_multiplier=12.0,
            residual_multiplier=0.22, logits_scaling=16.0,
            attention_multiplier=0.0078125,
        )
        defaults.update(kw)
        return ModelConfig(num_layers=num_layers, **defaults)

    @staticmethod
    def tiny_granite_moe(**kw) -> "ModelConfig":
        """Test-scale granite-4.0-h-small pattern: a period twice and a
        last without attention (`M A` twice, `M M`), 8 experts of 32, 2
        a token, an ungated shared MLP of 48 in every block, heads of
        16 (a page keeps them 128 wide), a state of 16."""
        m, a = "mamba", "attention"
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=48,
            num_layers=6, num_q_heads=4, num_kv_heads=2, head_dim=16,
            rms_eps=1e-5, max_positions=64, dtype="float32",
            use_qk_norm=False, tie_word_embeddings=True,
            layer_types=(m, a) * 2 + (m, m), first_k_dense=0,
            norm_zero_centred=False, num_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=32, shared_expert_intermediate_size=48,
            router_score="softmax", shared_expert_gate=False,
            mamba_num_heads=8, mamba_head_dim=16, mamba_state_dim=16,
            mamba_conv_kernel_dim=4, embedding_multiplier=12.0,
            residual_multiplier=0.22, logits_scaling=16.0,
            attention_multiplier=0.0625,
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
