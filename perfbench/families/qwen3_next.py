"""The Qwen3-Next family (periods of gated-delta-net blocks and one
gated-attention block, a router over all experts with the chip's share
held whole, a shared expert), between a configuration file's published
keys and the program.

A configuration of this family stands for ONE chip of an expert-parallel
group: `num_experts` in its file is what the chip holds, the router's
width and the first held expert's id are under `expert_parallel`."""

# the keys no configuration of this family may cut (perfbench/contract.py)
WIDTHS = ("hidden_size", "head_dim", "num_attention_heads",
          "num_key_value_heads", "intermediate_size",
          "moe_intermediate_size", "shared_expert_intermediate_size",
          "num_experts_per_tok", "linear_key_head_dim",
          "linear_value_head_dim", "linear_num_key_heads",
          "linear_num_value_heads", "linear_conv_kernel_dim",
          "partial_rotary_factor", "full_attention_interval")


def model_config(cfg: dict):
    """The program's ModelConfig; the serving horizon bounds the rope
    table."""
    from triton_dist_tpu.models import ModelConfig

    ep = cfg["expert_parallel"]
    return ModelConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_q_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        max_positions=cfg["serve"]["max_len"], dtype=cfg["torch_dtype"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        num_experts=ep["router_width"], experts_held=cfg["num_experts"],
        expert_offset=ep["expert_offset"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_expert_intermediate_size=cfg[
            "shared_expert_intermediate_size"],
        full_attention_interval=cfg["full_attention_interval"],
        partial_rotary_factor=cfg["partial_rotary_factor"],
        linear_num_key_heads=cfg["linear_num_key_heads"],
        linear_num_value_heads=cfg["linear_num_value_heads"],
        linear_key_head_dim=cfg["linear_key_head_dim"],
        linear_value_head_dim=cfg["linear_value_head_dim"],
        linear_conv_kernel_dim=cfg["linear_conv_kernel_dim"])


def size_vars(cfg: dict) -> dict:
    """The sizes a work formula may name (perfbench/work.py): L blocks
    of which Lf full attention and Ll delta net; E experts routed over,
    Eh held, k a token, widths Im and Is (shared); Hk / Hv delta-net
    key / value heads of dk / dv; K the convolution's width."""
    bytes_of = {"bfloat16": 2, "float16": 2, "float32": 4}
    L = cfg["num_hidden_layers"]
    lf = L // cfg["full_attention_interval"]
    return dict(
        L=L, Lf=lf, Ll=L - lf, H=cfg["hidden_size"], V=cfg["vocab_size"],
        hq=cfg["num_attention_heads"], hkv=cfg["num_key_value_heads"],
        d=cfg["head_dim"], E=cfg["expert_parallel"]["router_width"],
        Eh=cfg["num_experts"], k=cfg["num_experts_per_tok"],
        Im=cfg["moe_intermediate_size"],
        Is=cfg["shared_expert_intermediate_size"],
        Hk=cfg["linear_num_key_heads"], Hv=cfg["linear_num_value_heads"],
        dk=cfg["linear_key_head_dim"], dv=cfg["linear_value_head_dim"],
        K=cfg["linear_conv_kernel_dim"], tp=cfg["serve"]["tp"],
        b=bytes_of[cfg["torch_dtype"]])
