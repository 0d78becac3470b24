"""The Kimi-Linear family (channel-gated delta-net blocks and latent
attention blocks without rotary from two lists, a leading dense block,
a sigmoid router over all experts with the chip's share held whole, an
ungated shared expert), between a configuration file's published keys
and the program.

A configuration of this family stands for ONE chip of an expert-parallel
group: `num_experts` in its file is what the chip holds, the router's
width and the first held expert's id are under `expert_parallel`."""

# the keys no configuration of this family may cut (perfbench/contract.py);
# `linear_attn_config` whole: it holds the layer pattern and the delta
# net's heads, head size and convolution width
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "num_attention_heads", "num_key_value_heads", "head_dim",
          "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
          "v_head_dim", "num_experts_per_token", "num_shared_experts",
          "routed_scaling_factor", "first_k_dense_replace",
          "linear_attn_config")

# a latent row as the pool stores it: whole 128-value lanes
LANES = 128


def _page_row(cfg: dict) -> int:
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return -(-row // LANES) * LANES


def model_config(cfg: dict):
    """The program's ModelConfig; the serving horizon bounds the pool's
    table. What the source's `config` leaves open is the file's
    `assumed`: the gates' low rank is the delta net's head size."""
    from triton_dist_tpu.models import ModelConfig

    assert cfg["moe_router_activation_func"] == "sigmoid"
    assert cfg["moe_renormalize"] and cfg["mla_use_nope"]
    assert cfg["q_lora_rank"] is None and cfg["num_expert_group"] == 1
    ep, lin = cfg["expert_parallel"], cfg["linear_attn_config"]
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
        num_experts_per_tok=cfg["num_experts_per_token"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_expert_intermediate_size=cfg["num_shared_experts"]
        * cfg["moe_intermediate_size"],
        kda_layers=tuple(lin["kda_layers"]),
        full_attn_layers=tuple(lin["full_attn_layers"]),
        first_k_dense=cfg["first_k_dense_replace"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        linear_num_key_heads=lin["num_heads"],
        linear_num_value_heads=lin["num_heads"],
        linear_key_head_dim=lin["head_dim"],
        linear_value_head_dim=lin["head_dim"],
        linear_conv_kernel_dim=lin["short_conv_kernel_size"],
        linear_gate_rank=lin["head_dim"], norm_zero_centred=False,
        router_score="sigmoid", router_bias=True,
        routed_scaling_factor=cfg["routed_scaling_factor"],
        shared_expert_gate=False)


def size_vars(cfg: dict) -> dict:
    """The sizes a work formula may name (perfbench/work.py): L blocks
    of which Lk channel-gated delta nets, Lf latent attention and Ld
    with a dense MLP of width I (the other Lm with experts); E experts
    routed over, Eh held, k a token, widths Im and Is (shared); Hl
    delta-net heads of dk = dv; K the convolution's width; r the
    gates' low rank; hq attention heads of dn + dr (query and key) and
    dvh (value); c the latent rank, W a latent row as stored."""
    bytes_of = {"bfloat16": 2, "float16": 2, "float32": 4}
    lin = cfg["linear_attn_config"]
    L, ld = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return dict(
        L=L, Lk=len(lin["kda_layers"]), Lf=len(lin["full_attn_layers"]),
        Ld=ld, Lm=L - ld, H=cfg["hidden_size"], V=cfg["vocab_size"],
        I=cfg["intermediate_size"], hq=cfg["num_attention_heads"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
        dvh=cfg["v_head_dim"], c=cfg["kv_lora_rank"], W=_page_row(cfg),
        E=cfg["expert_parallel"]["router_width"], Eh=cfg["num_experts"],
        k=cfg["num_experts_per_token"], Im=cfg["moe_intermediate_size"],
        Is=cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
        Hl=lin["num_heads"], dk=lin["head_dim"], dv=lin["head_dim"],
        K=lin["short_conv_kernel_size"], r=lin["head_dim"],
        tp=cfg["serve"]["tp"], b=bytes_of[cfg["torch_dtype"]])
