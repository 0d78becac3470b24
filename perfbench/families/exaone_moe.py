"""The K-EXAONE family (`exaone_moe`: grouped-query attention blocks
from the source's `layer_types`, "sliding_attention" over the last
`sliding_window` positions with rotary and "full_attention" over every
position without; a leading dense block, a sigmoid router over all
experts with the chip's share held whole, an ungated shared expert),
between a configuration file's published keys and the program.

A configuration of this family stands for ONE chip of an expert-parallel
group: `num_experts` in its file is what the chip holds, the router's
width and the first held expert's id are under `expert_parallel`."""

# the keys no configuration of this family may cut (perfbench/contract.py)
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "num_attention_heads", "num_key_value_heads", "head_dim",
          "num_experts_per_tok", "num_shared_experts",
          "routed_scaling_factor", "first_k_dense_replace",
          "sliding_window", "sliding_window_pattern")


def model_config(cfg: dict):
    """The program's ModelConfig; the serving horizon bounds the pool's
    table and the rotary table. What the source's `config` leaves open
    is the file's `assumed`."""
    from triton_dist_tpu.models import ModelConfig

    assert cfg["scoring_func"] == "sigmoid" and cfg["norm_topk_prob"]
    assert cfg["n_group"] == 1 and cfg["topk_group"] == 1
    L, ld = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    assert cfg["mlp_layer_types"] == ["dense"] * ld + ["sparse"] * (L - ld)
    assert cfg["sliding_windows"] == [
        cfg["sliding_window"] if t == "sliding_attention" else 0
        for t in cfg["layer_types"]]
    ep = cfg["expert_parallel"]
    return ModelConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"], num_layers=L,
        num_q_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        rms_eps=cfg["rms_norm_eps"], max_positions=cfg["serve"]["max_len"],
        dtype=cfg["torch_dtype"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        num_experts=ep["router_width"], experts_held=cfg["num_experts"],
        expert_offset=ep["expert_offset"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_expert_intermediate_size=cfg["num_shared_experts"]
        * cfg["moe_intermediate_size"],
        layer_types=tuple(cfg["layer_types"]),
        sliding_window=cfg["sliding_window"], first_k_dense=ld,
        norm_zero_centred=False, router_score="sigmoid", router_bias=True,
        routed_scaling_factor=cfg["routed_scaling_factor"],
        shared_expert_gate=False)


def size_vars(cfg: dict) -> dict:
    """The sizes a work formula may name (perfbench/work.py): L blocks
    of which Lw attend a window of w positions and Lf every position,
    Ld with a dense MLP of width I (the other Lm with experts); hq /
    hkv heads of d; E experts routed over, Eh held, k a token, widths
    Im and Is (shared)."""
    bytes_of = {"bfloat16": 2, "float16": 2, "float32": 4}
    L, ld = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    lw = cfg["layer_types"].count("sliding_attention")
    return dict(
        L=L, Lw=lw, Lf=L - lw, Ld=ld, Lm=L - ld, H=cfg["hidden_size"],
        V=cfg["vocab_size"], I=cfg["intermediate_size"],
        hq=cfg["num_attention_heads"], hkv=cfg["num_key_value_heads"],
        d=cfg["head_dim"], w=cfg["sliding_window"],
        E=cfg["expert_parallel"]["router_width"], Eh=cfg["num_experts"],
        k=cfg["num_experts_per_tok"], Im=cfg["moe_intermediate_size"],
        Is=cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
        tp=cfg["serve"]["tp"], b=bytes_of[cfg["torch_dtype"]])
