"""The Granite 4.0-H family (`granitemoehybrid` with no expert layer:
Mamba-2 state-space blocks and grouped-query attention blocks without
rotary or q/k norm from the source's `layer_types`, a dense SwiGLU of
`shared_intermediate_size` in EVERY block, tied embeddings and four
multipliers), between a configuration file's published keys and the
program.

A configuration of this family is a WHOLE model on one chip: nothing is
held elsewhere, no group, no stage."""

# the keys no configuration of this family may cut (perfbench/contract.py)
WIDTHS = ("hidden_size", "intermediate_size", "shared_intermediate_size",
          "num_attention_heads", "num_key_value_heads", "mamba_d_state",
          "mamba_d_head", "mamba_n_heads", "mamba_expand", "mamba_d_conv",
          "mamba_n_groups", "embedding_multiplier", "residual_multiplier",
          "attention_multiplier", "logits_scaling")


def _checked(cfg: dict) -> None:
    """What of the source the program's path takes as given."""
    assert cfg["num_local_experts"] == 0 and cfg["num_experts_per_tok"] == 0
    assert cfg["position_embedding_type"] == "nope"
    assert cfg["normalization_function"] == "rmsnorm"
    assert cfg["hidden_act"] == "silu" and cfg["mamba_n_groups"] == 1
    assert cfg["mamba_conv_bias"] and not cfg["mamba_proj_bias"]
    assert not cfg["attention_bias"] and cfg["tie_word_embeddings"]
    assert set(cfg["layer_types"]) <= {"mamba", "attention"}
    assert (cfg["mamba_n_heads"] * cfg["mamba_d_head"]
            == cfg["mamba_expand"] * cfg["hidden_size"])
    assert cfg["hidden_size"] % cfg["num_attention_heads"] == 0


def model_config(cfg: dict):
    """The program's ModelConfig; the serving horizon bounds the pool's
    table. The head size is hidden_size / num_attention_heads (the
    source states none); what else the source's `config` leaves open
    is the file's `assumed`."""
    from triton_dist_tpu.models import ModelConfig

    _checked(cfg)
    L = cfg["num_hidden_layers"]
    return ModelConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["shared_intermediate_size"], num_layers=L,
        num_q_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        rms_eps=cfg["rms_norm_eps"], max_positions=cfg["serve"]["max_len"],
        dtype=cfg["torch_dtype"], use_qk_norm=False,
        tie_word_embeddings=True, layer_types=tuple(cfg["layer_types"]),
        first_k_dense=L, norm_zero_centred=False,
        mamba_num_heads=cfg["mamba_n_heads"],
        mamba_head_dim=cfg["mamba_d_head"],
        mamba_state_dim=cfg["mamba_d_state"],
        mamba_conv_kernel_dim=cfg["mamba_d_conv"],
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        attention_multiplier=float(cfg["attention_multiplier"]))


def size_vars(cfg: dict) -> dict:
    """The sizes a work formula may name (perfbench/work.py): L blocks
    of which Ls state-space and Lf attention, each with a dense MLP of
    width I; hq / hkv attention heads of d (the USEFUL head size, not
    the 128 a page keeps it in); Hm state-space heads of P channels
    over a state of N, Di = Hm P inner channels, K the convolution's
    width; V the vocabulary (the tied head's)."""
    bytes_of = {"bfloat16": 2, "float16": 2, "float32": 4}
    _checked(cfg)
    L = cfg["num_hidden_layers"]
    lf = cfg["layer_types"].count("attention")
    hm, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    return dict(
        L=L, Ls=L - lf, Lf=lf, H=cfg["hidden_size"], V=cfg["vocab_size"],
        I=cfg["shared_intermediate_size"], hq=cfg["num_attention_heads"],
        hkv=cfg["num_key_value_heads"],
        d=cfg["hidden_size"] // cfg["num_attention_heads"], Hm=hm, P=p,
        N=cfg["mamba_d_state"], Di=hm * p, K=cfg["mamba_d_conv"],
        tp=cfg["serve"]["tp"], b=bytes_of[cfg["torch_dtype"]])
