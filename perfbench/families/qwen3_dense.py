"""The dense Qwen3 family (GQA decoder, SwiGLU MLP, q/k norms, untied
head), between a configuration file's published keys and the program."""


def model_config(cfg: dict):
    """The program's ModelConfig; the serving horizon bounds the rope
    table."""
    from triton_dist_tpu.models import ModelConfig

    return ModelConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_q_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        max_positions=cfg["serve"]["max_len"], dtype=cfg["torch_dtype"],
        tie_word_embeddings=cfg["tie_word_embeddings"])


def size_vars(cfg: dict) -> dict:
    """The sizes a work formula may name (perfbench/work.py)."""
    bytes_of = {"bfloat16": 2, "float16": 2, "float32": 4}
    return dict(
        L=cfg["num_hidden_layers"], H=cfg["hidden_size"],
        I=cfg["intermediate_size"], V=cfg["vocab_size"],
        hq=cfg["num_attention_heads"], hkv=cfg["num_key_value_heads"],
        d=cfg["head_dim"], tp=cfg["serve"]["tp"],
        b=bytes_of[cfg["torch_dtype"]])
