"""The program's ModelConfig for a Qwen3 configuration file: dense
decoder layers, GQA with qk-norm, SwiGLU."""
from __future__ import annotations


def program_config(c: dict):
    """The program's ModelConfig for configuration dict ``c``, served in
    bf16 (parameters held in bf16 too, so the program prices them as
    served)."""
    import jax.numpy as jnp
    from repro.configs.base import ModelConfig
    from repro.core.policy import FactorizationPolicy

    if c["hidden_act"] != "silu" or c.get("attention_bias"):
        raise ValueError(f"{c['name']}: only SwiGLU without attention bias "
                         "is supported")
    return ModelConfig(
        name=c["name"], family="dense",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], head_dim=c["head_dim"],
        qk_norm=c["model_type"] == "qwen3", rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        # the program holds its own output head whether or not the
        # published model ties it to the embedding (``assumed`` in the file)
        tie_embeddings=False,
        fact=FactorizationPolicy.from_dict(c["policy"]),
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, remat=False)
