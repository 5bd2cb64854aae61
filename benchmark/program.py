"""The one place where the benchmark's weights enter the program's model."""


def build_model(model_cfg, weights, remat):
    """The program's model object holding the benchmark's weights. The
    skeleton comes from ``eval_shape`` (the program's own initialiser makes
    no array), the weights go in under ``state_dict`` names."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import gpt
    from benchmark.weights import program_state_dict
    cfg = gpt.GPTConfig(
        vocab_size=model_cfg["vocab_size"],
        max_seq_len=model_cfg["max_seq_len"], d_model=model_cfg["d_model"],
        n_layers=model_cfg["n_layers"], n_heads=model_cfg["n_heads"],
        ffn_mult=model_cfg["ffn_mult"], dtype=jnp.dtype(model_cfg["dtype"]),
        use_bias=model_cfg["use_bias"],
        tie_embeddings=model_cfg["tie_embeddings"], remat=remat)
    if cfg.head_dim != model_cfg["head_dim"] or cfg.d_ffn != model_cfg["d_ffn"]:
        raise ValueError("configuration file's derived sizes disagree")
    skeleton = jax.eval_shape(lambda: gpt.GPT(cfg))
    return skeleton.merge_params(program_state_dict(weights))
