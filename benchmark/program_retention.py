"""Where the benchmark's weights enter the program's model for a
configuration of retention layers (``program.py`` does the same for the
GPT-2-shaped ones): an ``eval_shape`` skeleton of ``gpt.GPT`` with the
configuration's norms, feed-forward and mixer. The blocks go in STACKED,
as the benchmark's maker lays them (the model's ``_stacked_blocks``, which
its scan over layers and the serving engines read as it is): 5.3 GB of
blocks exist once on the device."""


def model_config(model_cfg):
    """The program's configuration for the ``model`` group of a
    configuration file. A program that has no such layers raises here,
    before any weight is made."""
    import jax.numpy as jnp
    from paddle_tpu.models import gpt
    cfg = gpt.GPTConfig(
        vocab_size=model_cfg["vocab_size"],
        max_seq_len=model_cfg["max_seq_len"], d_model=model_cfg["d_model"],
        n_layers=model_cfg["n_layers"], n_heads=model_cfg["n_heads"],
        n_kv_heads=model_cfg["n_kv_heads"], ffn_width=model_cfg["d_ffn"],
        dtype=jnp.dtype(model_cfg["dtype"]),
        use_bias=model_cfg["use_bias"],
        tie_embeddings=model_cfg["tie_embeddings"], rope=True,
        rope_theta=model_cfg["rope_theta"], norm="rmsnorm",
        norm_eps=model_cfg["norm_eps"], ffn="swiglu", qk_norm=True,
        mixer="retention")
    if cfg.head_dim != model_cfg["head_dim"]:
        raise ValueError("configuration file's derived sizes disagree")
    return cfg


def build_model(model_cfg, weights):
    import jax
    from paddle_tpu.models import gpt
    from paddle_tpu.nn.module import Module
    cfg = model_config(model_cfg)
    skeleton = jax.eval_shape(lambda: gpt.GPT(cfg))
    stacked = Module.merge_params(skeleton.blocks[0], weights["layers"])
    return Module.merge_params(skeleton, {
        "wte": weights["wte"], "lm_head": weights["lm_head"],
        "lnf_scale": weights["lnf_scale"], "_stacked_blocks": stacked})
