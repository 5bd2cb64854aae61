"""Where the benchmark's weights enter the program's model for a
configuration of latent-attention layers over sparse experts
(``program.py`` and ``program_retention.py`` do the same for theirs): an
``eval_shape`` skeleton of ``gpt.GPT`` with the configuration's ranks,
head sizes, experts and leading dense layers. The expert layers go in
STACKED, as the benchmark's maker lays them (the model's
``_stacked_blocks``, which the serving engine scans as it is: 9.9 GB of
experts exist once on the device); the leading dense layers are blocks
of their own."""


def model_config(model_cfg):
    """The program's configuration for the ``model`` group of a
    configuration file. A program that has no such layers raises here,
    before any weight is made."""
    import jax.numpy as jnp
    from paddle_tpu.models import gpt
    return gpt.GPTConfig(
        vocab_size=model_cfg["vocab_size"],
        max_seq_len=model_cfg["max_seq_len"], d_model=model_cfg["d_model"],
        n_layers=model_cfg["n_layers"], n_heads=model_cfg["n_heads"],
        n_kv_heads=model_cfg["n_kv_heads"], ffn_width=model_cfg["d_ffn"],
        dtype=jnp.dtype(model_cfg["dtype"]),
        use_bias=model_cfg["use_bias"],
        tie_embeddings=model_cfg["tie_embeddings"], rope=True,
        rope_theta=model_cfg["rope_theta"], rope_interleave=True,
        norm="rmsnorm", norm_eps=model_cfg["norm_eps"], ffn="swiglu",
        mixer="latent", q_lora_rank=model_cfg["q_lora_rank"],
        kv_lora_rank=model_cfg["kv_lora_rank"],
        qk_nope_head_dim=model_cfg["qk_nope_head_dim"],
        qk_rope_head_dim=model_cfg["qk_rope_head_dim"],
        v_head_dim=model_cfg["v_head_dim"],
        routed_experts=model_cfg["n_experts"],
        experts_per_token=model_cfg["experts_per_token"],
        expert_width=model_cfg["expert_width"],
        shared_experts=model_cfg["n_shared_experts"],
        routed_scaling=model_cfg["routed_scaling"],
        leading_dense=model_cfg["leading_dense"])


def build_model(model_cfg, weights):
    import jax
    from paddle_tpu.models import gpt
    from paddle_tpu.nn.module import Module
    cfg = model_config(model_cfg)
    skeleton = jax.eval_shape(lambda: gpt.GPT(cfg))
    lead = cfg.leading_dense
    params = {"wte": weights["wte"], "lm_head": weights["lm_head"],
              "lnf_scale": weights["lnf_scale"],
              "_stacked_blocks": Module.merge_params(
                  skeleton.blocks[lead], weights["layers"])}
    for i, leaves in enumerate(weights["dense"]):
        for name, leaf in leaves.items():
            params[f"blocks.item_{i}.{name}"] = leaf
    return Module.merge_params(skeleton, params)
