"""Operations and bytes that a stack of latent-attention layers over
sparse experts REQUIRES, from shapes alone (``work.py`` has the dense
decoder's, ``work_retention.py`` the retention layers'). Each count is a
LOWER bound on what any implementation must do: each operand once, no
layout's padding, only the experts that were touched.

``model`` is the ``model`` group of a configuration file.
"""


# the program's named scopes that hold an expert layer's operations
# (`paddle_tpu/models/expert_layer.py`)
EXPERT_SCOPES = ("moe_route", "moe_experts", "moe_combine", "moe_shared")


def _itemsize(model: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[model["dtype"]]


def attention_params(model: dict) -> int:
    """The five matrices of a layer's attention: the query's two, the
    compression to a row and its rotary part, the expansion of a row to
    every head's key and value, the output projection."""
    d, h = model["d_model"], model["n_heads"]
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    return (d * model["q_lora_rank"] + model["q_lora_rank"] * h * qk
            + d * (model["kv_lora_rank"] + model["qk_rope_head_dim"])
            + model["kv_lora_rank"] * h
            * (model["qk_nope_head_dim"] + model["v_head_dim"])
            + h * model["v_head_dim"] * d)


def expert_params(model: dict) -> int:
    """Gate, up and down of ONE routed expert."""
    return 3 * model["d_model"] * model["expert_width"]


def expert_layer_active_params(model: dict) -> int:
    """What one token multiplies in an expert layer's feed-forward: the
    router, the experts it chose and the shared ones."""
    return (model["d_model"] * model["n_experts"]
            + (model["experts_per_token"] + model["n_shared_experts"])
            * expert_params(model))


def active_params_per_token(model: dict) -> int:
    """Matrix parameters one token goes through over the whole stack,
    without the output head."""
    lead = model["leading_dense"]
    return (model["n_layers"] * attention_params(model)
            + lead * 3 * model["d_model"] * model["d_ffn"]
            + (model["n_layers"] - lead) * expert_layer_active_params(model))


def head_params(model: dict) -> int:
    return model["vocab_size"] * model["d_model"]


def token_flops(model: dict, n_tokens: int, n_heads_applied: int) -> float:
    """The matrix products of ``n_tokens`` tokens (2 per ACTIVE
    parameter) and of ``n_heads_applied`` applications of the head."""
    return (2.0 * active_params_per_token(model) * n_tokens
            + 2.0 * head_params(model) * n_heads_applied)


def _per_key(model: dict):
    """FLOPs one query token spends on ONE key of its context in one
    layer: (absorbed, expanded, expanding the key). Absorbed: every
    head's query of rank + rope numbers against the row, and the weights
    times the row's first ``rank``. Expanded: heads of nope + rope
    against the key and the weights times a value of ``v``; the key's
    and value's making from the row is the third number (once a key,
    for however many queries share it)."""
    h, rk = model["n_heads"], model["kv_lora_rank"]
    rope, nope = model["qk_rope_head_dim"], model["qk_nope_head_dim"]
    v = model["v_head_dim"]
    return (2 * h * (2 * rk + rope), 2 * h * (nope + rope + v),
            2 * rk * h * (nope + v))


def decode_attention_flops(model: dict, contexts) -> float:
    """All layers' attention of generated tokens whose contexts (keys
    seen, the token itself included) are ``contexts``, each alone in its
    step, in the cheaper of the two exact forms (the expanded form would
    make every cached key anew for it)."""
    absorbed, expanded, making = _per_key(model)
    per_key = min(absorbed, expanded + making)
    return float(model["n_layers"] * per_key * sum(int(c) for c in contexts))


def chunk_attention_flops(model: dict, first: int, n: int) -> float:
    """All layers' attention of a prompt chunk of ``n`` tokens at
    positions ``first ..``: absorbed, or expanded with every key of the
    context made ONCE for the chunk, whichever is cheaper."""
    absorbed, expanded, making = _per_key(model)
    keys = sum(range(first + 1, first + n + 1))
    return float(model["n_layers"] * min(
        absorbed * keys, expanded * keys + making * (first + n)))


def latent_row_bytes(model: dict) -> int:
    """What one cached token takes in one layer."""
    return (model["kv_lora_rank"] + model["qk_rope_head_dim"]) \
        * _itemsize(model)


def attend_least_seconds(model: dict, rows: float, slots: float, calls: int,
                         peaks: dict) -> float:
    """The least time of ``calls`` decode attends (one a layer and step)
    that together read ``rows`` live cached rows (summed over the calls)
    for ``slots`` queries (summed likewise): each row read ONCE (it is
    key and value at once), each slot's absorbed queries in and weighted
    sums out; against the absorbed form's FLOPs."""
    del calls
    h, rk = model["n_heads"], model["kv_lora_rank"]
    nbytes = rows * latent_row_bytes(model) + slots * h * (
        2 * rk + model["qk_rope_head_dim"]) * _itemsize(model)
    flops = _per_key(model)[0] * rows
    return max(nbytes / peaks["hbm_bytes_per_s"],
               flops / peaks["flops_bf16"])


def expert_layer_least_seconds(model: dict, tokens: float, touched: float,
                               calls: float, peaks: dict) -> float:
    """The least time of ``calls`` expert layers (one a layer and
    program run) through which ``tokens`` tokens went (summed over the
    calls) and whose tokens touched ``touched`` distinct experts
    (summed likewise): the touched experts' matrices, the shared expert
    and the router once a call, the tokens in and out; against the
    FLOPs of the router, the chosen experts and the shared one. The
    larger of the two sums bounds the sum of the calls' own bounds from
    below."""
    item, d = _itemsize(model), model["d_model"]
    fixed = (model["n_shared_experts"] * expert_params(model) * item
             + d * model["n_experts"] * 4)
    nbytes = (touched * expert_params(model) * item + calls * fixed
              + tokens * 2 * d * item)
    flops = 2.0 * expert_layer_active_params(model) * tokens
    return max(nbytes / peaks["hbm_bytes_per_s"],
               flops / peaks["flops_bf16"])


def cache_bytes_per_token(model: dict) -> int:
    return model["n_layers"] * latent_row_bytes(model)
