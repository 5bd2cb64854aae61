"""Operations and bytes that a stack of power-retention layers REQUIRES,
from shapes alone (``work.py`` has the dense decoder's). Each count is a
lower bound on what any implementation must do: the exact ``phi`` of
``D (D + 1) / 2`` entries (no layout's padding), each operand once.

``model`` is the ``model`` group of a configuration file.
"""


def phi_entries(model: dict) -> int:
    d = model["head_dim"]
    return d * (d + 1) // 2


def matrix_params_per_layer(model: dict) -> int:
    """q, k, v and the gate; the output projection; gate, up and down of
    the feed-forward."""
    d, f, hd = model["d_model"], model["d_ffn"], model["head_dim"]
    h, kv = model["n_heads"], model["n_kv_heads"]
    return d * (h + 2 * kv) * hd + d * kv + h * hd * d + 3 * d * f


def head_params(model: dict) -> int:
    return model["vocab_size"] * model["d_model"]


def state_form_flops_per_token(model: dict) -> float:
    """One token through one layer in the state form: a multiply and an
    add for every entry of every key/value head's ``S`` and ``z`` (the
    update) and for every query head's read of them."""
    per_head = 2 * phi_entries(model) * (model["head_dim"] + 1)
    return (model["n_kv_heads"] + model["n_heads"]) * per_head


def retention_flops(model: dict, contexts) -> float:
    """All layers' retention for tokens whose contexts (tokens seen, the
    token itself included) are ``contexts``: each in the cheaper of the
    two exact forms, the state form or the attention form (scores and
    weights-times-values against ``c`` keys: 4 D c a query head)."""
    state = state_form_flops_per_token(model)
    per_key = 4 * model["head_dim"] * model["n_heads"]
    return model["n_layers"] * sum(min(state, per_key * int(c))
                                   for c in contexts)


def token_flops(model: dict, n_tokens: int, n_heads_applied: int) -> float:
    """The matrix products of ``n_tokens`` tokens (2 per parameter) and
    of ``n_heads_applied`` applications of the output head."""
    return (2.0 * model["n_layers"] * matrix_params_per_layer(model)
            * n_tokens + 2.0 * head_params(model) * n_heads_applied)


def _itemsize(model: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[model["dtype"]]


def step_bytes_per_slot(model: dict) -> int:
    """One decoding slot through one layer's step kernel: ``S`` and ``z``
    of every key/value head read once and written once in float32, and
    the slot's q, k, v and o in the configuration's type."""
    state = model["n_kv_heads"] * phi_entries(model) \
        * (model["head_dim"] + 1) * 4
    rows = (2 * model["n_heads"] + 2 * model["n_kv_heads"]) \
        * model["head_dim"] * _itemsize(model)
    return 2 * state + rows


def state_bytes_per_slot(model: dict) -> int:
    """What one sequence's state takes over all layers, float32, at the
    exact ``phi``."""
    return model["n_layers"] * model["n_kv_heads"] * phi_entries(model) \
        * (model["head_dim"] + 1) * 4
