"""Operations and bytes the algorithms REQUIRE, from shapes alone. These are
the benchmark's own counts: nothing here reads the program, a compiler's
``cost_analysis()`` or ``GPTConfig.flops_per_token()`` (which leaves out the
output head and counts attention as if it were not causal).

``model`` is the ``model`` group of a configuration file.
"""


def matrix_params_per_layer(model: dict) -> int:
    d, f = model["d_model"], model["d_ffn"]
    return d * 3 * d + d * d + 2 * d * f


def matrix_params(model: dict) -> int:
    """Parameters that sit in a matrix product on the way of every token:
    the blocks' four matrices and the (tied) output head. Biases,
    LayerNorms and the embedding lookups cost no product."""
    return (model["n_layers"] * matrix_params_per_layer(model)
            + model["vocab_size"] * model["d_model"])


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """Forward and backward of one token in a causal sequence of
    ``seq_len``: 6 per matrix parameter, plus causal attention, which is
    half of the full 12 * L * d * s. Recomputation is not counted."""
    attention = 6 * model["n_layers"] * model["d_model"] * seq_len
    return 6 * matrix_params(model) + attention


def prefill_flops(model: dict, n_prompt: int) -> float:
    """Forward over a prompt of ``n_prompt`` tokens with the head applied
    once, at the last position."""
    L, d = model["n_layers"], model["d_model"]
    blocks = 2 * L * matrix_params_per_layer(model) * n_prompt
    attention = 2 * L * d * n_prompt * (n_prompt + 1)   # sum of 4*L*d*c
    return blocks + attention + 2 * model["vocab_size"] * d


def decode_flops(model: dict, context: int) -> float:
    """One generated token whose keys and values number ``context``."""
    L, d = model["n_layers"], model["d_model"]
    return (2 * L * matrix_params_per_layer(model) + 4 * L * d * context
            + 2 * model["vocab_size"] * d)


def _itemsize(model: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[model["dtype"]]


def flash_kernel_work(model: dict, batch: int, seq_len: int) -> dict:
    """Per call of each flash-attention kernel on (batch, heads, seq_len,
    head_dim), causal: ``{kernel: (flops, bytes)}``. Products needed:
    forward 2 (scores, output); dq 3 (scores again, dP, dQ); dk/dv 4
    (scores again, dV, dP, dK). Each is 2 * s * s * head_dim per head,
    halved by the causal mask. Bytes: every operand and result once."""
    H, D = model["n_heads"], model["head_dim"]
    one = 2 * batch * H * seq_len * seq_len * D / 2
    t = batch * H * seq_len * D * _itemsize(model)     # one (B,H,S,D) tensor
    row = batch * H * seq_len * 4                      # one f32 per row
    return {
        "flash_attention_fwd": (2 * one, 4 * t + row),          # q k v o lse
        "flash_attention_bwd_dq": (3 * one, 5 * t + 2 * row),   # q k v do dq
        "flash_attention_bwd_dkdv": (4 * one, 6 * t + 2 * row),
    }


def paged_attend_work(model: dict, contexts) -> tuple:
    """One call of the fused append+attend decode kernel (one layer, one
    new token per live slot): it has to read each slot's keys and values
    once. ``contexts`` are the live slots' lengths. (flops, bytes)."""
    H, D = model["n_heads"], model["head_dim"]
    total = sum(int(c) for c in contexts)
    flops = 4 * H * D * total
    nbytes = 2 * H * D * _itemsize(model) * total
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """The roofline: the larger of compute time and memory time, and which
    of the two it was."""
    tc = flops / peaks["flops_bf16"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
