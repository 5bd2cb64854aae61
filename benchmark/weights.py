"""The benchmark's own weights: random, from ``--seed``, made on the device
in ONE jitted call, in the types the configuration states.

Both sides of the comparison get them from here: the program (loaded into
its model object as a checkpoint would be, ``load_into_program``) and the
plain reference (``reference/gpt_dense.py`` takes this structure as it
is). Nothing the program initialises is used.

Structure: ``{"wte", "wpe", "lnf_scale", "lnf_bias", "layers": [ {leaf:
array} x n_layers ]}``. Matrices and biases in the configuration's dtype,
LayerNorm scales and offsets in float32, all uniform with the usual
initialisers' standard deviations (0.02; 0.02 / sqrt(2 L) into the residual). Biases and LayerNorm offsets are
small random numbers, not zeros, so that a path that drops one shows.
"""

import functools

import jax
import jax.numpy as jnp

MATRIX_STD = 0.02
POSITION_STD = 0.01
BIAS_STD = 0.02

LAYER_LEAVES = ("ln1_scale", "ln1_bias", "wqkv", "bqkv", "wo", "bo",
                "ln2_scale", "ln2_bias", "wup", "bup", "wdown", "bdown")


def _seed_words(seed: int):
    """--seed may exceed 31 bits: hand it to the jitted maker as two
    non-negative int32 words (traced, so every seed shares one program)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF


@functools.lru_cache(maxsize=None)
def _maker(n_layers, d_model, d_ffn, vocab_size, max_seq_len, dtype_name):
    dt = jnp.dtype(dtype_name)
    d = d_model
    resid_std = MATRIX_STD / (2 * n_layers) ** 0.5

    def draw(key, shape, std, dtype):
        """Uniform on [-a, a] with a = std * sqrt(3): the same mean and
        variance as the normal initialisers, at a fraction of the cost
        (1.3e9 inverse error functions take a v5e six seconds)."""
        a = std * 3.0 ** 0.5
        return jax.random.uniform(key, shape, jnp.float32, -a, a).astype(dtype)

    def make(lo, hi):
        # "rbg" draws from the chip's own generator, not threefry
        key = jax.random.fold_in(jax.random.key(lo, impl="rbg"), hi)
        k_wte, k_wpe, k_lnf, k_layers = jax.random.split(key, 4)
        ks, kb = jax.random.split(k_lnf)
        out = {
            "wte": draw(k_wte, (vocab_size, d), MATRIX_STD, dt),
            "wpe": draw(k_wpe, (max_seq_len, d), POSITION_STD, dt),
            "lnf_scale": 1.0 + draw(ks, (d,), BIAS_STD, jnp.float32),
            "lnf_bias": draw(kb, (d,), BIAS_STD, jnp.float32),
        }
        # one draw per kind of leaf for all layers, then cut by layer: a
        # program of 16 generators, not of 292
        L = n_layers
        k = jax.random.split(k_layers, 12)
        stacked = {
            "ln1_scale": 1.0 + draw(k[0], (L, d), BIAS_STD, jnp.float32),
            "ln1_bias": draw(k[1], (L, d), BIAS_STD, jnp.float32),
            "wqkv": draw(k[2], (L, d, 3 * d), MATRIX_STD, dt),
            "bqkv": draw(k[3], (L, 3 * d), BIAS_STD, dt),
            "wo": draw(k[4], (L, d, d), resid_std, dt),
            "bo": draw(k[5], (L, d), BIAS_STD, dt),
            "ln2_scale": 1.0 + draw(k[6], (L, d), BIAS_STD, jnp.float32),
            "ln2_bias": draw(k[7], (L, d), BIAS_STD, jnp.float32),
            "wup": draw(k[8], (L, d, d_ffn), MATRIX_STD, dt),
            "bup": draw(k[9], (L, d_ffn), BIAS_STD, dt),
            "wdown": draw(k[10], (L, d_ffn, d), resid_std, dt),
            "bdown": draw(k[11], (L, d), BIAS_STD, dt),
        }
        out["layers"] = [{name: x[i] for name, x in stacked.items()}
                         for i in range(L)]
        return out

    return jax.jit(make)


def make_weights(model: dict, seed: int) -> dict:
    """``model`` is the ``model`` group of a configuration file."""
    lo, hi = _seed_words(seed)
    fn = _maker(model["n_layers"], model["d_model"], model["d_ffn"],
                model["vocab_size"], model["max_seq_len"], model["dtype"])
    return fn(jnp.int32(lo), jnp.int32(hi))


def program_state_dict(weights: dict) -> dict:
    """The same arrays under the names ``gpt.GPT.state_dict()`` uses."""
    state = {k: v for k, v in weights.items() if k != "layers"}
    for i, layer in enumerate(weights["layers"]):
        for name, value in layer.items():
            state[f"blocks.item_{i}.{name}"] = value
    return state


def free(tree) -> None:
    """Release device buffers now (a process's peak never falls, but what
    runs next needs the room)."""
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()
