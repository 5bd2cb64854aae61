"""Plain reference for JoyAI-LLM-Flash's forward pass (jdopensource; a
DeepSeek-V3-shaped decoder, arXiv:2412.19437: multi-head latent attention
over sparse experts with a shared expert, behind leading dense layers):
``jax.numpy``, float32, every product at ``Precision.HIGHEST``, the
EXPANDED attention only (every key and value made from its compressed
row), a plain loop over the experts, no kernels, no cache, no batching.
It imports nothing of the program (the helpers every reference of this
directory shares come from ``power_retention.py``) and takes only the
weights below.

The layer, with ``h`` the residual stream and ``RMS`` an RMSNorm with a
learned scale (eps from the configuration), no bias anywhere:

- ``x = RMS(h)``; ``c_q = RMS(x W_qa)``; ``q = c_q W_qb``, a head's 192
  numbers split into ``q_nope`` (128) and ``q_rope`` (64);
  ``[c_kv | k_rope] = x W_kva`` (512 | 64), ``c_kv = RMS(c_kv)``,
  ``k_rope`` ONE row a token for all heads; ``[k_nope | v] = c_kv W_kvb``
  a head (128 | 128); rotary positions (theta from the configuration) on
  ``q_rope`` and ``k_rope`` with the INTERLEAVED pairing ``(2i, 2i+1)``;
  scores ``(q_nope . k_nope + q_rope . k_rope) / sqrt(192)``, causal
  softmax, ``o = sum p v``; ``h += concat(o) W_o``;
- layer 0 (each of the ``leading_dense``): ``h += (silu(x W_g) * (x W_u))
  W_d``; every other layer: ``s = sigmoid(x W_r)`` in float32; the
  ``experts_per_token`` experts with the largest ``s + b`` (a tie to the
  lower index); ``w_e = scaling * s_e / sum of the chosen s``;
  ``h += sum_e w_e FFN_e(x) + FFN_shared(x)``, every FFN SiLU-gated. No
  token is dropped;
- final ``RMS``, ``logits = x W_head``.

Controls, each one precision below what the configuration states:
``mode="fp8"`` / ``"int8"`` round both operands of every matrix product
but the router's with one scale per tensor as the product sees it;
``router="bfloat16"`` computes the router's product and scores in
bfloat16; ``cache="float8"`` rounds what the latent cache keeps,
``[c_kv | k_rope]`` after the norm and the rotation, to float8's 4
exponent and 3 mantissa bits (one scale for the tensor) before any key or
value is made from it: through ``lax.reduce_precision``, because inside a
jitted program on the TPU XLA takes a conversion to float8 and back out
as excess precision (my chip run, PR 35: the round trip changed nothing
there, and 2.7% of attention's output when run operation by operation).

Weights: ``make_weights`` draws a layer at a time, the experts 32 at a
time, into the leaves of the program's blocks (one jitted program each,
donated), so that the maker reserves a few hundred megabytes beside what
it makes: ``{"wte", "lm_head", "lnf_scale", "dense": [leaves of a
leading dense layer, ...], "layers": {leaf: (expert layers, ...)}}``. The
program's engine scans over the stacks as they are, and the reference
cuts one layer out at a time.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

# what a reference of this directory needs whatever its layers are: the
# seed's words, the draw, the vocabulary's two tables, the controls'
# rounding, a product at HIGHEST, blocks of rows, the norm and the head
from benchmark.reference.power_retention import (  # noqa: F401
    F32, HIGHEST, MATRIX_STD, QUERY_BLOCK, SCALE_STD, VOCAB_BLOCKS,
    _by_rows, _draw, _head, _mm, _operands, _seed_words, _table_maker,
    rms_norm)

BIAS_STD = 0.01                 # the router's selection bias
EXPERT_BLOCK = 32               # experts drawn at a time


# ------------------------------------------------------------------ weights
def _key(lo, hi, *more):
    key = jax.random.fold_in(jax.random.key(lo, impl="rbg"), hi)
    for m in more:
        key = jax.random.fold_in(key, m)
    return key


def _sizes(model):
    return dict(
        d=model["d_model"], h=model["n_heads"], rq=model["q_lora_rank"],
        rk=model["kv_lora_rank"], nope=model["qk_nope_head_dim"],
        rope=model["qk_rope_head_dim"], v=model["v_head_dim"],
        f=model["d_ffn"], e=model["n_experts"], fe=model["expert_width"],
        fs=model["expert_width"] * model["n_shared_experts"],
        n=model["n_layers"], lead=model["leading_dense"])


def _attention_leaves(key, z, dt, resid_std):
    k = jax.random.split(key, 9)
    d, h = z["d"], z["h"]
    return {
        "ln1_scale": 1.0 + _draw(k[0], (d,), SCALE_STD, F32),
        "wq_a": _draw(k[1], (d, z["rq"]), MATRIX_STD, dt),
        "q_a_norm": 1.0 + _draw(k[2], (z["rq"],), SCALE_STD, F32),
        "wq_b": _draw(k[3], (z["rq"], h * (z["nope"] + z["rope"])),
                      MATRIX_STD, dt),
        "wkv_a": _draw(k[4], (d, z["rk"] + z["rope"]), MATRIX_STD, dt),
        "kv_a_norm": 1.0 + _draw(k[5], (z["rk"],), SCALE_STD, F32),
        "wkv_b": _draw(k[6], (z["rk"], h * (z["nope"] + z["v"])),
                       MATRIX_STD, dt),
        "wo": _draw(k[7], (h * z["v"], d), resid_std, dt),
        "ln2_scale": 1.0 + _draw(k[8], (d,), SCALE_STD, F32),
    }


@functools.lru_cache(maxsize=None)
def _makers(sizes, dtype):
    z, dt = dict(sizes), jnp.dtype(dtype)
    resid_std = MATRIX_STD / (2 * z["n"]) ** 0.5
    d, fe, fs, e = z["d"], z["fe"], z["fs"], z["e"]
    n_sparse = z["n"] - z["lead"]

    def dense_layer(lo, hi, layer):
        key = _key(lo, hi, layer)
        k = jax.random.split(jax.random.fold_in(key, 77), 3)
        return dict(
            _attention_leaves(key, z, dt, resid_std),
            wgate=_draw(k[0], (d, z["f"]), MATRIX_STD, dt),
            wup=_draw(k[1], (d, z["f"]), MATRIX_STD, dt),
            wdown=_draw(k[2], (z["f"], d), resid_std, dt))

    def sparse_small(lo, hi, layer):
        """An expert layer without its routed experts."""
        key = _key(lo, hi, layer)
        k = jax.random.split(jax.random.fold_in(key, 78), 5)
        return dict(_attention_leaves(key, z, dt, resid_std), **{
            "experts.w_router": _draw(k[0], (d, e), MATRIX_STD, F32),
            "experts.router_bias": _draw(k[1], (e,), BIAS_STD, F32),
            "experts.ws_gate": _draw(k[2], (d, fs), MATRIX_STD, dt),
            "experts.ws_up": _draw(k[3], (d, fs), MATRIX_STD, dt),
            "experts.ws_down": _draw(k[4], (fs, d), resid_std, dt)})

    def fill_small(stacks, lo, hi, layer, at):
        return {name: lax.dynamic_update_index_in_dim(
            stacks[name], leaf, at, axis=0)
            for name, leaf in sparse_small(lo, hi, layer).items()}

    def fill_experts(stacks, lo, hi, layer, at, block):
        k = jax.random.split(_key(lo, hi, layer, 79, block), 3)
        new = {
            "experts.w_gate": _draw(k[0], (EXPERT_BLOCK, d, fe),
                                    MATRIX_STD, dt),
            "experts.w_up": _draw(k[1], (EXPERT_BLOCK, d, fe), MATRIX_STD,
                                  dt),
            "experts.w_down": _draw(k[2], (EXPERT_BLOCK, fe, d), resid_std,
                                    dt)}
        return {name: lax.dynamic_update_slice(
            stacks[name], leaf[None], (at, block * EXPERT_BLOCK, 0, 0))
            for name, leaf in new.items()}

    def empty():
        small = {name: jnp.zeros((n_sparse,) + s.shape, s.dtype) for
                 name, s in jax.eval_shape(sparse_small, 0, 0, 0).items()}
        big = {"experts.w_gate": jnp.zeros((n_sparse, e, d, fe), dt),
               "experts.w_up": jnp.zeros((n_sparse, e, d, fe), dt),
               "experts.w_down": jnp.zeros((n_sparse, e, fe, d), dt)}
        return small, big

    return (jax.jit(dense_layer), jax.jit(fill_small, donate_argnums=(0,)),
            jax.jit(fill_experts, donate_argnums=(0,)), empty)


def make_weights(model: dict, seed: int) -> dict:
    """``model`` is the ``model`` group of the configuration's file."""
    lo, hi = (jnp.int32(w) for w in _seed_words(seed))
    z = _sizes(model)
    if z["e"] % EXPERT_BLOCK:
        raise ValueError(f"experts are drawn {EXPERT_BLOCK} at a time")
    dense_layer, fill_small, fill_experts, empty = _makers(
        tuple(sorted(z.items())), model["dtype"])
    dense = [dense_layer(lo, hi, jnp.int32(i)) for i in range(z["lead"])]
    small, big = empty()
    for at, i in enumerate(range(z["lead"], z["n"])):
        small = fill_small(small, lo, hi, jnp.int32(i), jnp.int32(at))
        for b in range(z["e"] // EXPERT_BLOCK):
            big = fill_experts(big, lo, hi, jnp.int32(i), jnp.int32(at),
                               jnp.int32(b))
    d, v = z["d"], model["vocab_size"]
    fill, dt = _table_maker(v, d, model["dtype"])

    def table(which):
        out = jnp.zeros((v, d), dt)
        for i in range(VOCAB_BLOCKS):
            out = fill(out, lo, hi, jnp.int32(which), jnp.int32(i))
        return out

    return {
        "wte": table(0),
        "lm_head": table(1).T,                     # (d, vocab), as stored
        "lnf_scale": 1.0 + _draw(_key(lo, hi, 999), (d,), SCALE_STD, F32),
        "dense": dense,
        "layers": dict(small, **big),
    }


EXPERT_STACKS = ("experts.w_gate", "experts.w_up", "experts.w_down")


def layer_weights(weights: dict, i: int) -> dict:
    """Layer ``i``'s leaves: a leading dense layer's own, or an expert
    layer's cut from the stacks; but the routed experts' three matrices
    stay the stacks they are (2.4 GB a layer: no copy is made of them),
    with the layer's place in them under ``experts.at``."""
    lead = len(weights["dense"])
    if i < lead:
        return weights["dense"][i]
    out = {name: leaf if name in EXPERT_STACKS else leaf[i - lead]
           for name, leaf in weights["layers"].items()}
    out["experts.at"] = jnp.int32(i - lead)
    return out


# ------------------------------------------------------------------- layers
def round_e4m3(x):
    """``x`` at float8 e4m3's precision, one scale for the tensor (its
    largest magnitude at 240, the largest that 4 exponent bits hold)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0
    return lax.reduce_precision(x / scale, exponent_bits=4,
                                mantissa_bits=3) * scale


def rope_interleaved(x, theta):
    """(T, ..., D) at positions 0..T-1, pairs (2i, 2i+1)."""
    t, d = x.shape[0], x.shape[-1]
    freqs = theta ** (-jnp.arange(d // 2, dtype=F32) / (d // 2))
    ang = jnp.arange(t, dtype=F32)[:, None] * freqs
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def expanded_attention(q_nope, q_rope, k_nope, k_rope, v, mode):
    """Causal attention with every head's key and value made: queries
    (T, H, nope) and (T, H, rope), keys (T, H, nope) and ONE rotary row
    (T, rope) for all heads, values (T, H, v). Query blocks of
    QUERY_BLOCK rows, each against every key with those after it
    masked."""
    t = q_nope.shape[0]
    scale = (q_nope.shape[-1] + q_rope.shape[-1]) ** -0.5

    def rows(xs, start):
        qn, qr = xs
        n = qn.shape[0]
        a, b = _operands(qn, k_nope, mode)
        c, e = _operands(qr, k_rope, mode)
        s = (jnp.einsum("thd,shd->hts", a, b, precision=HIGHEST)
             + jnp.einsum("thd,sd->hts", c, e, precision=HIGHEST)) * scale
        seen = jnp.arange(t)[None, :] <= start + jnp.arange(n)[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        p, vb = _operands(p, v, mode)
        return jnp.einsum("hts,shd->thd", p, vb, precision=HIGHEST)

    if t <= QUERY_BLOCK or t % QUERY_BLOCK:
        return rows((q_nope, q_rope), 0)
    n = t // QUERY_BLOCK
    out = lax.map(lambda xs: rows(xs[:2], xs[2]), (
        q_nope.reshape(n, QUERY_BLOCK, *q_nope.shape[1:]),
        q_rope.reshape(n, QUERY_BLOCK, *q_rope.shape[1:]),
        jnp.arange(0, t, QUERY_BLOCK)))
    return out.reshape(t, *out.shape[2:])


def attention(lp, x, z, theta, eps, mode, cache):
    """x (T, d), the residual stream -> what attention adds to it."""
    t = x.shape[0]
    h = rms_norm(x, lp["ln1_scale"], eps)
    cq = rms_norm(_mm(h, lp["wq_a"], mode), lp["q_a_norm"], eps)
    q = _mm(cq, lp["wq_b"], mode).reshape(t, z["h"], z["nope"] + z["rope"])
    kv = _mm(h, lp["wkv_a"], mode)
    c_kv = rms_norm(kv[:, :z["rk"]], lp["kv_a_norm"], eps)
    k_rope = rope_interleaved(kv[:, z["rk"]:], theta)
    if cache == "float8":
        kept = round_e4m3(jnp.concatenate([c_kv, k_rope], axis=-1))
        c_kv, k_rope = kept[:, :z["rk"]], kept[:, z["rk"]:]
    elif cache != "float32":
        raise ValueError(f"unknown cache control {cache!r}")
    up = _mm(c_kv, lp["wkv_b"], mode).reshape(t, z["h"],
                                               z["nope"] + z["v"])
    o = expanded_attention(
        q[..., :z["nope"]], rope_interleaved(q[..., z["nope"]:], theta),
        up[..., :z["nope"]], k_rope, up[..., z["nope"]:], mode)
    return _mm(o.reshape(t, -1), lp["wo"], mode)


def gated_ffn(n, w_gate, w_up, w_down, mode):
    return _mm(jax.nn.silu(_mm(n, w_gate, mode)) * _mm(n, w_up, mode),
               w_down, mode)


def choose_experts(n, w_router, bias, per_token, scaling, router="float32"):
    """Normed tokens (T, d) -> (experts (T, k), weights (T, k))."""
    if router == "bfloat16":
        logits = jnp.matmul(n.astype(jnp.bfloat16),
                            w_router.astype(jnp.bfloat16))
        s = jax.nn.sigmoid(logits).astype(F32)
    elif router == "float32":
        s = jax.nn.sigmoid(jnp.matmul(n, w_router.astype(F32),
                                      precision=HIGHEST))
    else:
        raise ValueError(f"unknown router control {router!r}")
    # the largest first; among equals the lower index (a stable sort)
    experts = jnp.argsort(-(s + bias), axis=-1, stable=True)[:, :per_token]
    chosen = jnp.take_along_axis(s, experts, axis=-1)
    return experts, scaling * chosen / jnp.sum(chosen, -1, keepdims=True)


def expert_ffn(lp, n, z, per_token, scaling, mode, router):
    """The expert layer on normed tokens ``n`` (T, d): a loop over the
    experts, each applied to every token and weighed by what the token
    gave it (nothing, if it did not choose it), and the shared expert.
    Also returns the chosen experts (T, k). The experts' matrices are one layer's
    (E, ...) or, with ``experts.at``, the stacks of several."""
    experts, weights = choose_experts(
        n, lp["experts.w_router"], lp["experts.router_bias"], per_token,
        scaling, router)
    at = lp.get("experts.at")
    cut = lambda name, e: (lp[name][e] if at is None else lax.dynamic_slice(
        lp[name], (at, e, 0, 0), (1, 1) + lp[name].shape[2:])[0, 0])

    def one(acc, e):
        w = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=-1)
        return acc + w[:, None] * gated_ffn(
            n, cut("experts.w_gate", e), cut("experts.w_up", e),
            cut("experts.w_down", e), mode), None

    out, _ = lax.scan(one, jnp.zeros_like(n), jnp.arange(z["e"]))
    return out + gated_ffn(n, lp["experts.ws_gate"], lp["experts.ws_up"],
                           lp["experts.ws_down"], mode), experts


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7, 8, 9))
def block(lp, x, sizes, theta, eps, per_token, scaling, mode, router,
          cache):
    """One decoder block. x (T, d) float32 -> (x, the normed input of
    its feed-forward, the experts its tokens chose: (T, 0) for a dense
    layer)."""
    z = dict(sizes)
    x = x + attention(lp, x, z, theta, eps, mode, cache)
    n = rms_norm(x, lp["ln2_scale"], eps)
    if "wgate" in lp:
        add = gated_ffn(n, lp["wgate"], lp["wup"], lp["wdown"], mode)
        experts = jnp.zeros((x.shape[0], 0), jnp.int32)
    else:
        add, experts = expert_ffn(lp, n, z, per_token, scaling, mode,
                                  router)
    return x + add, n, experts


def forward(weights, tokens, model, first_row=0, n_rows=None, mode="f32",
            router="float32", cache="float32"):
    """tokens (T,) int32 -> (logits (n_rows, vocab) float32 of the rows
    ``first_row .. first_row + n_rows`` (all rows by default); for each
    expert layer the pair (normed tokens (T, d) that its router saw,
    experts chosen (T, k))). The padding after a sequence changes nothing
    before it: every layer is causal."""
    z = _sizes(model)
    sizes = tuple(sorted(z.items()))
    routed = []
    with jax.default_matmul_precision("highest"):
        x = jnp.take(weights["wte"], tokens, axis=0).astype(F32)
        for i in range(z["n"]):
            x, n, experts = block(
                layer_weights(weights, i), x, sizes,
                float(model["rope_theta"]), float(model["norm_eps"]),
                model["experts_per_token"], float(model["routed_scaling"]),
                mode, router, cache)
            if i >= z["lead"]:
                routed.append((n, experts))
        n_rows = x.shape[0] if n_rows is None else n_rows
        logits = _head(x, weights["lnf_scale"], weights["lm_head"],
                       first_row, n_rows, float(model["norm_eps"]), mode)
    return logits, routed


def forward_logits(weights, tokens, model, **kw):
    return forward(weights, tokens, model, **kw)[0]
