"""Plain reference for the GPT-3 block (Brown et al. 2020; GPT-2's layer
equations): ``jax.numpy``, float32 arithmetic with every product at
``Precision.HIGHEST``, no kernels, no cache, no scan. It imports nothing of
the program and takes only the benchmark's own weights (``weights.py``).

What it follows of the configuration: the *stored* types. Parameters are
rounded to their stored dtype after every update and AdamW's moments to
theirs, because the configuration states bfloat16 storage with no float32
master copy; everything between two stores is float32.

It runs layer by layer (one jitted layer forward, one jitted layer
backward, used for every layer) so that GPT-3 XL's three checked training
steps fit beside nothing else on a 16 GB chip, attention goes query block
by query block, and the output head goes in blocks of rows.

``mode`` selects how the weight products are computed:

- ``"f32"``  the reference itself;
- ``"fp8"``  the control: both operands of every matrix product — the
  weight products and attention's two (scores, and probabilities times
  values) — rounded to float8 e4m3 with one scale per tensor (the nearest
  precision below the bfloat16 the configurations state), straight-through
  in the backward;
- ``"bf16"`` operands rounded to bfloat16 (used by the tests only).

``rows`` (training) plants the fault "half of the batch left out, the mean
taken over the rest" when it is set to fewer rows than the batch has.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
LN_EPS = 1e-5
QUERY_BLOCK = 512
HEAD_ROW_BLOCK = 2048


# ---------------------------------------------------------------- products
def _round_fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    y = (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    return x + lax.stop_gradient(y - x)


def _round_int8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    y = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + lax.stop_gradient(y - x)


def _round_bf16(x):
    return x + lax.stop_gradient(x.astype(jnp.bfloat16).astype(F32) - x)


def _operands(a, b, mode):
    """The two operands of a matrix product as ``mode`` would hold them."""
    a, b = a.astype(F32), b.astype(F32)
    if mode == "fp8":
        return _round_fp8(a), _round_fp8(b)
    if mode == "int8":
        return _round_int8(a), _round_int8(b)
    if mode == "bf16":
        return _round_bf16(a), _round_bf16(b)
    if mode != "f32":
        raise ValueError(f"unknown mode {mode!r}")
    return a, b


def _mm(a, b, mode):
    """A weight product ``a @ b`` in ``mode``."""
    a, b = _operands(a, b, mode)
    return jnp.matmul(a, b, precision=HIGHEST)


# ------------------------------------------------------------------ layers
def layer_norm(x, scale, bias):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + LN_EPS) * scale.astype(F32) \
        + bias.astype(F32)


def _attend_block(q_blk, k, v, first_row, mode):
    """Causal softmax attention of one block of query rows against the
    keys up to its last row. q_blk (B, Q, H, D); k, v (B, K, H, D)."""
    d = q_blk.shape[-1]
    q_blk, k = _operands(q_blk, k, mode)
    s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k, precision=HIGHEST)
    s = s / math.sqrt(d)
    rows = first_row + jnp.arange(q_blk.shape[1])[:, None]
    cols = jnp.arange(k.shape[1])[None, :]
    s = jnp.where(cols <= rows, s, -jnp.inf)
    p, v = _operands(jax.nn.softmax(s, axis=-1), v, mode)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)


def causal_attention(q, k, v, mode):
    """(B, S, H, D) each. Query blocks of QUERY_BLOCK rows, each
    recomputed in the backward so that no (S, S) score matrix is kept."""
    S = q.shape[1]
    out = []
    for start in range(0, S, QUERY_BLOCK):
        end = min(start + QUERY_BLOCK, S)
        out.append(jax.checkpoint(_attend_block, static_argnums=(3, 4))(
            q[:, start:end], k[:, :end], v[:, :end], start, mode))
    return jnp.concatenate(out, axis=1)


def block(lp, x, n_heads, mode):
    """One pre-LayerNorm decoder block. x (B, S, d) float32."""
    B, S, d = x.shape
    hd = d // n_heads
    h = layer_norm(x, lp["ln1_scale"], lp["ln1_bias"])
    qkv = _mm(h, lp["wqkv"], mode) + lp["bqkv"].astype(F32)
    q, k, v = (qkv[..., i * d:(i + 1) * d].reshape(B, S, n_heads, hd)
               for i in range(3))
    a = causal_attention(q, k, v, mode).reshape(B, S, d)
    x = x + _mm(a, lp["wo"], mode) + lp["bo"].astype(F32)
    h = layer_norm(x, lp["ln2_scale"], lp["ln2_bias"])
    h = jax.nn.gelu(_mm(h, lp["wup"], mode) + lp["bup"].astype(F32),
                    approximate=True)
    return x + _mm(h, lp["wdown"], mode) + lp["bdown"].astype(F32)


def embed(top, tokens):
    S = tokens.shape[-1]
    return jnp.take(top["wte"].astype(F32), tokens, axis=0) \
        + top["wpe"].astype(F32)[:S]


def logits_of(top, x, mode):
    """Output head: final LayerNorm, then the tied token table."""
    h = layer_norm(x, top["lnf_scale"], top["lnf_bias"])
    return _mm(h, top["wte"].astype(F32).T, mode)


def _ce_rows(top, x_rows, labels, mode):
    lg = logits_of(top, x_rows, mode)
    logz = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(logz - picked)


def lm_loss(top, x, tokens, mode):
    """Mean next-token cross-entropy over the B * (S - 1) predictions, the
    head taken HEAD_ROW_BLOCK rows at a time."""
    B, S, d = x.shape
    rows = x[:, :-1].reshape(B * (S - 1), d)
    labels = tokens[:, 1:].reshape(-1)
    total = jnp.zeros((), F32)
    for start in range(0, rows.shape[0], HEAD_ROW_BLOCK):
        end = min(start + HEAD_ROW_BLOCK, rows.shape[0])
        total = total + jax.checkpoint(_ce_rows, static_argnums=(3,))(
            top, rows[start:end], labels[start:end], mode)
    return total / (B * (S - 1))


# ----------------------------------------------------------- jitted pieces
@functools.partial(jax.jit, static_argnums=(2, 3))
def _block_fwd(lp, x, n_heads, mode):
    return block(lp, x, n_heads, mode)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _block_bwd(lp, x, dy, n_heads, mode):
    _, vjp = jax.vjp(lambda p, xx: block(p, xx, n_heads, mode), lp, x)
    return vjp(dy)                      # (d lp, d x)


@jax.jit
def _embed_fwd(top, tokens):
    return embed(top, tokens)


@functools.partial(jax.jit, static_argnums=(3,))
def _head_loss_and_grad(top, x, tokens, mode):
    loss, (d_top, d_x) = jax.value_and_grad(
        lambda t, xx: lm_loss(t, xx, tokens, mode), argnums=(0, 1))(top, x)
    return loss, d_top, d_x


@jax.jit
def _embed_bwd(top, tokens, d_x):
    _, vjp = jax.vjp(lambda t: embed(t, tokens), top)
    return vjp(d_x)[0]


@functools.partial(jax.jit, static_argnums=(2,))
def _logits_fwd(top, x, mode):
    return logits_of(top, x, mode)


def _top(weights):
    return {k: v for k, v in weights.items() if k != "layers"}


# ----------------------------------------------------------------- forward
def forward_logits(weights, tokens, n_heads, mode="f32"):
    """Logits (B, S, V) of a full forward pass over ``tokens`` (B, S):
    what prefill followed by cached decoding has to agree with."""
    top = _top(weights)
    x = _embed_fwd(top, tokens)
    for lp in weights["layers"]:
        x = _block_fwd(lp, x, n_heads, mode)
    return _logits_fwd(top, x, mode)


# ---------------------------------------------------------------- training
def _adamw_leaf(p, g, m, v, t, hp):
    b1, b2 = hp["beta1"], hp["beta2"]
    p32, g = p.astype(F32), g.astype(F32)
    m32 = b1 * m.astype(F32) + (1 - b1) * g
    v32 = b2 * v.astype(F32) + (1 - b2) * jnp.square(g)
    mhat = m32 / (1 - b1 ** t)
    vhat = v32 / (1 - b2 ** t)
    new_p = p32 - hp["learning_rate"] * mhat / (jnp.sqrt(vhat)
                                                 + hp["epsilon"])
    new_p = new_p - hp["learning_rate"] * hp["weight_decay"] * p32
    return new_p.astype(p.dtype), m32.astype(m.dtype), v32.astype(v.dtype)


@functools.partial(jax.jit, static_argnums=(5,), donate_argnums=(0, 2, 3))
def _adamw(params, grads, m, v, t, hp_items):
    hp = dict(hp_items)
    out = jax.tree_util.tree_map(
        lambda p, g, mm, vv: _adamw_leaf(p, g, mm, vv, t, hp),
        params, grads, m, v)
    pick = lambda i: jax.tree_util.tree_map(
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


def split_qkv_leaves(name, x):
    """The fused query/key/value leaves are compared part by part: the
    key bias has no gradient under softmax, and a rule on the reference's
    gradient has to be able to leave that part out alone."""
    if name in ("wqkv", "bqkv"):
        d = x.shape[-1] // 3
        return {f"{name}.{part}": x[..., i * d:(i + 1) * d]
                for i, part in enumerate("qkv")}
    return {name: x}


def _leaf_norms_impl(tree):
    out = {}
    for name, x in tree.items():
        for sub, part in split_qkv_leaves(name, x).items():
            out[sub] = jnp.sqrt(jnp.sum(jnp.square(part.astype(F32))))
    return out


_leaf_norms = jax.jit(_leaf_norms_impl)


@jax.jit
def _diff_norms(new, old):
    return _leaf_norms_impl(jax.tree_util.tree_map(
        lambda a, b: a.astype(F32) - b.astype(F32), new, old))


def _copy(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


def train_steps(weights, batches, n_heads, hp, mode="f32", rows=None):
    """Drive ``len(batches)`` AdamW steps from ``weights`` (left untouched)
    over ``batches`` (each (B, S) int32) and return::

        {"loss": [per step], "grad_norm": {leaf: norm at step 1},
         "change_norm": {leaf: norm of (parameters after the last step -
                                        parameters at the start)}}

    with leaves named ``wte``, ``wpe``, ``lnf_scale``, ``lnf_bias`` and
    ``layers.<i>.<leaf>`` (fused qkv leaves split, see above).
    """
    hp_items = tuple(sorted(
        (k, float(hp[k])) for k in ("learning_rate", "beta1", "beta2",
                                    "epsilon", "weight_decay")))
    mdt = jnp.dtype(hp["moment_dtype"])
    top0, layers0 = _top(weights), weights["layers"]
    top, layers = _copy(top0), [_copy(lp) for lp in layers0]
    zeros = lambda tree: jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, mdt), tree)
    m_top, v_top = zeros(top), zeros(top)
    m_l = [zeros(lp) for lp in layers]
    v_l = [zeros(lp) for lp in layers]
    losses, grad_norm = [], {}
    for step, tokens in enumerate(batches, start=1):
        tokens = jnp.asarray(tokens, jnp.int32)
        if rows is not None:
            tokens = tokens[:rows]
        t = jnp.float32(step)
        xs = [_embed_fwd(top, tokens)]
        for lp in layers:
            xs.append(_block_fwd(lp, xs[-1], n_heads, mode))
        loss, d_top, d_x = _head_loss_and_grad(top, xs.pop(), tokens, mode)
        losses.append(loss)
        for i in reversed(range(len(layers))):
            d_lp, d_x = _block_bwd(layers[i], xs.pop(), d_x, n_heads, mode)
            if step == 1:
                for k, n in _leaf_norms(d_lp).items():
                    grad_norm[f"layers.{i}.{k}"] = n
            layers[i], m_l[i], v_l[i] = _adamw(
                layers[i], d_lp, m_l[i], v_l[i], t, hp_items)
        d_emb = _embed_bwd(top, tokens, d_x)
        d_top = jax.tree_util.tree_map(jnp.add, d_top, d_emb)
        if step == 1:
            grad_norm.update(_leaf_norms(d_top))
        top, m_top, v_top = _adamw(top, d_top, m_top, v_top, t, hp_items)
    change = dict(_diff_norms(top, top0))
    for i, (new, old) in enumerate(zip(layers, layers0)):
        for k, n in _diff_norms(new, old).items():
            change[f"layers.{i}.{k}"] = n
    out = jax.device_get({"loss": losses, "grad_norm": grad_norm,
                          "change_norm": change})
    for tree in (top, layers, m_top, v_top, m_l, v_l):
        for leaf in jax.tree_util.tree_leaves(tree):
            leaf.delete()
    return {"loss": [float(x) for x in out["loss"]],
            "grad_norm": {k: float(v) for k, v in out["grad_norm"].items()},
            "change_norm": {k: float(v)
                            for k, v in out["change_norm"].items()}}
