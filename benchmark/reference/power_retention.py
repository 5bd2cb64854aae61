"""Plain reference for Brumby-14B-Base's forward pass (manifestai; a
Qwen3-14B-shaped decoder in which every attention layer is power
retention at power 2: Buckman, Gelada, Zhang, "Scaling Context Requires
Rethinking Attention", arXiv:2507.04239): ``jax.numpy``, float32, every
product at ``Precision.HIGHEST``, no kernels, no cache, no state. It
imports nothing of the program and takes only the weights below.

The layer, with ``h`` the RMSNorm of the residual stream ``x``:

- ``q = W_q h`` (H heads), ``k = W_k h``, ``v = W_v h`` (Hkv heads); q and
  k get a per-head RMSNorm over the head and then rotary positions
  (rotate-half, theta from the configuration); ``g = logsigmoid(W_g h)``,
  one per key/value head;
- for query head ``i`` of key/value group ``j`` and ``s <= t``:
  ``a_ts = (q_ti . k_sj)^2 * exp(b_tj - b_sj)`` with ``b`` the cumulative
  sum of ``g`` (never a running product), ``o_ti = sum_s a_ts v_sj /
  sum_s a_ts``: the ATTENTION form, query block by query block, so the
  reference shares no algorithm with the program's state form;
- ``x += W_o o``; ``x += W_down(silu(W_gate n) * (W_up n))`` with ``n`` the
  RMSNorm of ``x``; final RMSNorm; ``logits = W_head x``.

``mode`` is the control, as `gpt_dense.py` has it: ``"fp8"`` / ``"int8"``
round both operands of every matrix product (the weight products, the
scores and the weights-times-values) with one scale per tensor as the
product sees it (the feed-forward and the head go in blocks of rows).
``state="bfloat16"`` is the control one precision below the state's
float32: the state form, token by token, with ``S`` and ``z`` rounded to
bfloat16 after every token (``phi`` here is the upper triangle with
``sqrt(2)`` off the diagonal: not the program's layout).

Weights: ``make_weights`` draws a layer at a time into stacks of
``n_layers`` (one jitted program, donated, for every layer; another for
each block of the two vocabulary matrices), so that the maker reserves a
gigabyte beside what it makes. ``{"wte", "lm_head", "lnf_scale",
"layers": {leaf: (n_layers, ...)}}``: the program's engine scans over
the stacks as they are (no second copy of 5.3 GB of blocks), and the
reference cuts one layer out at a time.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = 512
ROW_BLOCK = 2048                # feed-forward and head, in blocks of rows
MATRIX_STD = 0.02
SCALE_STD = 0.02
VOCAB_BLOCKS = 8

LAYER_LEAVES = ("ln1_scale", "wqkv", "q_norm", "k_norm", "wg", "wo",
                "ln2_scale", "wgate", "wup", "wdown")


# ------------------------------------------------------------------ weights
def _seed_words(seed: int):
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF


def _draw(key, shape, std, dtype):
    a = std * 3.0 ** 0.5
    return jax.random.uniform(key, shape, F32, -a, a).astype(dtype)


@functools.lru_cache(maxsize=None)
def _layer_maker(n_layers, d, n_heads, kv_heads, head_dim, d_ffn, dtype):
    dt = jnp.dtype(dtype)
    resid_std = MATRIX_STD / (2 * n_layers) ** 0.5
    qkv = (n_heads + 2 * kv_heads) * head_dim

    def draw(lo, hi, layer):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.key(lo, impl="rbg"), hi), layer)
        k = jax.random.split(key, len(LAYER_LEAVES))
        return {
            "ln1_scale": 1.0 + _draw(k[0], (d,), SCALE_STD, F32),
            "wqkv": _draw(k[1], (d, qkv), MATRIX_STD, dt),
            "q_norm": 1.0 + _draw(k[2], (head_dim,), SCALE_STD, F32),
            "k_norm": 1.0 + _draw(k[3], (head_dim,), SCALE_STD, F32),
            "wg": _draw(k[4], (d, kv_heads), MATRIX_STD, dt),
            "wo": _draw(k[5], (n_heads * head_dim, d), resid_std, dt),
            "ln2_scale": 1.0 + _draw(k[6], (d,), SCALE_STD, F32),
            "wgate": _draw(k[7], (d, d_ffn), MATRIX_STD, dt),
            "wup": _draw(k[8], (d, d_ffn), MATRIX_STD, dt),
            "wdown": _draw(k[9], (d_ffn, d), resid_std, dt),
        }

    def fill(stacked, lo, hi, layer):
        """Layer ``layer`` drawn and laid into the (donated) stacks."""
        return {name: lax.dynamic_update_index_in_dim(
            stacked[name], leaf, layer, axis=0)
            for name, leaf in draw(lo, hi, layer).items()}

    empty = lambda: {
        name: jnp.zeros((n_layers,) + sds.shape, sds.dtype) for name, sds
        in jax.eval_shape(draw, 0, 0, 0).items()}
    return jax.jit(fill, donate_argnums=(0,)), empty


@functools.lru_cache(maxsize=None)
def _table_maker(rows, d, dtype):
    """A (rows, d) matrix filled block by block into one donated buffer."""
    dt = jnp.dtype(dtype)
    block = -(-rows // VOCAB_BLOCKS)

    def fill(out, lo, hi, which, i):
        key = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
            jax.random.key(lo, impl="rbg"), hi), 1000 + which), i)
        # the last block is laid so that it ends with the matrix
        start = jnp.minimum(i * block, rows - block)
        return lax.dynamic_update_slice(
            out, _draw(key, (block, d), MATRIX_STD, dt), (start, 0))

    return jax.jit(fill, donate_argnums=(0,)), dt


def make_weights(model: dict, seed: int) -> dict:
    """``model`` is the ``model`` group of the configuration's file."""
    lo, hi = (jnp.int32(w) for w in _seed_words(seed))
    d, v = model["d_model"], model["vocab_size"]
    fill_layer, empty = _layer_maker(
        model["n_layers"], d, model["n_heads"], model["n_kv_heads"],
        model["head_dim"], model["d_ffn"], model["dtype"])
    layers = empty()
    for i in range(model["n_layers"]):
        layers = fill_layer(layers, lo, hi, jnp.int32(i))
    fill, dt = _table_maker(v, d, model["dtype"])

    def table(which):
        out = jnp.zeros((v, d), dt)
        for i in range(VOCAB_BLOCKS):
            out = fill(out, lo, hi, jnp.int32(which), jnp.int32(i))
        return out

    key = jax.random.fold_in(jax.random.key(lo, impl="rbg"), hi)
    return {
        "wte": table(0),
        "lm_head": table(1).T,                     # (d, vocab), as stored
        "lnf_scale": 1.0 + _draw(jax.random.fold_in(key, 999), (d,),
                                 SCALE_STD, F32),
        "layers": layers,
    }


def layer_weights(weights: dict, i: int) -> dict:
    """Layer ``i``'s leaves, cut from the stacks."""
    return {name: leaf[i] for name, leaf in weights["layers"].items()}


# ----------------------------------------------------------------- products
def _round_fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _round_int8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _operands(a, b, mode):
    a, b = a.astype(F32), b.astype(F32)
    if mode == "fp8":
        return _round_fp8(a), _round_fp8(b)
    if mode == "int8":
        return _round_int8(a), _round_int8(b)
    if mode != "f32":
        raise ValueError(f"unknown mode {mode!r}")
    return a, b


def _mm(a, b, mode):
    a, b = _operands(a, b, mode)
    return jnp.matmul(a, b, precision=HIGHEST)


def _by_rows(fn, x, block):
    """``fn`` over blocks of ``block`` rows of ``x`` (one program for
    every block), where the rows divide so; else over all rows at once
    (the tests' short sequences)."""
    t = x.shape[0]
    if t <= block or t % block:
        return fn(x, 0)
    out = lax.map(lambda xs: fn(*xs), (
        x.reshape(t // block, block, *x.shape[1:]),
        jnp.arange(0, t, block)))
    return out.reshape(t, *out.shape[2:])


# ------------------------------------------------------------------- layers
def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                         + eps) * scale.astype(F32)


def rope(x, theta):
    """(T, heads, D) at positions 0..T-1, rotate-half."""
    t, _, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(t, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def retention_attention(q, k, v, g, mode="f32"):
    """The attention form. q (T, H, D); k, v (T, Hkv, D); g (T, Hkv) the
    log of the gate. Query blocks of QUERY_BLOCK rows, each against
    every key with those after it masked."""
    t, n_heads, d = q.shape
    kv_heads = k.shape[1]
    b = jnp.cumsum(g, axis=0)                              # (T, Hkv)
    qg = q.reshape(t, kv_heads, n_heads // kv_heads, d)

    def rows(xs, start):
        qb, bb = xs
        n = qb.shape[0]
        qb, kb = _operands(qb, k, mode)
        s = jnp.einsum("tjgd,sjd->jgts", qb, kb, precision=HIGHEST)
        seen = jnp.arange(t)[None, :] <= start + jnp.arange(n)[:, None]
        lag = bb.T[:, :, None] - b.T[:, None, :]
        decay = jnp.where(seen, jnp.exp(jnp.where(seen, lag, 0.0)), 0.0)
        a = s * s * decay[:, None]                          # (Hkv,g,t,s)
        ab, vb = _operands(a, v, mode)
        num = jnp.einsum("jgts,sjd->tjgd", ab, vb, precision=HIGHEST)
        den = jnp.sum(a, axis=-1).transpose(2, 0, 1)[..., None]
        return (num / den).reshape(n, n_heads, d)

    if t <= QUERY_BLOCK or t % QUERY_BLOCK:
        return rows((qg, b), 0)
    n = t // QUERY_BLOCK
    out = lax.map(lambda xs: rows(xs[:2], xs[2]), (
        qg.reshape(n, QUERY_BLOCK, *qg.shape[1:]),
        b.reshape(n, QUERY_BLOCK, kv_heads), jnp.arange(0, t, QUERY_BLOCK)))
    return out.reshape(t, n_heads, d)


def _phi_triangle(x):
    """(..., D) -> (..., D (D + 1) / 2): x_a x_b for a <= b, sqrt(2) off
    the diagonal, so that phi(x) . phi(y) = (x . y)^2."""
    d = x.shape[-1]
    rows, cols = jnp.triu_indices(d)
    c = jnp.where(rows == cols, 1.0, math.sqrt(2.0)).astype(F32)
    return x[..., rows] * x[..., cols] * c


def retention_state_form(q, k, v, g, state_dtype):
    """The state form, token by token, with ``S`` and ``z`` rounded to
    ``state_dtype`` after every token (the control below float32)."""
    t, n_heads, d = q.shape
    kv_heads = k.shape[1]
    group = n_heads // kv_heads
    dp = d * (d + 1) // 2
    keep = lambda x: x.astype(state_dtype).astype(F32)

    def one(carry, xs):
        S, z = carry
        qt, kt, vt, gt = xs
        pk = _phi_triangle(kt)                             # (Hkv, P)
        a = jnp.exp(gt)[:, None]
        S = keep(a[..., None] * S + pk[:, :, None] * vt[:, None, :])
        z = keep(a * z + pk)
        pq = _phi_triangle(qt.reshape(kv_heads, group, d))
        num = jnp.einsum("jgp,jpd->jgd", pq, S, precision=HIGHEST)
        den = jnp.einsum("jgp,jp->jg", pq, z, precision=HIGHEST)
        return (S, z), (num / den[..., None]).reshape(n_heads, d)

    init = (jnp.zeros((kv_heads, dp, d), F32), jnp.zeros((kv_heads, dp),
                                                         F32))
    return lax.scan(one, init, (q, k, v, g))[1]


def mix_inputs(lp, x, n_heads, kv_heads, theta, eps, mode):
    """x (T, d) -> q (T, H, D), k, v (T, Hkv, D), g (T, Hkv)."""
    t, _ = x.shape
    h = rms_norm(x, lp["ln1_scale"], eps)
    qkv = _mm(h, lp["wqkv"], mode)
    hd = qkv.shape[-1] // (n_heads + 2 * kv_heads)
    q = qkv[:, :n_heads * hd].reshape(t, n_heads, hd)
    k = qkv[:, n_heads * hd:(n_heads + kv_heads) * hd].reshape(
        t, kv_heads, hd)
    v = qkv[:, (n_heads + kv_heads) * hd:].reshape(t, kv_heads, hd)
    q = rope(rms_norm(q, lp["q_norm"], eps), theta)
    k = rope(rms_norm(k, lp["k_norm"], eps), theta)
    g = jax.nn.log_sigmoid(_mm(h, lp["wg"], mode))
    return q, k, v, g


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7))
def block(lp, x, n_heads, kv_heads, theta, eps, mode, state):
    """One decoder block. x (T, d) float32."""
    t, d = x.shape
    q, k, v, g = mix_inputs(lp, x, n_heads, kv_heads, theta, eps, mode)
    o = (retention_attention(q, k, v, g, mode) if state == "float32" else
         retention_state_form(q, k, v, g, jnp.dtype(state)))
    x = x + _mm(o.reshape(t, -1), lp["wo"], mode)

    def ffn(rows, _):
        n = rms_norm(rows, lp["ln2_scale"], eps)
        gated = jax.nn.silu(_mm(n, lp["wgate"], mode)) \
            * _mm(n, lp["wup"], mode)
        return rows + _mm(gated, lp["wdown"], mode)

    return _by_rows(ffn, x, ROW_BLOCK)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _head(x, lnf_scale, lm_head, first_row, n_rows, eps, mode):
    x = lax.dynamic_slice_in_dim(x, first_row, n_rows, axis=0)
    return _by_rows(
        lambda rows, _: _mm(rms_norm(rows, lnf_scale, eps), lm_head, mode),
        x, ROW_BLOCK)


def forward_logits(weights, tokens, model, first_row=0, n_rows=None,
                   mode="f32", state="float32"):
    """tokens (T,) int32 -> logits (n_rows, vocab) float32 of the rows
    ``first_row .. first_row + n_rows`` (all rows by default). The
    padding after a sequence changes nothing before it: every layer is
    causal. ``model`` is the ``model`` group of the configuration."""
    with jax.default_matmul_precision("highest"):
        x = jnp.take(weights["wte"], tokens, axis=0).astype(F32)
        for i in range(model["n_layers"]):
            x = block(layer_weights(weights, i), x, model["n_heads"],
                      model["n_kv_heads"],
                      float(model["rope_theta"]),
                      float(model["norm_eps"]), mode, state)
        n_rows = x.shape[0] if n_rows is None else n_rows
        return _head(x, weights["lnf_scale"], weights["lm_head"],
                     first_row, n_rows, float(model["norm_eps"]), mode)
