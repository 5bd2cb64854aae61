"""Flash attention (forward + backward) as Pallas TPU kernels.

Reference analog: paddle/fluid/operators/fused/fused_attention_op.cu,
fmha_ref.h (dropout), fused_softmax_mask.cu.h (mask fusion). This is the
TPU-native re-design: an online-softmax (FlashAttention-2 style) kernel
tiled for the MXU, with a custom VJP whose backward recomputes attention
probabilities from the saved log-sum-exp instead of materializing the
(S, S) matrix.

v2 capabilities (VERDICT r2 item 3):
- **Key-padding masks** via per-example ``kv_lens`` (the BERT path): each
  batch row attends to its first ``kv_lens[b]`` keys; fully-masked KV
  blocks are skipped, not just masked.
- **Additive bias** of shape (B|1, H|1, Sq, Sk) (e.g. relative-position or
  arbitrary additive masks), blocked into the kernel without materializing
  a (B, H, Sq, Sk) tensor when a broadcast dim is 1. The bias is treated
  as a constant: its cotangent is zero (use the XLA reference path to
  train through a bias).
- **Deterministic dropout** on the attention probabilities from an explicit
  integer seed: the keep-mask is a counter-based hash PRF of
  (head, row, col, seed), so forward and backward regenerate identical
  masks with zero residual memory (≙ fmha_ref.h's Philox dropout).
- **GQA**: ``k``/``v`` may carry fewer heads than ``q`` (Hq % Hkv == 0);
  query head h reads kv head h // (Hq // Hkv).

Layout contract: public API takes (B, S, H, D) like
paddle.nn.functional.scaled_dot_product_attention; kernels operate on
(B*H, S, D). Sequence dims are zero-padded to tile multiples; KV padding
is masked inside the kernel, Q padding is sliced off (its gradient
contributions vanish because the padded dO rows are zero).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

_LANES = 128
_NEG_INF = float("-inf")


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _keep_mask(seed, bh, i, j, block_q, block_k, sk_total, rate):
    """Counter-based keep mask: lowbias32 hash of the global (row, col)
    cell index mixed with (seed, head). Deterministic across fwd/bwd."""

    def mix(x):
        x = x ^ (x >> 16)
        x = x * jnp.uint32(0x7FEB352D)
        x = x ^ (x >> 15)
        x = x * jnp.uint32(0x846CA68B)
        return x ^ (x >> 16)

    row = (i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)).astype(jnp.uint32)
    col = (j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)).astype(jnp.uint32)
    lin = row * jnp.uint32(sk_total) + col
    h = mix(mix(lin ^ seed.astype(jnp.uint32)) ^ bh.astype(jnp.uint32))
    thresh = jnp.uint32(min(int(rate * 2.0**32), 2**32 - 1))
    return h >= thresh


def _mask_cols(s, kvlen, i, j, block_q, block_k, causal):
    col = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = col < kvlen
    if causal:
        row = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        mask = jnp.logical_and(mask, row >= col)
    return jnp.where(mask, s, _NEG_INF)


# ---------------------------------------------------------------------------
# Forward kernel: grid (BH, nq, nk); nk is the innermost "arbitrary" dim with
# running (m, l, acc) scratch carried across kv blocks.
# ---------------------------------------------------------------------------


def _fwd_kernel(*refs, causal, scale, block_q, block_k, has_bias,
                bias_sq1, dropout_rate, sk_total):
    kvlen_ref, seed_ref, q_ref, k_ref, v_ref = refs[:5]
    idx = 5
    bias_ref = refs[idx] if has_bias else None
    idx += int(has_bias)
    o_ref, lse_ref, acc_ref, m_ref, l_ref = refs[idx:idx + 5]

    bh = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kvlen = kvlen_ref[bh]
    # Causal: blocks strictly above the diagonal contribute nothing.
    # KV blocks entirely beyond this row's valid length are skipped.
    run = jnp.logical_and(
        (j * block_k <= (i + 1) * block_q - 1) if causal else (j >= 0),
        j * block_k < kvlen)

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if has_bias:
            s = s + bias_ref[0].astype(jnp.float32)
        s = _mask_cols(s, kvlen, i, j, block_q, block_k, causal)

        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # finite floor: a block whose every cell is masked (-inf bias)
        # must give p = exp(-inf - m_cur) = 0, not exp(-inf + inf) = NaN
        m_cur = jnp.maximum(m_cur, -1e30)
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, :1])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        if dropout_rate > 0.0:
            keep = _keep_mask(seed_ref[0], bh, i, j, block_q, block_k,
                              sk_total, dropout_rate)
            p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
        acc_ref[...] = (acc_ref[...] * alpha[:, :1]
                        + jax.lax.dot(p.astype(v.dtype), v,
                                      preferred_element_type=jnp.float32))
        m_ref[...] = m_cur

    @pl.when(j == nk - 1)
    def _finalize():
        # rows with zero valid keys (kvlen == 0) produce 0 output and a
        # finite lse so the backward recomputation stays NaN-free
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        # lane-broadcast (block_q, 128) layout: Mosaic requires the last two
        # block dims to be (8k, 128m); a (1, block_q) row block is rejected
        m_safe = jnp.where(m_ref[...] == _NEG_INF, 0.0, m_ref[...])
        lse_ref[0] = m_safe + jnp.log(jnp.where(l_ref[...] == 0.0, 1.0,
                                                l_ref[...]))


def _bias_group(bias_mode, h_q):
    """Index map component selecting the bias leading dim from the bh grid
    index, for bias collapsed to (G, Sq|1, Sk)."""
    if bias_mode == "one":
        return lambda b: 0
    if bias_mode == "batch":
        return lambda b: b // h_q
    if bias_mode == "head":
        return lambda b: b % h_q
    return lambda b: b  # "bh"


def _bias_spec(bias_sq1, block_q, block_k, g, grid_ij):
    """Bias BlockSpec: a size-1 Sq dim stays size-1 (index map pins it to
    block 0) so a key-only mask is never broadcast to (..., Sq, Sk) in HBM;
    the kernel's `s + bias` broadcasts it across rows for free."""
    bq = 1 if bias_sq1 else block_q
    if grid_ij:  # grid (b, i, j)
        return pl.BlockSpec(
            (1, bq, block_k),
            lambda b, i, j: (g(b), 0 if bias_sq1 else i, j))
    # grid (b, j, i) — the dk/dv pass
    return pl.BlockSpec(
        (1, bq, block_k),
        lambda b, j, i: (g(b), 0 if bias_sq1 else i, j))


def _fa_forward(q, k, v, kvlen, seed, bias, causal, scale, block_q, block_k,
                group, bias_mode, bias_sq1, h_q, dropout_rate, interpret):
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq, nk = sq // block_q, sk // block_k
    has_bias = bias is not None
    kernel = functools.partial(
        _fwd_kernel, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, has_bias=has_bias, bias_sq1=bias_sq1,
        dropout_rate=dropout_rate, sk_total=sk)
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d),
                     lambda b, i, j: (b // group, j, 0)),
        pl.BlockSpec((1, block_k, d),
                     lambda b, i, j: (b // group, j, 0)),
    ]
    args = [kvlen, seed, q, k, v]
    if has_bias:
        g = _bias_group(bias_mode, h_q)
        in_specs.append(_bias_spec(bias_sq1, block_q, block_k, g,
                                   grid_ij=True))
        args.append(bias)
    # ptlint: disable=PT009 -- flash forward streams the FULL K/V per
    # query block by construction (online softmax): the seq/block_q
    # re-read is the O(block) -memory tradeoff the kernel exists for.
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_attention_fwd",
        interpret=interpret,
    )(*args)
    return out, lse


# ---------------------------------------------------------------------------
# Backward kernels. dK/dV: grid (BH, nk, nq) accumulating over q blocks.
# dQ: grid (BH, nq, nk) accumulating over kv blocks. Probabilities are
# recomputed from the saved LSE; delta = rowsum(dO * O) is precomputed.
# ---------------------------------------------------------------------------


def _recompute_p(q_ref, k_ref, bias_ref, lse_ref, kvlen, i, j, causal,
                 scale, block_q, block_k, has_bias):
    q = q_ref[0]
    k = k_ref[0]
    lse = lse_ref[0][:, :1]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if has_bias:
        s = s + bias_ref[0].astype(jnp.float32)
    s = _mask_cols(s, kvlen, i, j, block_q, block_k, causal)
    return jnp.exp(s - lse)


def _bwd_dkdv_kernel(*refs, causal, scale, block_q, block_k, has_bias,
                     bias_sq1, dropout_rate, sk_total):
    kvlen_ref, seed_ref, q_ref, k_ref, v_ref, do_ref = refs[:6]
    idx = 6
    bias_ref = refs[idx] if has_bias else None
    idx += int(has_bias)
    lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc = refs[idx:idx + 6]

    bh = pl.program_id(0)
    j = pl.program_id(1)
    i = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    kvlen = kvlen_ref[bh]
    run = jnp.logical_and(
        ((i + 1) * block_q - 1 >= j * block_k) if causal else (i >= 0),
        j * block_k < kvlen)

    @pl.when(run)
    def _body():
        q = q_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        delta = delta_ref[0][:, :1]
        p = _recompute_p(q_ref, k_ref, bias_ref, lse_ref, kvlen, i, j,
                         causal, scale, block_q, block_k, has_bias)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            keep = _keep_mask(seed_ref[0], bh, i, j, block_q, block_k,
                              sk_total, dropout_rate)
            p_d = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
            dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
        else:
            p_d = p
        dv_acc[...] += jax.lax.dot_general(
            p_d.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(*refs, causal, scale, block_q, block_k, has_bias,
                   bias_sq1, dropout_rate, sk_total):
    kvlen_ref, seed_ref, q_ref, k_ref, v_ref, do_ref = refs[:6]
    idx = 6
    bias_ref = refs[idx] if has_bias else None
    idx += int(has_bias)
    lse_ref, delta_ref, dq_ref, dq_acc = refs[idx:idx + 4]

    bh = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    kvlen = kvlen_ref[bh]
    run = jnp.logical_and(
        (j * block_k <= (i + 1) * block_q - 1) if causal else (j >= 0),
        j * block_k < kvlen)

    @pl.when(run)
    def _body():
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        delta = delta_ref[0][:, :1]
        p = _recompute_p(q_ref, k_ref, bias_ref, lse_ref, kvlen, i, j,
                         causal, scale, block_q, block_k, has_bias)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            keep = _keep_mask(seed_ref[0], bh, i, j, block_q, block_k,
                              sk_total, dropout_rate)
            dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        dq_acc[...] += jax.lax.dot(ds, k,
                                   preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _fa_backward(q, k, v, kvlen, seed, bias, out, lse, do, causal, scale,
                 block_q, block_k, group, bias_mode, bias_sq1, h_q,
                 dropout_rate, interpret):
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq, nk = sq // block_q, sk // block_k
    delta = jnp.broadcast_to(
        jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                axis=-1, keepdims=True), (bh, sq, _LANES))
    has_bias = bias is not None

    kw = dict(causal=causal, scale=scale, block_q=block_q, block_k=block_k,
              has_bias=has_bias, bias_sq1=bias_sq1,
              dropout_rate=dropout_rate, sk_total=sk)
    g = _bias_group(bias_mode, h_q)

    # dK/dV pass: grid (b, j, i)
    kvspec = pl.BlockSpec(memory_space=pltpu.SMEM)
    sdspec = pl.BlockSpec(memory_space=pltpu.SMEM)
    qspec = pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0))
    kspec = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b // group, j, 0))
    okspec = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0))
    rowspec = pl.BlockSpec((1, block_q, _LANES), lambda b, j, i: (b, i, 0))
    in_specs = [kvspec, sdspec, qspec, kspec, kspec, qspec]
    args = [kvlen, seed, q, k, v, do]
    if has_bias:
        in_specs.append(_bias_spec(bias_sq1, block_q, block_k, g,
                                   grid_ij=False))
        args.append(bias)
    in_specs += [rowspec, rowspec]
    args += [lse, delta]
    # dk/dv are produced per *query* head (b over B*Hq) and group-summed
    # below for GQA
    # ptlint: disable=PT009 -- dk/dv re-streams every Q/dO/LSE row
    # block per K/V tile (flash backward recomputation); inherent to
    # the tiling, not a blocking bug.
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, **kw),
        grid=(bh, nk, nq),
        in_specs=in_specs,
        out_specs=[okspec, okspec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_attention_bwd_dkdv",
        interpret=interpret,
    )(*args)
    if group > 1:
        dk = dk.reshape(-1, group, sk, d).sum(axis=1).astype(k.dtype)
        dv = dv.reshape(-1, group, sk, d).sum(axis=1).astype(v.dtype)

    # dQ pass: grid (b, i, j)
    kvspec2 = pl.BlockSpec(memory_space=pltpu.SMEM)
    sdspec2 = pl.BlockSpec(memory_space=pltpu.SMEM)
    qspec2 = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    kspec2 = pl.BlockSpec((1, block_k, d),
                          lambda b, i, j: (b // group, j, 0))
    rowspec2 = pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0))
    in_specs2 = [kvspec2, sdspec2, qspec2, kspec2, kspec2, qspec2]
    args2 = [kvlen, seed, q, k, v, do]
    if has_bias:
        in_specs2.append(_bias_spec(bias_sq1, block_q, block_k, g,
                                    grid_ij=True))
        args2.append(bias)
    in_specs2 += [rowspec2, rowspec2]
    args2 += [lse, delta]
    # ptlint: disable=PT009 -- dq re-streams the FULL K/V per query
    # block, mirroring the forward's online-softmax walk.
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **kw),
        grid=(bh, nq, nk),
        in_specs=in_specs2,
        out_specs=[qspec2],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_attention_bwd_dq",
        interpret=interpret,
    )(*args2)[0]
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wiring on the padded (BH, S, D) representation
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11, 12,
                                                    13, 14, 15))
def _flash(q, k, v, kvlen, seed, bias, causal, scale, block_q, block_k,
           group, bias_mode, bias_sq1, h_q, dropout_rate, interpret):
    out, _ = _fa_forward(q, k, v, kvlen, seed, bias, causal, scale,
                         block_q, block_k, group, bias_mode, bias_sq1, h_q,
                         dropout_rate, interpret)
    return out


def _flash_fwd(q, k, v, kvlen, seed, bias, causal, scale, block_q, block_k,
               group, bias_mode, bias_sq1, h_q, dropout_rate, interpret):
    out, lse = _fa_forward(q, k, v, kvlen, seed, bias, causal, scale,
                           block_q, block_k, group, bias_mode, bias_sq1,
                           h_q, dropout_rate, interpret)
    return out, (q, k, v, kvlen, seed, bias, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, group, bias_mode, bias_sq1,
               h_q, dropout_rate, interpret, residuals, do):
    import numpy as np
    q, k, v, kvlen, seed, bias, out, lse = residuals
    dq, dk, dv = _fa_backward(q, k, v, kvlen, seed, bias, out, lse, do,
                              causal, scale, block_q, block_k, group,
                              bias_mode, bias_sq1, h_q, dropout_rate,
                              interpret)
    zero_int = lambda x: np.zeros(x.shape, jax.dtypes.float0)  # noqa: E731
    dbias = None if bias is None else jnp.zeros_like(bias)
    return dq, dk, dv, zero_int(kvlen), zero_int(seed), dbias


_flash.defvjp(_flash_fwd, _flash_bwd)


def _tune_key(b, sq, sk, h_q, h_kv, d, dtype, causal, has_kvlens,
              has_bias, has_dropout):
    from paddle_tpu.ops.pallas.autotune import AutotuneCache
    return AutotuneCache.key(
        "flash_attention", b=b, sq=sq, sk=sk, hq=h_q, hkv=h_kv, d=d,
        dtype=str(dtype), causal=bool(causal), kvlens=bool(has_kvlens),
        bias=bool(has_bias), dropout=bool(has_dropout))


# measured default on a v5e chip (see flash_attention docstring); used
# when the autotune cache has no entry for the shape
_DEFAULT_BLOCKS = (256, 512)


def tune_flash_attention(q, k, v, causal=False, scale=None, kv_lens=None,
                         bias=None, dropout_p=0.0, dropout_seed=None,
                         candidates=None, include_bwd=True, iters=3):
    """Eagerly measure flash-attention block candidates on the REAL shapes
    and persist the winner (≙ auto_tune_base.h PickBestKernel — Pallas
    block sizes are trace-time constants, so tuning runs outside jit; any
    later ``flash_attention`` call on these shapes picks the tuned blocks
    from the cache at trace time). Returns ((block_q, block_k), timings).
    """
    import jax as _jax

    from paddle_tpu.ops.pallas import autotune as at

    q, k, v = jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)
    b, sq, h_q, d = q.shape
    sk, h_kv = k.shape[1], k.shape[2]
    key = _tune_key(b, sq, sk, h_q, h_kv, d, q.dtype, causal,
                    kv_lens is not None, bias is not None, dropout_p > 0)
    if candidates is None:
        candidates = [(128, 128), (128, 256), (256, 256), (256, 512),
                      (512, 256), (512, 512), (1024, 512)]
    lim_q, lim_k = _round_up(sq, _LANES), _round_up(sk, _LANES)
    candidates = sorted({(min(bq, lim_q), min(bk, lim_k))
                         for bq, bk in candidates})

    # one jitted callable per candidate, built once: the timing loop must
    # measure kernel runtime, not re-trace/re-compile every call
    jitted = {}

    def build_and_run(cfg):
        if cfg not in jitted:
            bq, bk = cfg

            def fwd(q, k, v, _bq=bq, _bk=bk):
                o = flash_attention(q, k, v, causal=causal, scale=scale,
                                    kv_lens=kv_lens, bias=bias,
                                    dropout_p=dropout_p,
                                    dropout_seed=dropout_seed,
                                    block_q=_bq, block_k=_bk)
                return jnp.sum(o.astype(jnp.float32) ** 2)

            fn = _jax.grad(fwd, argnums=(0, 1, 2)) if include_bwd else fwd
            jitted[cfg] = _jax.jit(fn)
        out = jitted[cfg](q, k, v)
        leaf = _jax.tree_util.tree_leaves(out)[0]
        float(leaf.reshape(-1)[0] if leaf.ndim else leaf)  # sync

    def geom_check(cfg):
        # static PT006 refusal (ISSUE 20): never compile/time a block
        # pair whose VMEM residency cannot fit
        from paddle_tpu.analysis import kernelmodel as km
        bq, bk = cfg

        def dry():
            _jax.eval_shape(
                lambda q, k, v: flash_attention(
                    q, k, v, causal=causal, scale=scale,
                    kv_lens=kv_lens, bias=bias, dropout_p=dropout_p,
                    dropout_seed=dropout_seed, block_q=bq,
                    block_k=bk),
                q, k, v)
        return km.budget_reason(dry)

    return at.tune("flash_attention", key, candidates, build_and_run,
                   iters=iters, geom_check=geom_check)


def flash_attention(q, k, v, causal=False, scale=None, kv_lens=None,
                    bias=None, dropout_p=0.0, dropout_seed=None,
                    block_q=None, block_k=None, interpret=None):
    """Flash attention over (B, S, H, D) inputs; returns (B, S, Hq, D).

    Args:
      q: (B, Sq, Hq, D).
      k, v: (B, Sk, Hkv, D) with Hq % Hkv == 0 (GQA/MQA when Hkv < Hq).
      causal: lower-triangular mask; requires Sq == Sk.
      kv_lens: optional (B,) int32 — per example, keys at positions
        >= kv_lens[b] are masked out (contiguous key-padding mask, the
        BERT case). Blocks wholly beyond the valid length are skipped.
      bias: optional additive attention bias, shape broadcastable to
        (B, Hq, Sq, Sk) with leading dims each either full or 1. Constant
        w.r.t. differentiation (zero cotangent).
      dropout_p / dropout_seed: attention-probability dropout; the mask is
        a deterministic PRF of (seed, head, row, col). ``dropout_seed`` is
        a scalar int32 (array or python int).
      interpret: defaults to True off-TPU so tests run on CPU.

    Default blocks (256, 512) measured 1.48x over the XLA reference path at
    (8, 2048, 16, 64) bf16 fwd+bwd on a v5e chip; (128, 128) was 0.5x.
    """
    q, k, v = jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)
    b, sq, h_q, d = q.shape
    sk, h_kv = k.shape[1], k.shape[2]
    if h_q % h_kv:
        raise ValueError(f"GQA needs Hq % Hkv == 0, got {h_q} vs {h_kv}")
    group = h_q // h_kv
    if causal and sq != sk:
        raise ValueError(
            f"causal flash attention needs sq == sk, got {sq} vs {sk}")
    if dropout_p >= 1.0 or dropout_p < 0.0:
        raise ValueError(f"dropout_p must be in [0, 1), got {dropout_p}")
    if dropout_p > 0.0 and dropout_seed is None:
        raise ValueError("dropout_p > 0 requires dropout_seed")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    if block_q is None or block_k is None:
        # trace-time cache lookup (tune_flash_attention fills it); the
        # measured v5e default otherwise
        from paddle_tpu.ops.pallas.autotune import get_cache
        hit = get_cache().get(_tune_key(
            b, sq, sk, h_q, h_kv, d, q.dtype, causal, kv_lens is not None,
            bias is not None, dropout_p > 0))
        tuned = hit if hit is not None else _DEFAULT_BLOCKS
        block_q = block_q if block_q is not None else tuned[0]
        block_k = block_k if block_k is not None else tuned[1]

    # clamp blocks for short sequences — padding 128 rows up to a 256/512
    # block would multiply the real work
    block_q = min(block_q, _round_up(sq, _LANES))
    block_k = min(block_k, _round_up(sk, _LANES))
    sq_p = _round_up(max(sq, block_q), block_q)
    sk_p = _round_up(max(sk, block_k), block_k)
    # D is NOT padded: Mosaic accepts a block dim equal to the full array
    # dim, and zero-padding 64→128 would double the contraction FLOPs.

    def to3(x, s_p):
        hh = x.shape[2]
        x = jnp.transpose(x, (0, 2, 1, 3)).reshape(b * hh, x.shape[1], d)
        return jnp.pad(x, ((0, 0), (0, s_p - x.shape[1]), (0, 0)))

    if kv_lens is None:
        kvlen3 = jnp.full((b * h_q,), sk, jnp.int32)
    else:
        kv_lens = jnp.minimum(jnp.asarray(kv_lens, jnp.int32), sk)
        kvlen3 = jnp.repeat(kv_lens, h_q)

    seed_arr = jnp.reshape(
        jnp.asarray(0 if dropout_seed is None else dropout_seed,
                    jnp.int32), (1,))

    bias_mode = "one"
    bias_sq1 = False
    bias3 = None
    if bias is not None:
        # -inf is a legal mask value for callers; keep it finite in-kernel
        bias = jnp.maximum(jnp.asarray(bias, jnp.float32), -1e30)
        # broadcast b/h/sk, but keep a size-1 Sq dim: the kernel's bias
        # block pins it to one row, so a key-only mask never materializes
        # the (.., Sq, Sk) tensor in HBM
        bias = jnp.broadcast_to(
            bias, jnp.broadcast_shapes(bias.shape, (1, 1, 1, sk)))
        if bias.ndim != 4:
            raise ValueError(f"bias must be 4-D, got {bias.shape}")
        bb, bh_, bsq, _ = bias.shape
        if bsq not in (1, sq):
            raise ValueError(f"bias Sq dim must be 1 or {sq}, got {bsq}")
        bias_sq1 = bsq == 1
        if (bb, bh_) == (1, 1):
            bias_mode = "one"
        elif bh_ == 1:
            bias_mode = "batch"
        elif bb == 1:
            bias_mode = "head"
        else:
            bias_mode = "bh"
        bias3 = bias.reshape(bb * bh_, bsq, sk)
        bias3 = jnp.pad(bias3, ((0, 0), (0, 0 if bias_sq1 else sq_p - sq),
                                (0, sk_p - sk)))

    out3 = _flash(to3(q, sq_p), to3(k, sk_p), to3(v, sk_p), kvlen3,
                  seed_arr, bias3, causal, float(scale), block_q, block_k,
                  group, bias_mode, bias_sq1, h_q, float(dropout_p),
                  bool(interpret))
    out = out3[:, :sq, :].reshape(b, h_q, sq, d)
    return jnp.transpose(out, (0, 2, 1, 3))


def ptgeom_cases():
    """Geometry registry for tools/ptgeom.py (ISSUE 20): the bench
    ladder x the autotune block-candidate space, forward and backward,
    driven under jax.eval_shape (nothing executes)."""
    from paddle_tpu.analysis import kernelmodel as km

    def case(geom, bq, bk, bwd=False):
        p = km.LADDER[geom]
        d = p["dm"] // p["heads"]
        q = km.sds((1, p["seq"], p["heads"], d), p["dtype"])

        def run():
            import jax as _jax

            def fwd(q, k, v):
                o = flash_attention(q, k, v, causal=True, block_q=bq,
                                    block_k=bk)
                return jnp.sum(o.astype(jnp.float32))

            fn = _jax.grad(fwd, argnums=(0, 1, 2)) if bwd else (
                lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                block_q=bq,
                                                block_k=bk))
            _jax.eval_shape(fn, q, q, q)
        return km.GeomCase(
            kernel="flash_attention", geometry=geom,
            config=f"bq{bq}.bk{bk}" + (".bwd" if bwd else ""), run=run)

    cases = [case("tiny", 256, 512)]
    for geom in ("350m", "r06"):
        for bq, bk in ((128, 128), (256, 512), (512, 512),
                       (1024, 512)):
            cases.append(case(geom, bq, bk))
        cases.append(case(geom, 256, 512, bwd=True))
    return cases
