"""Flash-decode attention: single-query-per-sequence cached attention as a
Pallas TPU kernel.

Reference analog: the decode half of
paddle/fluid/operators/fused/fused_multi_transformer_op.cu (the
masked_multihead_attention CUDA path that reads the CacheKV tensor one
timestep at a time). The TPU re-design streams the KV cache block-wise
through VMEM with an online softmax, so one kernel launch covers the whole
cache read at HBM bandwidth:

- **Per-sequence lengths**: each batch row attends to its first
  ``lengths[b]`` cache entries. The lengths ride in as a scalar-prefetch
  operand and the KV BlockSpec index maps *clamp* trailing block indices to
  the row's last valid block — Mosaic's pipeline elides the DMA for a
  repeated block index, so blocks beyond a row's length cost no HBM
  traffic (``pl.when`` alone would only skip the compute, not the
  prefetch). That is what makes a continuous-batching engine with ragged
  lengths bandwidth-proportional: short sequences don't pay for the
  longest one.
- **GQA/MQA**: ``Hq % Hkv == 0``; all ``G = Hq // Hkv`` query heads of one
  KV head are processed together as the sublane dim of a single (G, block_k)
  MXU matmul, so grouped queries amortize each KV block read.
- **Head-major cache layout** ``(B, H, T, D)``: the kernel's KV block is a
  contiguous (block_k, D) tile — no transposition of the cache in HBM, the
  BlockSpec index map does the addressing.

Decode is forward-only (no VJP): generation never differentiates through
the cache.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["decode_attention", "decode_attention_reference"]

_LANES = 128
_NEG_INF = float("-inf")


def online_softmax_step(q, k, v, col0, length, acc_ref, m_ref, l_ref,
                        scale):
    """One KV-block update of the online softmax: masked scores against
    columns [col0, col0+block) valid below ``length``, then the running
    (m, l, acc) rescale-and-accumulate. Shared by the contiguous and
    the paged decode kernels — ONE numerics definition. ``q`` (rows, d)
    against ``k``/``v`` (block, d) is one head; a leading axis on all
    of them (and on the three refs) is a block of heads folded at once,
    each exactly as it would be alone."""
    heads = tuple(range(q.ndim - 2))
    last = q.ndim - 1
    s = jax.lax.dot_general(
        q, k, (((last,), (last,)), (heads, heads)),
        preferred_element_type=jnp.float32) * scale
    col = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, last)
    s = jnp.where(col < length, s, _NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    m_cur = jnp.maximum(m_cur, -1e30)  # fully-masked block → p = 0
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[..., :1])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = (acc_ref[...] * alpha[..., :1]
                    + jax.lax.dot_general(
                        p.astype(v.dtype), v,
                        (((last,), (last - 1,)), (heads, heads)),
                        preferred_element_type=jnp.float32))
    m_ref[...] = m_cur


def online_softmax_init(acc_ref, m_ref, l_ref):
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def online_softmax_finalize(o_ref, acc_ref, l_ref):
    """``o_ref`` is one head's (1, rows, d) block, or a block of heads
    (heads, rows, d) over refs with that leading axis."""
    l = l_ref[..., :1]
    o_ref[...] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(
        o_ref.dtype).reshape(o_ref.shape)


def online_softmax_write_stats(ml_ref, m_ref, l_ref):
    """Pack the running (m, l) into the optional stats output: column 0
    = running max, column 1 = softmax denominator (columns 2+ are
    don't-care). ONE packing definition shared by the contiguous and
    paged decode kernels — the host-side unpack in both callers reads
    exactly these two columns. Shapes as in `online_softmax_finalize`."""
    l = l_ref[..., :1]
    ml_ref[...] = jnp.concatenate(
        [m_ref[..., :1], l, l_ref[..., 2:]], axis=-1).reshape(ml_ref.shape)


def fold_fresh_row(o, m, l, q, k_row, v_row, scale, group):
    """Fold ONE extra KV column per row (its fresh k/v) into a decode
    kernel result obtained with ``return_stats``: the output equals a
    softmax over [prefix + fresh row], so the kernel only ever reads
    the existing prefix and the caches/pools stay READ-ONLY in the
    caller's layer loop. q (B, Hq, D); o/m/l from the kernel; k_row/
    v_row (B, Hkv, D) in cache dtype. Returns (B, Hq, D) float32. ONE
    numerics definition shared by the contiguous engine path
    (gpt.GPTBlock.decode_rows) and the paged engine. Zero-length rows
    are safe: l == 0 and m == -inf degrade to attention over just the
    fresh row."""
    b, hq, d = q.shape
    hkv = k_row.shape[1]
    qg = q.reshape(b, hkv, group, d)
    s_new = jnp.einsum("bhgd,bhd->bhg", qg.astype(jnp.float32),
                       k_row.astype(jnp.float32)) * scale
    s_new = s_new.reshape(b, hq)
    m2 = jnp.maximum(m, s_new)
    w_pre = l * jnp.exp(m - m2)
    w_new = jnp.exp(s_new - m2)
    v_exp = jnp.repeat(v_row.astype(jnp.float32), group, axis=1)
    return ((o.astype(jnp.float32) * w_pre[..., None]
             + v_exp * w_new[..., None])
            / (w_pre + w_new)[..., None])


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *rest, scale, block_k,
            hkv, with_stats):
    # the stats output ref exists only when requested (out_specs are
    # built conditionally), so the trailing refs shift
    if with_stats:
        ml_ref, acc_ref, m_ref, l_ref = rest
    else:
        ml_ref, (acc_ref, m_ref, l_ref) = None, rest
    bh = pl.program_id(0)
    j = pl.program_id(1)
    nk = pl.num_programs(1)
    b = bh // hkv

    @pl.when(j == 0)
    def _init():
        online_softmax_init(acc_ref, m_ref, l_ref)

    length = len_ref[b]

    # Guard against double-counting: for j beyond the row's last valid
    # block the index map re-presents that SAME last block (to elide the
    # DMA), so the compute must not run again.
    @pl.when(j * block_k < length)
    def _body():
        online_softmax_step(q_ref[0], k_ref[0, 0], v_ref[0, 0],
                            j * block_k, length, acc_ref, m_ref, l_ref,
                            scale)

    @pl.when(j == nk - 1)
    def _finalize():
        online_softmax_finalize(o_ref, acc_ref, l_ref)
        if with_stats:
            online_softmax_write_stats(ml_ref, m_ref, l_ref)


def _pick_block(T: int, block_k: int) -> int:
    """Largest power-of-two lane-multiple block that divides T."""
    bk = min(block_k, T)
    while bk > _LANES and T % bk:
        bk //= 2
    if T % bk:
        raise ValueError(
            f"cache length {T} must be a multiple of {_LANES}")
    return bk


def decode_attention_reference(q, k_cache, v_cache, lengths, scale=None):
    """Naive XLA oracle: full masked softmax over the cache.

    q: (B, Hq, D); k/v_cache: (B, Hkv, T, D); lengths: (B,) int32.
    """
    b, hq, d = q.shape
    hkv, T = k_cache.shape[1], k_cache.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    group = hq // hkv
    qg = q.reshape(b, hkv, group, d)
    s = jnp.einsum("bhgd,bhtd->bhgt", qg, k_cache).astype(jnp.float32)
    s = s * scale
    mask = jnp.arange(T)[None, None, None, :] < lengths[:, None, None, None]
    s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgt,bhtd->bhgd", p.astype(v_cache.dtype), v_cache)
    return o.reshape(b, hq, d).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, lengths, scale=None,
                     block_k=512, interpret=None, return_stats=False):
    """One decode step of cached attention for B sequences at once.

    Args:
      q: (B, Hq, D) — the query for each sequence's current position.
      k_cache, v_cache: (B, Hkv, T, D) head-major caches with
        Hq % Hkv == 0 (GQA when Hkv < Hq). T must be a multiple of 128.
      lengths: (B,) int32 — row b attends to cache positions
        [0, lengths[b]); beyond-length blocks are not re-fetched from HBM
        (clamped scalar-prefetch index map).
      scale: softmax scale, default 1/sqrt(D).
      block_k: KV block size streamed through VMEM (shrunk to divide T).
      interpret: defaults to True off-TPU so tests run on CPU.
      return_stats: also return the online-softmax running max ``m`` and
        denominator ``l`` (each (B, Hq) f32) so the caller can fold
        extra attention columns in analytically — the decode engine
        adds the current token's fresh KV row this way, letting the
        kernel read ONLY the prefix.

    Returns (B, Hq, D) in q's dtype; with return_stats, (o, m, l).
    """
    q = jnp.asarray(q)
    k_cache, v_cache = jnp.asarray(k_cache), jnp.asarray(v_cache)
    b, hq, d = q.shape
    hkv, T = k_cache.shape[1], k_cache.shape[2]
    if hq % hkv:
        raise ValueError(f"GQA needs Hq % Hkv == 0, got {hq} vs {hkv}")
    group = hq // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    bk = _pick_block(T, block_k)
    nk = T // bk

    # all G query heads of one KV head ride the sublane dim of one matmul;
    # pad G up to the dtype's sublane tile
    sub = 16 if q.dtype in (jnp.bfloat16, jnp.float16) else 8
    gp = max(sub, (group + sub - 1) // sub * sub)
    qg = q.reshape(b, hkv, group, d).reshape(b * hkv, group, d)
    qg = jnp.pad(qg, ((0, 0), (0, gp - group), (0, 0)))

    def kv_index(bh, j, lens):
        # clamp past-the-end block indices to the last valid block: a
        # repeated index is not re-DMA'd, so rows shorter than T skip the
        # bandwidth for their tail
        bb = bh // hkv
        nb = jnp.maximum((lens[bb] + bk - 1) // bk, 1)
        return (bb, bh % hkv, jnp.minimum(j, nb - 1), 0)

    lengths = jnp.asarray(lengths, jnp.int32)
    out_specs = [pl.BlockSpec((1, gp, d), lambda bh, j, lens: (bh, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((b * hkv, gp, d), q.dtype)]
    if return_stats:  # stats output only exists when asked for — the
        # per-token serving hot path must not allocate a dead buffer
        out_specs.append(pl.BlockSpec((1, gp, _LANES),
                                      lambda bh, j, lens: (bh, 0, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((b * hkv, gp, _LANES), jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hkv, nk),
        in_specs=[
            pl.BlockSpec((1, gp, d), lambda bh, j, lens: (bh, 0, 0)),
            pl.BlockSpec((1, 1, bk, d), kv_index),
            pl.BlockSpec((1, 1, bk, d), kv_index),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((gp, d), jnp.float32),
            pltpu.VMEM((gp, _LANES), jnp.float32),
            pltpu.VMEM((gp, _LANES), jnp.float32),
        ],
    )
    res = pl.pallas_call(
        # ptlint: disable=PT001 -- scale is a static Python float kwarg
        # (a tracer here would already fail partial-binding)
        functools.partial(_kernel, scale=float(scale), block_k=bk,
                          hkv=hkv, with_stats=return_stats),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="decode_attention",
        interpret=interpret,
    )(lengths, qg, k_cache, v_cache)
    o = res[0][:, :group, :].reshape(b, hq, d)
    if not return_stats:
        return o
    ml = res[1]
    m = ml[:, :group, 0].reshape(b, hq)
    l = ml[:, :group, 1].reshape(b, hq)
    return o, m, l


def ptgeom_cases():
    """Geometry registry for tools/ptgeom.py (ISSUE 20): bench-ladder
    cache shapes x the block_k sweep, under jax.eval_shape."""
    from paddle_tpu.analysis import kernelmodel as km

    def case(geom, block_k):
        p = km.LADDER[geom]
        d = p["dm"] // p["heads"]
        T = max(p["seq"], _LANES)
        B = 8
        q = km.sds((B, p["heads"], d), p["dtype"])
        kc = km.sds((B, p["kv_heads"], T, d), p["dtype"])
        ln = km.sds((B,), "int32")

        def run():
            import jax as _jax
            _jax.eval_shape(
                lambda q, kc, vc, ln: decode_attention(
                    q, kc, vc, ln, block_k=block_k),
                q, kc, kc, ln)
        return km.GeomCase(kernel="decode_attention", geometry=geom,
                           config=f"bk{block_k}", run=run)

    cases = [case("tiny", 512)]
    for geom in ("350m", "r06"):
        for bk in (256, 512, 1024):
            cases.append(case(geom, bk))
    return cases
