"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

This capability is ABSENT in the reference (SURVEY §5.7: no
sequence_parallel / ring_attention / context_parallel / ulysses anywhere in
the tree) — it is designed fresh for TPU:

- **Ring attention** (Liu et al. 2023): the sequence axis is sharded over a
  mesh axis; each step computes blockwise attention of the local Q shard
  against the currently-held KV shard, accumulates online-softmax state,
  and rotates KV one hop around the ring with `lax.ppermute` (ICI
  collective-permute). Peak memory per chip is O(S_local²) and the KV
  transfer overlaps compute under XLA's scheduler.
- **Ulysses** (DeepSpeed-Ulysses 2023): `lax.all_to_all` re-shards
  (seq-sharded, all heads) → (all seq, head-sharded), runs ordinary local
  attention, and all-to-alls back. Cheaper than a ring when
  n_heads % sp == 0 and S fits a chip.

Both are written for `jax.shard_map` bodies and are differentiable (scan +
ppermute/all_to_all have transpose rules), so `jax.grad` through a train
step produces the reversed ring the reference would have had to hand-code.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["ring_attention", "ulysses_attention",
           "sequence_parallel_attention"]


def _chunk_attn(q, k, v, m, l, acc, q_off, kv_off, scale, causal, sk_valid):
    """One online-softmax accumulation of local Q against one KV chunk.

    q: (B,H,Sq,D) k/v: (B,H,Sk,D); m/l: (B,H,Sq); acc: (B,H,Sq,D) fp32.
    Positions are global: q rows start at q_off, kv cols at kv_off.
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    sk = k.shape[2]
    col = kv_off + lax.broadcasted_iota(jnp.int32, s.shape, 3)
    mask = col < (kv_off + sk_valid)
    if causal:
        row = q_off + lax.broadcasted_iota(jnp.int32, s.shape, 2)
        mask = jnp.logical_and(mask, row >= col)
    s = jnp.where(mask, s, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * alpha + jnp.sum(p, axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   scale: Optional[float] = None):
    """Ring attention over a sharded sequence axis; call inside shard_map.

    q/k/v: LOCAL shards (B, S_local, H, D) — the global sequence is
    S_local * axis_size(axis_name), shard i holding rows
    [i*S_local, (i+1)*S_local). Returns the local output shard.
    """
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    # (B,H,S,D) internally
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))

    # derive from qt so the carry is device-varying under shard_map's VMA
    # tracking (plain constants would be 'unvarying' and reject the scan)
    zero = qt[..., 0].astype(jnp.float32) * 0.0
    m0 = zero - jnp.inf
    l0 = zero
    acc0 = qt.astype(jnp.float32) * 0.0
    q_off = my * s_loc
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, t):
        kc, vc, m, l, acc = carry
        src = (my - t) % n  # who originally owned the chunk we now hold
        kv_off = src * s_loc

        def compute(args):
            m, l, acc = args
            return _chunk_attn(qt, kc, vc, m, l, acc, q_off, kv_off,
                               scale, causal, s_loc)

        if causal:
            # chunks entirely in the future contribute nothing; skip the
            # matmuls (every chip computes ~half the chunks — the same
            # total work as single-chip causal attention)
            fully_masked = kv_off > q_off + s_loc - 1
            m, l, acc = lax.cond(fully_masked, lambda a: a, compute,
                                 (m, l, acc))
        else:
            m, l, acc = compute((m, l, acc))
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return (kc, vc, m, l, acc), None

    (kt, vt, m, l, acc), _ = lax.scan(
        step, (kt, vt, m0, l0, acc0), jnp.arange(n))
    out = acc / l[..., None]
    return jnp.transpose(out.astype(q.dtype), (0, 2, 1, 3))


def ulysses_attention(q, k, v, axis_name: str, causal: bool = False,
                      scale: Optional[float] = None, attn_fn=None):
    """Ulysses sequence parallelism: all-to-all seq-shard → head-shard, run
    full-sequence local attention on n_heads/sp heads, all-to-all back.

    q/k/v: LOCAL shards (B, S_local, H, D) with H % axis_size == 0.
    """
    n = lax.axis_size(axis_name)
    b, s_loc, h, d = q.shape
    if h % n != 0:
        raise ValueError(f"n_heads={h} not divisible by axis size {n}")
    if attn_fn is None:
        from paddle_tpu.nn.functional.attention import attention_reference
        attn_fn = functools.partial(attention_reference, is_causal=causal,
                                    scale=scale)

    def a2a(x, split, concat):
        return lax.all_to_all(x, axis_name, split_axis=split,
                              concat_axis=concat, tiled=True)

    # (B, S_loc, H, D) -> (B, S, H/n, D): split heads, gather sequence
    qg, kg, vg = (a2a(x, 2, 1) for x in (q, k, v))
    og = attn_fn(qg, kg, vg)
    # back: split sequence, gather heads
    return a2a(og, 1, 2)


def sequence_parallel_attention(q, k, v, mesh: Mesh, axis: str = "sp",
                                causal: bool = True,
                                scale: Optional[float] = None,
                                mode: str = "ring",
                                batch_axes=("dp", "fsdp"),
                                head_axis: str = "tp"):
    """Global-view wrapper: shard_map ring/ulysses attention over `axis`.

    q/k/v: GLOBAL (B, S, H, D) arrays inside (or outside) a pjit program
    over `mesh`; the sequence axis is (re)sharded over `axis`, batch over
    `batch_axes`, heads over `head_axis`.
    """
    fn = {"ring": ring_attention, "ulysses": ulysses_attention}[mode]
    spec = P(batch_axes, axis, head_axis, None)
    body = functools.partial(fn, axis_name=axis, causal=causal, scale=scale)
    mapped = jax.shard_map(
        lambda q, k, v: body(q, k, v),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
    return mapped(q, k, v)
