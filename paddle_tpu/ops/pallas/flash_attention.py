"""Flash attention (forward + backward) as Pallas TPU kernels.

Reference analog: paddle/fluid/operators/fused/fused_attention_op.cu,
fmha_ref.h (dropout), fused_softmax_mask.cu.h (mask fusion). This is the
TPU-native re-design: an online-softmax (FlashAttention-2 style) kernel
tiled for the MXU, with a custom VJP whose backward recomputes attention
probabilities from the saved log-sum-exp instead of materializing the
(S, S) matrix.

What a grid step does follows its block's position (``block_plan``, PR 36):
the grid is (batch-heads, the plan's steps), so a causal block above the
diagonal gets no step and no copy; a block the diagonal crosses is masked
and one under it is not; the key-length compare is compiled only for a
caller that passed ``kv_lens`` or whose keys were padded. The backward
makes its probabilities once a block (``flash_attention_bwd``) wherever
float32 dQ for the whole sequence fits VMEM.

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
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "block_plan"]

_LANES = 128
_NEG_INF = float("-inf")


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


_FIRST, _LAST, _MASKED = 1, 2, 4


class BlockPlan(NamedTuple):
    """The (query block, key block) pairs one batch-head visits, in the
    order the forward walks them (query block by query block), and
    whether each needs its scores masked. Pairs not listed are skipped:
    no grid step, no copy."""
    nq: int
    nk: int
    i: np.ndarray        # (steps,) query block of each step
    j: np.ndarray        # (steps,) key block
    mask: np.ndarray     # (steps,) bool: the block is cut by a mask

    @property
    def masked(self) -> int:
        return int(self.mask.sum())

    @property
    def unmasked(self) -> int:
        return len(self.i) - self.masked

    @property
    def skipped(self) -> int:
        return self.nq * self.nk - len(self.i)

    @property
    def kinds(self) -> Tuple[bool, ...]:
        """Which bodies a kernel compiles: (True,) masked only, (False,)
        unmasked only, or both."""
        return tuple(m for m in (False, True)
                     if (self.masked if m else self.unmasked))

    def table(self, k_major: bool = False):
        """(i, j, flags) int32 arrays, one entry a grid step. ``flags``
        says whether the step is the first (_FIRST) or last (_LAST) of
        its accumulation (a query block's key blocks; ``k_major``: a key
        block's query blocks, the backward's order) and whether it takes
        the masked body (_MASKED)."""
        order = np.lexsort((self.i, self.j)) if k_major \
            else np.arange(len(self.i))
        i, j, mask = self.i[order], self.j[order], self.mask[order]
        run = j if k_major else i
        edge = np.flatnonzero(np.diff(run)) + 1
        flags = mask.astype(np.int32) * _MASKED
        flags[np.r_[0, edge]] |= _FIRST
        flags[np.r_[edge - 1, len(run) - 1]] |= _LAST
        return i.astype(np.int32), j.astype(np.int32), flags


@functools.lru_cache(maxsize=None)
def block_plan(sq: int, sk: int, block_q: int, block_k: int, causal: bool,
               kv_masked: bool = False) -> BlockPlan:
    """What each grid step of the flash kernels does, from static shapes
    alone. A causal block wholly above the diagonal is not visited; one
    the diagonal crosses is masked; one wholly under it is not. Without
    ``causal`` every block is visited and only the last key block, where
    ``sk`` is not a multiple of ``block_k``, is masked (its padding).
    ``kv_masked`` (the caller passed ``kv_lens``) masks every block: the
    lengths are known only on the device.

    At the training cell, 2,048 tokens causal: the forward's (1024, 1024)
    gives 4 pairs, 1 unmasked, 2 masked, 1 skipped; the backward's
    (512, 512) 16 pairs, 6 unmasked, 4 masked, 6 skipped; (256, 512), the
    blocks before PR 36, 32 pairs, 12 unmasked, 8 masked, 12 skipped (all
    20 were masked then, and the 12 fetched and stepped over).
    """
    nq, nk = -(-sq // block_q), -(-sk // block_k)
    i, j = np.divmod(np.arange(nq * nk), nk)
    lo, hi = j * block_k, j * block_k + block_k - 1      # a block's keys
    if causal:
        visit = lo <= i * block_q + block_q - 1
        mask = hi > i * block_q
    else:
        visit = np.ones(nq * nk, bool)
        mask = hi >= sk
    mask = mask | kv_masked
    return BlockPlan(nq, nk, i[visit], j[visit], mask[visit])


def _nt(a, b):
    """a @ b.T in float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _positions(shape, q0, k0, k_major):
    """Global (query, key) index of every cell of a score tile whose
    rows are queries, or keys where ``k_major``."""
    qd, kd = (1, 0) if k_major else (0, 1)
    return (q0 + jax.lax.broadcasted_iota(jnp.int32, shape, qd),
            k0 + jax.lax.broadcasted_iota(jnp.int32, shape, kd))


def _keep_mask(seed, bh, q0, k0, shape, k_major, sk_total, rate):
    """Counter-based keep mask: lowbias32 hash of the global (row, col)
    cell index mixed with (seed, head). Deterministic across fwd/bwd."""

    def mix(x):
        x = x ^ (x >> 16)
        x = x * jnp.uint32(0x7FEB352D)
        x = x ^ (x >> 15)
        x = x * jnp.uint32(0x846CA68B)
        return x ^ (x >> 16)

    row, col = _positions(shape, q0, k0, k_major)
    lin = row.astype(jnp.uint32) * jnp.uint32(sk_total) \
        + col.astype(jnp.uint32)
    h = mix(mix(lin ^ seed.astype(jnp.uint32)) ^ bh.astype(jnp.uint32))
    thresh = jnp.uint32(min(int(rate * 2.0**32), 2**32 - 1))
    return h >= thresh


def _mask_scores(s, q0, k0, kvlen, causal, k_major):
    """-inf where a key is at or beyond ``kvlen`` (None: no such compare
    is compiled) or, ``causal``, after its query."""
    row, col = _positions(s.shape, q0, k0, k_major)
    mask = None if kvlen is None else col < kvlen
    if causal:
        mask = row >= col if mask is None \
            else jnp.logical_and(mask, row >= col)
    return s if mask is None else jnp.where(mask, s, _NEG_INF)


def _step(ti_ref, tj_ref, tf_ref):
    t = pl.program_id(1)
    return ti_ref[t], tj_ref[t], tf_ref[t]


def _for_each_kind(kinds, flags, run, body):
    """``body(masked)`` once for each kind of block the plan holds, each
    under the predicate that picks its steps (none where the plan holds
    one kind and ``run`` is None: the body is then the whole step)."""
    for masked in kinds:
        cond = run
        if len(kinds) == 2:
            mine = (flags & _MASKED != 0) if masked \
                else (flags & _MASKED == 0)
            cond = mine if run is None else jnp.logical_and(mine, run)
        if cond is None:
            body(masked)
        else:
            pl.when(cond)(functools.partial(body, masked))


def _grad_tiles(q, k, v, do, bias, lse, delta, seed, bh, q0, k0, kvlen,
                masked, k_major, *, causal, scale, dropout_rate, sk_total):
    """One tile of the backward, (block_q, block_k) or, ``k_major``,
    (block_k, block_q) with ``bias``, ``lse`` and ``delta`` laid out to
    match: the probabilities recomputed from ``lse`` as dV's operand
    (dropout applied) and dS as dK's and dQ's, both in the operands'
    type."""
    s = (_nt(k, q) if k_major else _nt(q, k)) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if masked:
        s = _mask_scores(s, q0, k0, kvlen, causal, k_major)
    p = jnp.exp(s - lse)
    dp = _nt(v, do) if k_major else _nt(do, v)
    p_d = p
    if dropout_rate > 0.0:
        keep = _keep_mask(seed, bh, q0, k0, p.shape, k_major, sk_total,
                          dropout_rate)
        p_d = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
        dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
    ds = p * (dp - delta) * scale
    return p_d.astype(do.dtype), ds.astype(q.dtype)


# ---------------------------------------------------------------------------
# Forward kernel: grid (BH, steps); a query block's key blocks are
# consecutive steps with running (m, l, acc) scratch carried across them.
# ---------------------------------------------------------------------------


def _fwd_kernel(ti_ref, tj_ref, tf_ref, kvlen_ref, seed_ref, q_ref, k_ref,
                v_ref, *refs, causal, scale, block_q, block_k, has_bias,
                has_kvlens, kv_mask, kinds, dropout_rate, sk_total, sk):
    bias_ref = refs[0] if has_bias else None
    o_ref, lse_ref, acc_ref, m_ref, l_ref = refs[int(has_bias):]

    bh = pl.program_id(0)
    i, j, flags = _step(ti_ref, tj_ref, tf_ref)

    @pl.when(flags & _FIRST != 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kvlen = kvlen_ref[bh] if has_kvlens else sk
    # KV blocks entirely beyond this row's valid length are skipped
    run = j * block_k < kvlen if has_kvlens else None

    def body(masked):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = _nt(q, k) * scale
        if has_bias:
            s = s + bias_ref[0].astype(jnp.float32)
        if masked:
            s = _mask_scores(s, i * block_q, j * block_k,
                             kvlen if kv_mask else None, causal, False)

        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        if masked or has_bias:
            # finite floor: a block whose every cell is masked (-inf
            # bias) must give p = exp(-inf - m_cur) = 0, not
            # exp(-inf + inf) = NaN
            m_cur = jnp.maximum(m_cur, -1e30)
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, :1])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        if dropout_rate > 0.0:
            keep = _keep_mask(seed_ref[0], bh, i * block_q, j * block_k,
                              p.shape, False, sk_total, dropout_rate)
            p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
        acc_ref[...] = (acc_ref[...] * alpha[:, :1]
                        + jax.lax.dot(p.astype(v.dtype), v,
                                      preferred_element_type=jnp.float32))
        m_ref[...] = m_cur

    _for_each_kind(kinds, flags, run, body)

    @pl.when(flags & _LAST != 0)
    def _finalize():
        # rows with zero valid keys (kvlen == 0) produce 0 output and a
        # finite lse so the backward recomputation stays NaN-free
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        m_safe = jnp.where(m_ref[...] == _NEG_INF, 0.0, m_ref[...])
        lse = m_safe + jnp.log(jnp.where(l_ref[...] == 0.0, 1.0,
                                         l_ref[...]))
        # one float32 a row, along the lanes: the (block_q, 128)
        # lane-broadcast tile turned once a query block
        lse_ref[0] = lse.T[:1]


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


def _bias_spec(bias_sq1, block_q, block_k, g, k_major=False):
    """Bias BlockSpec: a size-1 Sq dim stays size-1 (index map pins it to
    block 0) so a key-only mask is never broadcast to (..., Sq, Sk) in HBM;
    the kernel's `s + bias` broadcasts it across rows for free.
    ``k_major``: the bias was transposed to (G, Sk, Sq|1)."""
    bq = 1 if bias_sq1 else block_q
    if k_major:
        return pl.BlockSpec(
            (1, block_k, bq),
            lambda b, t, ti, tj, *_: (g(b), tj[t], 0 if bias_sq1 else ti[t]))
    return pl.BlockSpec(
        (1, bq, block_k),
        lambda b, t, ti, tj, *_: (g(b), 0 if bias_sq1 else ti[t], tj[t]))


def _specs(block_q, block_k, d, group):
    """The BlockSpecs every kernel shares, over the plan's tables."""
    return dict(
        q=pl.BlockSpec((1, block_q, d),
                       lambda b, t, ti, tj, *_: (b, ti[t], 0)),
        kv=pl.BlockSpec((1, block_k, d),
                        lambda b, t, ti, tj, *_: (b // group, tj[t], 0)),
        dkv=pl.BlockSpec((1, block_k, d),
                         lambda b, t, ti, tj, *_: (b, tj[t], 0)),
        # one float32 a row, lane-broadcast (the two-kernel backward) or
        # along the lanes
        col=pl.BlockSpec((1, block_q, _LANES),
                         lambda b, t, ti, tj, *_: (b, ti[t], 0)),
        row=pl.BlockSpec((1, 1, block_q),
                         lambda b, t, ti, tj, *_: (b, 0, ti[t])))


def _launch(table, bh, in_specs, out_specs, scratch):
    """What every kernel's ``pallas_call`` shares: the grid (BH, steps)
    over the plan's tables, handed in with kvlen and seed as the five
    scalars the index maps and the bodies read."""
    return dict(
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(bh, len(table[0])),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")))


def _fa_forward(q, k, v, kvlen, seed, bias, causal, scale, block_q, block_k,
                group, bias_mode, bias_sq1, h_q, dropout_rate, interpret,
                sk, has_kvlens):
    bh, sq, d = q.shape
    plan = block_plan(sq, sk, block_q, block_k, causal, has_kvlens)
    has_bias = bias is not None
    kernel = functools.partial(
        _fwd_kernel, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, has_bias=has_bias, has_kvlens=has_kvlens,
        kv_mask=has_kvlens or (not causal and k.shape[1] != sk),
        kinds=plan.kinds, dropout_rate=dropout_rate, sk_total=k.shape[1],
        sk=sk)
    sp = _specs(block_q, block_k, d, group)
    in_specs = [sp["q"], sp["kv"], sp["kv"]]
    args = [q, k, v]
    if has_bias:
        in_specs.append(_bias_spec(bias_sq1, block_q, block_k,
                                   _bias_group(bias_mode, h_q)))
        args.append(bias)
    table = plan.table()
    # ptlint: disable=PT009 -- flash forward streams the K/V under the
    # diagonal per query block by construction (online softmax): the
    # seq/block_q re-read is the O(block) -memory tradeoff the kernel
    # exists for.
    out, lse = pl.pallas_call(
        kernel,
        **_launch(table, bh, in_specs, [sp["q"], sp["row"]],
                  [pltpu.VMEM((block_q, d), jnp.float32),
                   pltpu.VMEM((block_q, _LANES), jnp.float32),
                   pltpu.VMEM((block_q, _LANES), jnp.float32)]),
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ],
        name="flash_attention_fwd",
        interpret=interpret,
    )(*table, kvlen, seed, *args)
    return out, lse


# ---------------------------------------------------------------------------
# Backward. Probabilities are recomputed from the saved LSE; delta =
# rowsum(dO * O) is precomputed. One kernel makes p, dP and dS once a
# block and feeds dV, dK and dQ from them (5 products): grid (BH, steps)
# walking key block by key block with score tiles laid out keys x queries
# (lse and delta broadcast along the sublanes, dV and dK plain products),
# dK/dV accumulated over a key block's query blocks and dQ for the whole
# sequence of the batch-head in float32 scratch. Where that scratch
# cannot fit VMEM, two kernels: dK/dV over a key block's query blocks and
# dQ over a query block's key blocks, each making its own probabilities
# (7 products).
# ---------------------------------------------------------------------------


def _bwd_kernel(ti_ref, tj_ref, tf_ref, kvlen_ref, seed_ref, q_ref, k_ref,
                v_ref, do_ref, *refs, tile, block_q, block_k, has_bias,
                has_kvlens, kv_mask, kinds, sk):
    bias_ref = refs[0] if has_bias else None
    (lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
     dq_acc, dk_acc, dv_acc) = refs[int(has_bias):]

    bh = pl.program_id(0)
    t = pl.program_id(1)
    i, j, flags = _step(ti_ref, tj_ref, tf_ref)

    @pl.when(t == 0)
    def _init_dq():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(flags & _FIRST != 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    kvlen = kvlen_ref[bh] if has_kvlens else sk
    run = j * block_k < kvlen if has_kvlens else None

    def body(masked):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        p, ds = _grad_tiles(
            q, k, v, do, bias_ref[0] if has_bias else None, lse_ref[0],
            delta_ref[0], seed_ref[0], bh, i * block_q, j * block_k,
            kvlen if kv_mask else None, masked, True, **tile)
        dv_acc[...] += jax.lax.dot(p, do,
                                   preferred_element_type=jnp.float32)
        dk_acc[...] += jax.lax.dot(ds, q,
                                   preferred_element_type=jnp.float32)
        rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        dq_acc[rows, :] += jax.lax.dot_general(
            ds, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _for_each_kind(kinds, flags, run, body)

    @pl.when(flags & _LAST != 0)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(t == pl.num_programs(1) - 1)
    def _finalize_dq():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkdv_kernel(ti_ref, tj_ref, tf_ref, kvlen_ref, seed_ref, q_ref,
                     k_ref, v_ref, do_ref, *refs, tile, block_q, block_k,
                     has_bias, has_kvlens, kv_mask, kinds, sk):
    bias_ref = refs[0] if has_bias else None
    lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc = \
        refs[int(has_bias):]

    bh = pl.program_id(0)
    i, j, flags = _step(ti_ref, tj_ref, tf_ref)

    @pl.when(flags & _FIRST != 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    kvlen = kvlen_ref[bh] if has_kvlens else sk
    run = j * block_k < kvlen if has_kvlens else None

    def body(masked):
        q = q_ref[0]
        do = do_ref[0]
        p, ds = _grad_tiles(
            q, k_ref[0], v_ref[0], do, bias_ref[0] if has_bias else None,
            lse_ref[0][:, :1], delta_ref[0][:, :1], seed_ref[0], bh,
            i * block_q, j * block_k, kvlen if kv_mask else None, masked,
            False, **tile)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _for_each_kind(kinds, flags, run, body)

    @pl.when(flags & _LAST != 0)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(ti_ref, tj_ref, tf_ref, kvlen_ref, seed_ref, q_ref,
                   k_ref, v_ref, do_ref, *refs, tile, block_q, block_k,
                   has_bias, has_kvlens, kv_mask, kinds, sk):
    bias_ref = refs[0] if has_bias else None
    lse_ref, delta_ref, dq_ref, dq_acc = refs[int(has_bias):]

    bh = pl.program_id(0)
    i, j, flags = _step(ti_ref, tj_ref, tf_ref)

    @pl.when(flags & _FIRST != 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    kvlen = kvlen_ref[bh] if has_kvlens else sk
    run = j * block_k < kvlen if has_kvlens else None

    def body(masked):
        k = k_ref[0]
        _, ds = _grad_tiles(
            q_ref[0], k, v_ref[0], do_ref[0],
            bias_ref[0] if has_bias else None, lse_ref[0][:, :1],
            delta_ref[0][:, :1], seed_ref[0], bh, i * block_q, j * block_k,
            kvlen if kv_mask else None, masked, False, **tile)
        dq_acc[...] += jax.lax.dot(ds, k,
                                   preferred_element_type=jnp.float32)

    _for_each_kind(kinds, flags, run, body)

    @pl.when(flags & _LAST != 0)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


# The backward's blocks are the forward's cut to this: its five products
# keep four score tiles alive where the forward keeps two, and at
# (4, 2048, 16, 128) it measured 1.34 ms a call at (512, 512) against 1.45
# at (1024, 1024) (one v5e, 2026-10-05, PR 36); at 8,192 tokens only the
# smaller tiles leave room for dQ.
_BWD_BLOCK = 512

# what the one-kernel backward may take of a v5e core's 16 MiB of VMEM
# (``kernelmodel.vmem_budget_bytes``'s default)
_BWD_VMEM_BYTES = 16 * 1024 * 1024 - 512 * 1024


def _one_kernel_bwd_bytes(sq, d, block_q, block_k, itemsize, bias_sq=0):
    """VMEM the one-kernel backward needs: ``kernelmodel.vmem_estimate``'s
    arithmetic (float32 dQ for the whole sequence and dK/dV for a key
    block in scratch, every blocked operand twice) and two float32 score
    tiles for the compiler's temporaries. Read on the chip: (512, 512) at
    8,192 x 128 compiles and runs (12.1 MiB by this count), (1024, 1024)
    there is refused (20.1). A head narrower than the 128 lanes is padded
    to them in VMEM."""
    d = _round_up(d, _LANES)
    blocks = (2 * block_q * d + 2 * block_k * d      # q dO, k v
              + 2 * block_k * d + sq * d) * itemsize  # dk dv, dq
    rows = 2 * 8 * block_q * 4                        # lse, delta
    bias = block_k * bias_sq * 4
    return (2 * (blocks + rows + bias)
            + (sq * d + 2 * block_k * d) * 4
            + 2 * block_q * block_k * 4)


def _fa_backward(q, k, v, kvlen, seed, bias, out, lse, do, causal, scale,
                 block_q, block_k, group, bias_mode, bias_sq1, h_q,
                 dropout_rate, interpret, sk, has_kvlens):
    bh, sq, d = q.shape
    sk_p = k.shape[1]
    block_q, block_k = (_BWD_BLOCK if b % _BWD_BLOCK == 0 else b
                        for b in (block_q, block_k))
    plan = block_plan(sq, sk, block_q, block_k, causal, has_kvlens)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, None, :]
    has_bias = bias is not None
    kw = dict(tile=dict(causal=causal, scale=scale,
                        dropout_rate=dropout_rate, sk_total=sk_p),
              block_q=block_q, block_k=block_k, has_bias=has_bias,
              has_kvlens=has_kvlens,
              kv_mask=has_kvlens or (not causal and sk_p != sk),
              kinds=plan.kinds, sk=sk)
    g = _bias_group(bias_mode, h_q)
    sp = _specs(block_q, block_k, d, group)
    by_key = plan.table(k_major=True)
    dkv_shape = [jax.ShapeDtypeStruct((bh, sk_p, d), k.dtype),
                 jax.ShapeDtypeStruct((bh, sk_p, d), v.dtype)]
    dkv_scratch = [pltpu.VMEM((block_k, d), jnp.float32),
                   pltpu.VMEM((block_k, d), jnp.float32)]
    in_specs = [sp["q"], sp["kv"], sp["kv"], sp["q"]]
    args = [q, k, v, do]

    one_kernel = _one_kernel_bwd_bytes(
        sq, d, block_q, block_k, q.dtype.itemsize,
        0 if not has_bias else _LANES if bias_sq1 else block_q
    ) <= _BWD_VMEM_BYTES
    if one_kernel:
        if has_bias:
            in_specs.append(_bias_spec(bias_sq1, block_q, block_k, g,
                                       k_major=True))
            args.append(jnp.swapaxes(bias, 1, 2))
        # dk/dv are produced per *query* head (b over B*Hq) and
        # group-summed below for GQA
        # ptlint: disable=PT009 -- the backward re-streams every Q/dO
        # row block at or under the diagonal per K/V tile (flash
        # backward recomputation); inherent to the tiling.
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_kernel, **kw),
            **_launch(by_key, bh, in_specs + [sp["row"], sp["row"]],
                      [pl.BlockSpec((1, sq, d),
                                    lambda b, t, *_: (b, 0, 0)),
                       sp["dkv"], sp["dkv"]],
                      [pltpu.VMEM((sq, d), jnp.float32)] + dkv_scratch),
            out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)] + dkv_shape,
            name="flash_attention_bwd",
            interpret=interpret,
        )(*by_key, kvlen, seed, *args, lse, delta)
    else:
        if has_bias:
            in_specs.append(_bias_spec(bias_sq1, block_q, block_k, g))
            args.append(bias)
        # these two read a row's lse and delta lane-broadcast
        in_specs += [sp["col"], sp["col"]]
        args += [jnp.broadcast_to(x[:, 0, :, None], (bh, sq, _LANES))
                 for x in (lse, delta)]
        # ptlint: disable=PT009 -- dk/dv re-streams every Q/dO/LSE row
        # block per K/V tile (flash backward recomputation); inherent to
        # the tiling, not a blocking bug.
        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkdv_kernel, **kw),
            **_launch(by_key, bh, in_specs, [sp["dkv"], sp["dkv"]],
                      dkv_scratch),
            out_shape=dkv_shape,
            name="flash_attention_bwd_dkdv",
            interpret=interpret,
        )(*by_key, kvlen, seed, *args)
        by_query = plan.table()
        # ptlint: disable=PT009 -- dq re-streams the K/V under the
        # diagonal per query block, mirroring the forward's walk.
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, **kw),
            **_launch(by_query, bh, in_specs, [sp["q"]],
                      [pltpu.VMEM((block_q, d), jnp.float32)]),
            out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)],
            name="flash_attention_bwd_dq",
            interpret=interpret,
        )(*by_query, kvlen, seed, *args)[0]
    if group > 1:
        dk = dk.reshape(-1, group, sk_p, d).sum(axis=1).astype(k.dtype)
        dv = dv.reshape(-1, group, sk_p, d).sum(axis=1).astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wiring on the padded (BH, S, D) representation
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(6, 18)))
def _flash(q, k, v, kvlen, seed, bias, causal, scale, block_q, block_k,
           group, bias_mode, bias_sq1, h_q, dropout_rate, interpret, sk,
           has_kvlens):
    out, _ = _fa_forward(q, k, v, kvlen, seed, bias, causal, scale,
                         block_q, block_k, group, bias_mode, bias_sq1, h_q,
                         dropout_rate, interpret, sk, has_kvlens)
    return out


def _flash_fwd(q, k, v, kvlen, seed, bias, causal, scale, block_q, block_k,
               group, bias_mode, bias_sq1, h_q, dropout_rate, interpret,
               sk, has_kvlens):
    out, lse = _fa_forward(q, k, v, kvlen, seed, bias, causal, scale,
                           block_q, block_k, group, bias_mode, bias_sq1,
                           h_q, dropout_rate, interpret, sk, has_kvlens)
    return out, (q, k, v, kvlen, seed, bias, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, group, bias_mode, bias_sq1,
               h_q, dropout_rate, interpret, sk, has_kvlens, residuals, do):
    q, k, v, kvlen, seed, bias, out, lse = residuals
    dq, dk, dv = _fa_backward(q, k, v, kvlen, seed, bias, out, lse, do,
                              causal, scale, block_q, block_k, group,
                              bias_mode, bias_sq1, h_q, dropout_rate,
                              interpret, sk, has_kvlens)
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


# The default (block_q, block_k), used when the autotune cache has no
# entry for the shape. Measured on one v5e, 2026-10-05 (PR 36), causal
# bfloat16 forward + backward, kernel time a call from the device trace:
# at (4, 2048, 16, 128) forward 0.82 ms and backward 1.45 ms, at
# (8, 2048, 16, 64) 1.68 and 2.95, the fastest forward of fifteen pairs at
# both head sizes ((256, 512), the default before: 1.42 + 1.53 and 3.01 +
# 3.25; the backward alone is 7% faster at (512, 512), the forward a third
# slower there). Large blocks win because a step's bookkeeping (the
# running max and sum, the accumulator's rescale) and the MXU's weight
# loads are paid once a block, which outweighs the masked half of a
# diagonal block.
_DEFAULT_BLOCKS = (1024, 1024)


def _default_blocks(sq, sk, bias_sq):
    """The default blocks for what the caller passed: ``_DEFAULT_BLOCKS``,
    halved where a bias block of (block_q, block_k) float32 rides along
    (at 1024 x 1024 two buffers of it are half a core's VMEM), and halved
    again, each side alone, while padding the sequence to a multiple of
    the block would add more than an eighth to it."""
    def fit(s, block):
        s_lanes = _round_up(s, _LANES)
        while block > _LANES and block < s_lanes and \
                _round_up(s, block) * 8 > s_lanes * 9:
            block //= 2
        return block
    bq, bk = _DEFAULT_BLOCKS
    if bias_sq > 1:
        bq, bk = bq // 2, bk // 2
    return fit(sq, bq), fit(sk, bk)


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
        candidates = [(128, 128), (256, 256), (256, 512), (512, 512),
                      (512, 1024), (1024, 512), (1024, 1024)]
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

    Blocks not passed come from the autotune cache or, by default, from
    ``_default_blocks``: (1024, 1024), smaller where a bias block rides
    along or the sequence would be padded by much. Measured on one v5e,
    2026-10-05 (PR 36), causal bfloat16, kernel time a call in the device
    trace, forward | backward: (4, 2048, 16, 128) 0.82 | 1.45 ms, where
    the kernels before this PR took 1.73-1.92 | 1.66 + 1.91 at their
    default (256, 512); (8, 2048, 16, 64) 1.68 | 2.95 ms against
    3.74 | 3.49 + 4.00. ``block_plan`` says what each grid step of a shape
    does.
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
        # default the shapes give otherwise
        from paddle_tpu.ops.pallas.autotune import get_cache
        hit = get_cache().get(_tune_key(
            b, sq, sk, h_q, h_kv, d, q.dtype, causal, kv_lens is not None,
            bias is not None, dropout_p > 0))
        tuned = hit if hit is not None else _default_blocks(
            sq, sk, 0 if bias is None else jnp.shape(bias)[-2])
        block_q = block_q if block_q is not None else tuned[0]
        block_k = block_k if block_k is not None else tuned[1]

    # clamp blocks for short sequences — padding 128 rows up to a larger
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
                  bool(interpret), sk, kv_lens is not None)
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

    cases = [case("tiny", *_DEFAULT_BLOCKS)]
    for geom in ("350m", "r06"):
        for bq, bk in ((128, 128), (256, 512), (512, 512), (1024, 512),
                       _DEFAULT_BLOCKS):
            cases.append(case(geom, bq, bk))
        cases.append(case(geom, 512, 512, bwd=True))
        cases.append(case(geom, *_DEFAULT_BLOCKS, bwd=True))
    return cases
