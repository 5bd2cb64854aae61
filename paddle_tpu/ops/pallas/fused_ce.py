"""Fused blockwise softmax-cross-entropy over a projection — the (N, V)
logits never exist in HBM, forward or backward.

Reference analog: paddle/fluid/operators/collective/
c_softmax_with_cross_entropy_op.cu:38-192, which fuses the softmax-CE of
TP-sharded logits so no rank materializes the full vocab row. On TPU the
bigger prize is the *dense* case: at (B=8, S=2048, V=50304) the fp32
logits + grads are ~6.6 GB of HBM traffic per step that this kernel never
pays. Three Pallas passes, each streaming (block_n, block_v) logit tiles
recomputed in VMEM:

  fwd : online logsumexp over vocab blocks + gather of the label logit
        → per-row loss and lse (the only (N,)-sized residual).
  dx  : p = exp(x·wᵀ − lse); dx += (p − onehot)·g @ w_block.
  dw  : same recompute, accumulated over row blocks into (block_v, d).

Weights ride in embedding layout (V, d) — the tied LM head (wte) feeds the
kernel directly, no transposed copy.

Cost model: 5 logit-matmul passes of N·V·d MACs total (1 fwd + 2 recompute
+ dx + dw) vs the unfused 3 — a deliberate FLOPs-for-bandwidth trade; the
unfused path is HBM-bound on the logit round-trips, and the MXU has the
headroom (GPT-1.3B single-chip sits at ~0.50 MFU).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_softmax_cross_entropy"]

_LANES = 128
_NEG_INF = float("-inf")


def _logits_block(x_ref, w_ref):
    # (block_n, d) x (block_v, d) → (block_n, block_v) fp32 on the MXU
    return jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _fwd_kernel(lab_ref, x_ref, w_ref, loss_ref, lse_ref, m_sc, l_sc,
                pick_sc, *, block_v):
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        pick_sc[...] = jnp.zeros_like(pick_sc)

    s = _logits_block(x_ref, w_ref)
    col = j * block_v + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    lab = lab_ref[...][:, :1]                       # (block_n, 1)
    pick_sc[...] += jnp.sum(
        jnp.where(col == lab, s, 0.0), axis=1, keepdims=True)

    m_prev = m_sc[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    l_sc[...] = l_sc[...] * alpha + jnp.sum(
        jnp.exp(s - m_cur[:, :1]), axis=1, keepdims=True)
    m_sc[...] = m_cur

    @pl.when(j == nv - 1)
    def _finalize():
        lse = m_sc[...] + jnp.log(l_sc[...])
        lse_ref[...] = lse
        valid = lab_ref[...][:, :1] >= 0            # ignored rows → 0 loss
        loss_ref[...] = jnp.where(valid, lse - pick_sc[...], 0.0)


def _dlogits(x_ref, w_ref, lab_ref, g_ref, lse_ref, j, block_v):
    """(p − onehot) · g for one logit tile, recomputed from the saved lse
    (g is pre-zeroed for ignored rows on the host)."""
    s = _logits_block(x_ref, w_ref)
    p = jnp.exp(s - lse_ref[...][:, :1])
    col = j * block_v + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    onehot = (col == lab_ref[...][:, :1]).astype(jnp.float32)
    return (p - onehot) * g_ref[...][:, :1]


def _dx_kernel(lab_ref, g_ref, x_ref, w_ref, lse_ref, dx_ref, dx_sc, *,
               block_v):
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        dx_sc[...] = jnp.zeros_like(dx_sc)

    dl = _dlogits(x_ref, w_ref, lab_ref, g_ref, lse_ref, j, block_v)
    dx_sc[...] += jax.lax.dot(dl.astype(w_ref.dtype), w_ref[...],
                              preferred_element_type=jnp.float32)

    @pl.when(j == nv - 1)
    def _finalize():
        dx_ref[...] = dx_sc[...].astype(dx_ref.dtype)


def _dw_kernel(lab_ref, g_ref, x_ref, w_ref, lse_ref, dw_ref, dw_sc, *,
               block_v):
    i = pl.program_id(1)
    ni = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        dw_sc[...] = jnp.zeros_like(dw_sc)

    j = pl.program_id(0)
    dl = _dlogits(x_ref, w_ref, lab_ref, g_ref, lse_ref, j, block_v)
    dw_sc[...] += jax.lax.dot_general(
        dl.astype(x_ref.dtype), x_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(i == ni - 1)
    def _finalize():
        dw_ref[...] = dw_sc[...].astype(dw_ref.dtype)


def _pick_block_v(V: int, want: int) -> int:
    for bv in (want, 512, 384, 256, 128):
        if bv <= V and V % bv == 0 and bv % _LANES == 0:
            return bv
    raise ValueError(
        f"vocab {V} has no 128-multiple block divisor (pad the vocab "
        f"— GPT-3's 50304 = 131*384 is already padded for this)")


def _pad_rows(a, n_pad, fill=0):
    return jnp.pad(a, ((0, n_pad), (0, 0)) if a.ndim == 2
                   else ((0, n_pad),), constant_values=fill)


def _row_spec(block_n):
    return pl.BlockSpec((block_n, _LANES), lambda i, j: (i, 0))


def _fwd(x, w, lab2, block_n, block_v, interpret):
    n, d = x.shape
    V = w.shape[0]
    grid = (n // block_n, V // block_v)
    # ptlint: disable=PT009 -- the fused head never materializes the
    # (n, V) logits: every row block walks ALL vocab tiles (online
    # softmax), so w is re-read n/block_n times by design — that HBM
    # traffic is what buys the O(block) logit memory.
    loss, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, block_v=block_v),
        grid=grid,
        in_specs=[
            _row_spec(block_n),
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, d), lambda i, j: (j, 0)),
        ],
        out_specs=[_row_spec(block_n), _row_spec(block_n)],
        out_shape=[jax.ShapeDtypeStruct((n, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((n, _LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_n, _LANES), jnp.float32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="fused_ce_fwd",
        interpret=interpret,
    )(lab2, x, w)
    return loss[:, 0], lse


def _bwd(x, w, lab2, lse, g2, block_n, block_v, interpret):
    n, d = x.shape
    V = w.shape[0]
    row = _row_spec(block_n)
    # ptlint: disable=PT009 -- dx rebuilds softmax tiles from scratch:
    # w is re-streamed per row block exactly like the forward walk.
    dx = pl.pallas_call(
        functools.partial(_dx_kernel, block_v=block_v),
        grid=(n // block_n, V // block_v),
        in_specs=[
            row, row,
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, d), lambda i, j: (j, 0)),
            row,
        ],
        out_specs=pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_n, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="fused_ce_bwd_dx",
        interpret=interpret,
    )(lab2, g2, x, w, lse)

    rowT = pl.BlockSpec((block_n, _LANES), lambda j, i: (i, 0))
    # ptlint: disable=PT009 -- dw walks every row block per vocab tile
    # (the transposed online-softmax recomputation); x re-reads scale
    # with V/block_v, inherent to not materializing logits.
    dw = pl.pallas_call(
        functools.partial(_dw_kernel, block_v=block_v),
        grid=(V // block_v, n // block_n),
        in_specs=[
            rowT, rowT,
            pl.BlockSpec((block_n, d), lambda j, i: (i, 0)),
            pl.BlockSpec((block_v, d), lambda j, i: (j, 0)),
            rowT,
        ],
        out_specs=pl.BlockSpec((block_v, d), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((V, d), w.dtype),
        scratch_shapes=[pltpu.VMEM((block_v, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="fused_ce_bwd_dw",
        interpret=interpret,
    )(lab2, g2, x, w, lse)
    return dx, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_ce(x, w, lab2, block_n, block_v, interpret):
    loss, _ = _fwd(x, w, lab2, block_n, block_v, interpret)
    return loss


def _fused_ce_fwd(x, w, lab2, block_n, block_v, interpret):
    loss, lse = _fwd(x, w, lab2, block_n, block_v, interpret)
    return loss, (x, w, lab2, lse)


def _fused_ce_bwd(block_n, block_v, interpret, res, dloss):
    import numpy as np
    x, w, lab2, lse = res
    # zero the cotangent on ignored rows so (p − onehot)·g vanishes there
    g = jnp.where(lab2[:, 0] >= 0, dloss.astype(jnp.float32), 0.0)
    g2 = jnp.broadcast_to(g[:, None], (g.shape[0], _LANES))
    dx, dw = _bwd(x, w, lab2, lse, g2, block_n, block_v, interpret)
    return dx, dw, np.zeros(lab2.shape, jax.dtypes.float0)


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


def fused_softmax_cross_entropy(x, w, labels, block_n: int = 128,
                                block_v: int = 512, interpret=None):
    """Per-row CE of ``softmax(x @ w.T)`` against ``labels`` without
    materializing the (N, V) logits.

    Args:
      x: (N, d) final hidden rows (post head-LN).
      w: (V, d) projection in embedding layout (tied wte feeds directly).
      labels: (N,) int32; negative labels are ignored (0 loss, 0 grad) —
        the shifted-causal-LM padding convention.
      block_n / block_v: logit tile streamed through VMEM; block_v is
        shrunk to a 128-multiple divisor of V (ValueError if none exists).
      interpret: defaults to True off-TPU so tests run on CPU.

    Returns (N,) fp32 per-row losses. Differentiable in x and w.
    """
    x, w = jnp.asarray(x), jnp.asarray(w)
    n, d = x.shape
    V = w.shape[0]
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    bv = _pick_block_v(V, block_v)
    if block_n % 8:
        raise ValueError(f"block_n must be a multiple of 8, got {block_n}")
    bn = block_n
    n_pad = (n + bn - 1) // bn * bn - n
    labels = jnp.asarray(labels, jnp.int32)
    if n_pad:
        x = _pad_rows(x, n_pad)
        labels = _pad_rows(labels, n_pad, fill=-1)
    lab2 = jnp.broadcast_to(labels[:, None], (labels.shape[0], _LANES))
    # ptlint: disable=PT001 -- interpret is a static Python flag
    loss = _fused_ce(x, w, lab2, bn, bv, bool(interpret))
    return loss[:n] if n_pad else loss


def ptgeom_cases():
    """Geometry registry for tools/ptgeom.py (ISSUE 20): head shapes
    from the bench ladder x logit-tile candidates, forward and
    backward, under jax.eval_shape."""
    from paddle_tpu.analysis import kernelmodel as km

    def case(geom, bn, bv, bwd=False):
        p = km.LADDER[geom]
        n = 64 if geom == "tiny" else 2048
        x = km.sds((n, p["dm"]), p["dtype"])
        w = km.sds((p["vocab"], p["dm"]), p["dtype"])
        lab = km.sds((n,), "int32")

        def run():
            import jax as _jax

            def loss(x, w, lab):
                l = fused_softmax_cross_entropy(x, w, lab, block_n=bn,
                                                block_v=bv)
                return jnp.sum(jnp.asarray(l, jnp.float32))

            fn = _jax.grad(loss, argnums=(0, 1)) if bwd else loss
            _jax.eval_shape(fn, x, w, lab)
        return km.GeomCase(
            kernel="fused_ce", geometry=geom,
            config=f"bn{bn}.bv{bv}" + (".bwd" if bwd else ""), run=run)

    cases = [case("tiny", 128, 512)]
    for geom in ("350m", "r06"):
        for bn, bv in ((128, 512), (256, 512)):
            cases.append(case(geom, bn, bv))
        cases.append(case(geom, 128, 512, bwd=True))
    return cases
