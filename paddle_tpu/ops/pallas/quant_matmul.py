"""Weight-only int8 matmul as a Pallas TPU kernel.

Reference analog: the int8 fused GEMM inventory —
paddle/fluid/operators/fused/attn_gemm_int8.h, quant_dequant_kernel.h,
and cublasLt int8 matmul dispatch. On TPU the win is different: decode is
HBM-bandwidth bound, so the kernel's job is to stream the weight matrix
through VMEM as int8 (4x less HBM traffic than fp32, 2x less than bf16)
and dequantize per-tile right before the MXU contraction. XLA's own
convert-fusion materializes the dequantized tile too, but only this
kernel guarantees the int8→float convert never round-trips HBM and lets
us pick MXU-shaped tiles.

Inference-only: gradients flow to the activation x (straight-through
w.r.t. the dequantized weight is the XLA path's job; serving never needs
dw).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["int8_matmul"]

_LANES = 128


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _kernel(x_ref, q_ref, scale_ref, o_ref, acc_ref):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    w = q_ref[...].astype(x.dtype)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = (acc_ref[...]
                      * scale_ref[...].astype(jnp.float32)
                      ).astype(o_ref.dtype)


def int8_matmul(x, q, scale, block_m: int = 256, block_n: int = 512,
                block_k: int = 512, interpret=None):
    """``(x @ q.astype(float)) * scale`` with q int8, scale per-column.

    x: (..., K) float; q: (K, N) int8; scale: (N,) or (1, N) fp32.
    Returns (..., N) in x.dtype. Off-TPU runs in interpreter mode.
    """
    x = jnp.asarray(x)
    q = jnp.asarray(q)
    scale = jnp.asarray(scale, jnp.float32).reshape(1, -1)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    lead = x.shape[:-1]
    kdim = x.shape[-1]
    n = q.shape[1]
    assert q.shape[0] == kdim, (x.shape, q.shape)
    x2 = x.reshape(-1, kdim)
    m = x2.shape[0]

    # decode has tiny M — clamp blocks so padding never multiplies work
    block_m = min(block_m, _round_up(m, 8))
    block_n = min(block_n, _round_up(n, _LANES))
    block_k = min(block_k, _round_up(kdim, _LANES))
    m_p, n_p, k_p = (_round_up(m, block_m), _round_up(n, block_n),
                     _round_up(kdim, block_k))
    if (m_p, k_p) != (m, kdim):
        x2 = jnp.pad(x2, ((0, m_p - m), (0, k_p - kdim)))
    if (k_p, n_p) != (kdim, n):
        q = jnp.pad(q, ((0, k_p - kdim), (0, n_p - n)))
    if n_p != n:
        scale = jnp.pad(scale, ((0, 0), (0, n_p - n)))

    # ptlint: disable=PT009 -- K-blocked matmul: x re-reads once per N
    # tile and q once per M tile — the classic blocked-GEMM streaming
    # pattern; re-read factor is bounded by the block_n/block_m sweep
    # the autotuner already prices in wall time.
    out = pl.pallas_call(
        _kernel,
        grid=(m_p // block_m, n_p // block_n, k_p // block_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_p, n_p), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="int8_matmul",
        interpret=interpret,
    )(x2, q, scale)
    return out[:m, :n].reshape(*lead, n)


def ptgeom_cases():
    """Geometry registry for tools/ptgeom.py (ISSUE 20): the MLP-width
    int8 matmul at train-like and decode-like M, under
    jax.eval_shape."""
    from paddle_tpu.analysis import kernelmodel as km

    def case(geom, m, bm, bn, bk):
        p = km.LADDER[geom]
        kdim = p["dm"]
        n = 4 * p["dm"]
        x = km.sds((m, kdim), p["dtype"])
        qm = km.sds((kdim, n), "int8")
        sc = km.sds((n,), "float32")

        def run():
            import jax as _jax
            _jax.eval_shape(
                lambda x, qm, sc: int8_matmul(
                    x, qm, sc, block_m=bm, block_n=bn, block_k=bk),
                x, qm, sc)
        return km.GeomCase(kernel="int8_matmul", geometry=geom,
                           config=f"m{m}.bm{bm}.bn{bn}.bk{bk}",
                           run=run)

    cases = []
    for geom in ("350m", "r06"):
        cases.append(case(geom, 2048, 256, 512, 512))
        cases.append(case(geom, 8, 256, 512, 512))
    return cases
