"""Weight-only int8 post-training quantization for serving.

Reference analog: python/paddle/fluid/contrib/slim/quantization/
post_training_quantization.py (PTQ: per-channel absmax weight scales) and
the int8 fused kernels (operators/fused/*int8*). The TPU-native design is
weight-only QDQ: weights live in HBM as int8 + per-channel fp32 scales
(4x smaller than fp32, 2x smaller than bf16 — decode is HBM-bandwidth
bound, so smaller weights are faster weights) and are dequantized at use
INSIDE the jitted program, where XLA fuses the convert into the matmul
read instead of materializing a float copy.

    from paddle_tpu import quantization as quant
    qmodel = quant.quantize_for_inference(model)
    out = qmodel.generate(tokens, max_new_tokens=64)   # transparent

``QuantTensor`` is a pytree (int8 payload + scales) that presents the
array protocol (__jax_array__, .T, shape/dtype), so model code written
against plain weights (``x @ self.wqkv``) runs unmodified.
"""

import re
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

__all__ = ["QuantTensor", "quantize_tensor", "quantize_for_inference",
           "dequantize_params", "quantize_aware", "convert", "qat"]

# embedding-table heuristic shared with the planner: vocab-ratio tables
# are lookup (gather) weights — quantizing them per-column would mix
# per-matmul-channel semantics with per-row lookups; skip by default
_VOCAB_RATIO = 4


@jax.tree_util.register_pytree_node_class
class QuantTensor:
    """int8 weight + per-channel fp32 scales, dequantized at use.

    ``axis`` records which dim carries the channel scales (kept size-1 in
    ``scale`` for broadcasting). Registered as a pytree so it passes
    through jit/scan/stack like a weight array; the array protocol makes
    ``x @ qt``, ``qt.T``, ``jnp.take(qt, ...)`` work unmodified.
    """

    def __init__(self, q, scale, dtype=jnp.bfloat16):
        self.q = q
        self.scale = scale
        self._dtype = dtype

    def dequantize(self):
        return (self.q.astype(jnp.float32) * self.scale).astype(self._dtype)

    # NOTE deliberately NO __jax_array__: jax's deferring binary ops would
    # convert (dequantize) the operand BEFORE Python ever tries our
    # __rmatmul__, silently bypassing the Pallas int8 kernel. Without it,
    # jnp_array @ qt returns NotImplemented and Python dispatches here.

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim

    @property
    def size(self):
        return self.q.size

    @property
    def dtype(self):
        return self._dtype

    @property
    def T(self):  # noqa: N802 (array-protocol parity)
        return self.dequantize().T

    def astype(self, dtype):
        return self.dequantize().astype(dtype)

    def __matmul__(self, other):
        return self.dequantize() @ other

    def __rmatmul__(self, other):
        """``x @ qt`` — the serving hot path. On TPU this routes the
        Pallas int8 matmul (weights stream HBM→VMEM as int8, dequantized
        per-tile at the MXU; ops/pallas/quant_matmul.py) for a 2-D
        weight and a same-dtype activation outside a GSPMD-partitioned
        trace (the compiler cannot split a Mosaic call); elsewhere XLA
        fuses the convert into the dot. The kernel, once chosen, is the
        path: its failure propagates."""
        from paddle_tpu.distributed.mesh import gspmd_partitioned
        other = jnp.asarray(other)
        if (jax.default_backend() == "tpu" and self.q.ndim == 2
                and other.ndim >= 2 and other.dtype == self._dtype
                and not gspmd_partitioned()):
            from paddle_tpu.ops.pallas.quant_matmul import int8_matmul
            return int8_matmul(other, self.q, self.scale.reshape(1, -1))
        return other @ self.dequantize()

    def __getitem__(self, idx):
        return self.dequantize()[idx]

    def __repr__(self):
        return (f"QuantTensor(int8{list(self.shape)}, "
                f"dequant={self._dtype.__name__ if hasattr(self._dtype, '__name__') else self._dtype})")

    def tree_flatten(self):
        return (self.q, self.scale), (self._dtype,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        q, scale = children
        return cls(q, scale, aux[0])


def quantize_tensor(w, axis: int = -1) -> QuantTensor:
    """Symmetric per-channel absmax int8 quantization (≙ PTQ
    abs_max/channel_wise_abs_max, post_training_quantization.py). ``axis``
    is the channel dim whose scales are kept (the matmul OUTPUT dim for a
    weight used as ``x @ w``: quantization error then never mixes across
    output features)."""
    w = jnp.asarray(w)
    dtype = w.dtype
    wf = w.astype(jnp.float32)
    reduce_axes = tuple(i for i in range(w.ndim) if i != axis % w.ndim)
    absmax = jnp.max(jnp.abs(wf), axis=reduce_axes, keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return QuantTensor(q, scale, dtype)


def _is_vocab_table(shape) -> bool:
    return (len(shape) == 2 and shape[0] >= _VOCAB_RATIO * shape[1]
            and shape[0] >= 256)


def _matmul_weights(model):
    """Param names the structural planner classifies as column/row/expert
    matmul weights (its completion already separates matmul weights from
    lookup tables — exactly the split PTQ needs). Vocab-ratio tables at
    the root are excluded even when their spec collides with the
    row-parallel spec."""
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.distributed.planner import (plan_module,
                                                _in_repeated_block)
    matmul_specs = {P("fsdp", "tp"), P("tp", "fsdp"), P("fsdp", None),
                    P("ep", "fsdp", "tp"), P("ep", "tp", "fsdp")}
    plan = plan_module(model)
    names = set()
    for name, w in model.named_parameters():
        if plan.get(name) not in matmul_specs:
            continue
        if not _in_repeated_block(name) and _is_vocab_table(w.shape):
            continue
        names.add(name)
    return names


def quantize_for_inference(model, include: Optional[str] = None,
                           min_size: int = 4096):
    """Return a copy of ``model`` with matmul weights replaced by int8
    ``QuantTensor``s (weight-only PTQ for the Predictor/generate serving
    paths; VERDICT r2 item 7).

    Quantized: weights the structural planner classifies as matmul
    (column/row/expert-parallel) with >= ``min_size`` elements — or
    exactly the params matching the ``include`` regex when given.
    Embedding/position tables (lookup + lax.dynamic_slice consumers),
    biases, norms and scalars stay float.
    """
    params, _ = model.split_params()
    selected = None if include is not None else _matmul_weights(model)
    out = {}
    n_q = 0
    for name, w in params.items():
        quantize = (re.search(include, name) is not None) \
            if include is not None else (name in selected
                  and jnp.issubdtype(w.dtype, jnp.floating)
                  and w.size >= min_size)
        if quantize:
            # matmul weights (in, out): channel dim is the output = -1;
            # conv kernels OIHW: the output-channel dim is 0
            out[name] = quantize_tensor(w, axis=0 if w.ndim == 4 else -1)
            n_q += 1
        else:
            out[name] = w
    if n_q == 0:
        raise ValueError("quantize_for_inference found no weight to "
                         "quantize (check include/min_size)")
    return model.merge_params(out)


def dequantize_params(params):
    """Flat param dict with every QuantTensor materialized back to float
    (for checkpointing a quantized model or accuracy diffing)."""
    return {k: (v.dequantize() if isinstance(v, QuantTensor) else v)
            for k, v in params.items()}


# QAT (fake-quant training → convert into the weight-only serving path);
# imported at the tail so qat.py can import the PTQ machinery above.
from paddle_tpu.quantization import qat  # noqa: E402
from paddle_tpu.quantization.qat import convert, quantize_aware  # noqa: E402
