"""paddle_tpu — a TPU-native deep-learning framework.

Brand-new design with the capabilities of the reference system surveyed in
``SURVEY.md`` (PaddlePaddle ~v2.4), built idiomatically on JAX/XLA/Pallas:

- tracing + XLA compilation instead of per-op kernel dispatch
  (ref: paddle/phi/core/kernel_factory.h:268 per-call dispatch, eliminated);
- GSPMD named-mesh sharding instead of program-rewrite parallel passes
  (ref: python/paddle/distributed/auto_parallel/);
- ICI/DCN collectives scheduled by XLA instead of NCCL process groups
  (ref: paddle/fluid/distributed/collective/ProcessGroup.h:53);
- Pallas kernels where the reference uses hand-written CUDA fusions
  (ref: paddle/fluid/operators/fused/).

Top-level namespaces mirror the reference's user surface
(python/paddle/{tensor,nn,optimizer,amp,autograd,io,static,distributed}).
"""

from paddle_tpu.version import __version__
from paddle_tpu import flags
from paddle_tpu.flags import get_flags, set_flags
from paddle_tpu import dtypes
from paddle_tpu.dtypes import (
    bfloat16, float16, float32, float64, int8, int16, int32, int64,
    uint8, bool_, complex64, complex128, get_default_dtype, set_default_dtype,
)
from paddle_tpu import random
from paddle_tpu.random import seed, get_rng_state, set_rng_state

# The functional tensor-op surface (ref: python/paddle/tensor/, 314 fns).
from paddle_tpu.tensor import *  # noqa: F401,F403
from paddle_tpu.tensor import __all__ as _tensor_all

from paddle_tpu.framework import (
    Tensor, to_tensor, is_tensor, no_grad, device_count, devices,
    set_device, get_device, grad, value_and_grad, stop_gradient,
)
from paddle_tpu.framework.compat import (
    CPUPlace, CUDAPlace, CUDAPinnedPlace, NPUPlace, TPUPlace, ParamAttr,
    LazyGuard, DataParallel, enable_static, disable_static,
    in_dynamic_mode, is_grad_enabled, set_grad_enabled, check_shape,
    disable_signal_handler, get_cuda_rng_state, set_cuda_rng_state,
    create_parameter, iinfo, reverse)
from paddle_tpu.dtypes import bool_ as bool  # noqa: A001 (ref name)
from paddle_tpu.dtypes import to_dtype as dtype  # ref: paddle.dtype

import paddle_tpu.nn as nn
import paddle_tpu.optimizer as optimizer
import paddle_tpu.autograd as autograd
import paddle_tpu.amp as amp
import paddle_tpu.io as io
import paddle_tpu.metric as metric
import paddle_tpu.distributed as distributed
import paddle_tpu.vision as vision
import paddle_tpu.profiler as profiler
import paddle_tpu.incubate as incubate
import paddle_tpu.static as static
import paddle_tpu.sparse as sparse
import paddle_tpu.quantization as quantization
import paddle_tpu.distribution as distribution
import paddle_tpu.text as text
import paddle_tpu.audio as audio
import paddle_tpu.geometric as geometric
import paddle_tpu.linalg as linalg
import paddle_tpu.fft as fft
import paddle_tpu.signal as signal
import paddle_tpu.stats as stats
import paddle_tpu.observability as observability
import paddle_tpu.onnx as onnx
import paddle_tpu.inference as inference
import paddle_tpu.jit as jit  # callable module: paddle_tpu.jit(fn) / jit.to_static
import paddle_tpu.hub as hub
import paddle_tpu.device as device
import paddle_tpu.reader as reader
import paddle_tpu.dataset as dataset
import paddle_tpu.utils as utils
import paddle_tpu.sysconfig as sysconfig
import paddle_tpu.regularizer as regularizer
import paddle_tpu.cost_model as cost_model
from paddle_tpu.reader import batch
from paddle_tpu.framework.io import save, load
from paddle_tpu.hapi import Model, summary, flops

__all__ = (
    ["__version__", "nn", "optimizer", "autograd", "amp", "io", "metric",
     "distributed", "vision", "profiler", "incubate", "static", "sparse",
     "quantization",
     "distribution", "text", "audio", "geometric", "linalg", "fft", "signal", "stats",
     "observability",
     "onnx", "hub", "device", "reader", "dataset", "utils",
     "sysconfig", "regularizer", "batch", "version", "cost_model",
     "Tensor", "to_tensor", "is_tensor", "jit", "no_grad", "grad",
     "value_and_grad", "stop_gradient", "device_count", "devices",
     "set_device", "get_device", "save", "load", "Model", "summary", "flops",
     "seed", "get_rng_state", "set_rng_state", "get_flags", "set_flags",
     "get_default_dtype", "set_default_dtype", "inference",
     "CPUPlace", "CUDAPlace", "CUDAPinnedPlace", "NPUPlace", "TPUPlace",
     "ParamAttr", "LazyGuard", "DataParallel", "enable_static",
     "disable_static", "in_dynamic_mode", "is_grad_enabled",
     "set_grad_enabled", "check_shape", "disable_signal_handler",
     "get_cuda_rng_state", "set_cuda_rng_state", "create_parameter",
     "iinfo", "reverse", "bool", "dtype"]
    + list(_tensor_all)
)
