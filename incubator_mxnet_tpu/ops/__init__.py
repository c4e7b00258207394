"""Operator library (TPU-native equivalent of [U:src/operator/]).

The reference registers ~1000 C++/CUDA kernels behind the NNVM registry; here
every operator is a *pure function on jax.Arrays* registered in
:mod:`.registry`.  XLA plays the role of mshadow/cuDNN/oneDNN: lowering,
fusion, tiling onto the MXU.  Custom Pallas kernels slot in as just another
registered function.
"""
from . import registry
from .registry import register, get_op, list_ops, Op
from . import tensor  # noqa: F401  (registers tensor ops)
from . import nn  # noqa: F401  (registers NN ops)
from . import rnn_ops  # noqa: F401  (registers fused RNN)
from . import attention  # noqa: F401  (registers fused/flash attention)
from . import moe  # noqa: F401  (registers the MoE dispatch/combine kernels)
from . import hyper_connections  # noqa: F401  (registers the mHC residual mix)
from . import ssm  # noqa: F401  (registers the state-space scan, its convolution and gated norm)
from . import sparse_attention  # noqa: F401  (registers indexer-selected attention and its pieces)
from . import detection  # noqa: F401  (registers MultiBox*/box_nms/box_iou)
from . import quantization  # noqa: F401  (registers quantize_v2/dequantize/int8 ops)
from . import linalg  # noqa: F401  (registers the la_op family)
from . import random_ops  # noqa: F401  (registers _random_*/_sample_* samplers)
from . import optimizer_ops  # noqa: F401  (registers fused update kernels as public ops)
from . import spatial  # noqa: F401  (registers ROI/grid/bilinear/spatial CV ops)

__all__ = ["register", "get_op", "list_ops", "Op", "registry", "tensor", "nn"]
