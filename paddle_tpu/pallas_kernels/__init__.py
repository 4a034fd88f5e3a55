"""Hand-written Pallas TPU kernels — the TPU-native analog of the
reference's fused CUDA ops (paddle/fluid/operators/fused/,
multihead_matmul_op.cu) and its xbyak JIT CPU codegen (operators/jit/)."""

from .flash_attention import flash_attention  # noqa: F401
from . import adoption  # noqa: F401  (the one rule that chooses a kernel)
from . import paged_attention  # noqa: F401  (decode-serving gather kernel)
