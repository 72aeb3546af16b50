"""Pallas TPU device kernels.

The reference ships its device kernels as CUDA under ``csrc/`` (fused
transformer ``csrc/transformer/*.cu``, fused optimizers
``csrc/adam/multi_tensor_adam.cu``, quantizer ``csrc/quantization/*.cu``);
the TPU-native equivalents live here as Pallas kernels lowered through
Mosaic onto the MXU/VPU.

Every kernel has a pure-jnp reference implementation used (a) on non-TPU
backends, (b) as the ground truth in unit tests (Pallas interpret mode vs
reference), so the whole package is CI-testable on CPU.
"""

from .block_sparse_attention import block_sparse_attention, sparse_mha_reference
from .flash_attention import (flash_attention, flash_attention_packed,
                              mha_reference)
from .fused_adam import fused_adam_step
from .fused_lamb import fused_lamb_step
from .quantizer import dequantize, quantize

__all__ = [
    "flash_attention",
    "flash_attention_packed",
    "mha_reference",
    "block_sparse_attention",
    "sparse_mha_reference",
    "fused_adam_step",
    "fused_lamb_step",
    "quantize",
    "dequantize",
]
