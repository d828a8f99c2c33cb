"""Custom TPU kernels (Pallas) behind MXNet-style op entry points.

The reference accelerates its hot ops with hand-written CUDA/cuDNN
(SURVEY.md §2.1 "Operator library"); here XLA covers the bulk and Pallas
covers what XLA won't fuse well — starting with flash attention.
"""
from .flash_attention import flash_attention
from .blocked_cross_entropy import fused_linear_cross_entropy
from .fused_layernorm import fused_layer_norm
from .grouped_matmul import grouped_matmul
from .fused_update import fused_bucket_rule
from .paged_attention import paged_decode_attention
from .quant_matmul import quant_matmul, resolve_compute_dtype
from .quant_kv import resolve_kv_dtype

__all__ = ["flash_attention", "fused_linear_cross_entropy",
           "fused_layer_norm", "fused_bucket_rule", "grouped_matmul",
           "paged_decode_attention", "quant_matmul",
           "resolve_compute_dtype", "resolve_kv_dtype"]
