"""bigdl_tpu.ops — Pallas TPU kernels for the hot ops.

XLA fuses most of this framework automatically (SURVEY §7 architecture
stance); these kernels cover the cases where hand-tiling pays:
attention's O(T²) score matrix (never materialized — online softmax in
VMEM) and single-pass LayerNorm.  On non-TPU backends the public
wrappers compute reference jnp implementations, so tests and CPU
development need no TPU; on a TPU there is no second path — a kernel
compiles or its error propagates.
"""
from .block_sparse import (BlockMask, block_sparse_attention,
                           block_sparse_matmul, magnitude_block_mask,
                           sliding_window_mask, strided_mask)
from .flash_attention import flash_attention
from .layer_norm import fused_layer_norm
