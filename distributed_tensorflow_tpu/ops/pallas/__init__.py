"""Pallas TPU kernels for the hot ops.

The reference has no native kernels of its own — its compute lowers to the
C++/Eigen/cuDNN kernels inside the pinned ``tensorflow==1.4.0`` wheel
(reference requirements.txt:6).  This package is the TPU-native analogue:
hand-written Mosaic/Pallas kernels for the ops where XLA's automatic
fusion leaves performance on the table, dispatched behind the same
signatures as the pure-XLA implementations in ``ops``.

Every kernel runs in Pallas interpret mode off-TPU so the whole test suite
exercises the real kernel code paths on the virtual CPU mesh.
"""
from .flash_attention import flash_attention, make_flash_attention_fn
from .fused import (fused_adam_update, fused_layernorm, fused_rmsnorm,
                    resolve_fused_ln)
from .paged_attention import (MIN_PAGE_SIZE, page_size_kernel_ok, page_walk,
                              paged_decode_attention,
                              paged_window_attention)

__all__ = [
    "flash_attention",
    "make_flash_attention_fn",
    "fused_adam_update",
    "fused_layernorm",
    "fused_rmsnorm",
    "resolve_fused_ln",
    "MIN_PAGE_SIZE",
    "page_size_kernel_ok",
    "page_walk",
    "paged_decode_attention",
    "paged_window_attention",
]
