"""Hand-written Pallas TPU kernels for the hot ops — the role CUDA/cuDNN
kernels and the NVRTC pointwise-fusion JIT (``src/operator/fusion/``) played
in the reference. Everything else rides XLA's own fusion.
"""
from .flash_attention import flash_attention
from .paged_attention import paged_attention_kernel

__all__ = ["flash_attention", "paged_attention_kernel"]
