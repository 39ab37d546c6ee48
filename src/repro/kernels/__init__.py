"""Pallas TPU kernels for the ingest hot spots, the train step's attention
and the dropless expert layer's grouped matrix products.

Each kernel: <name>.py (a Pallas call and its tiling), a pure oracle (ref.py;
for flash attention, models.attention.attention_naive), and a jit'd wrapper
in ops.py (interpret=True off-TPU).
"""
from .ops import flash_attention, gf256_matmul, grouped_matmul, pack_tokens

__all__ = ["flash_attention", "gf256_matmul", "grouped_matmul", "pack_tokens"]
