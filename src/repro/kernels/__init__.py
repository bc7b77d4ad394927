"""Pallas TPU kernels for the paper's perf-critical operators:
flash_attention (fused MHA, §3.2.1), paged_attention (block-table flash
decode/prefill for the serving engine) and quant_matmul (int4 dequant
GEMM, §3.3.1), plus grouped_lora for multi-tenant adapters. Validated in
interpret mode on CPU; the engine's kernels (paged_attention,
grouped_lora) are also compiled for a TPU v5e in tests/test_tpu_compile.py."""
from . import flash_attention, paged_attention, quant_matmul

__all__ = ["flash_attention", "paged_attention", "quant_matmul"]
