"""Pallas TPU kernels for the LoRA-FL hot spots.

Wrappers default to ``interpret=None`` (auto-detect): real Pallas lowering
on TPU/GPU, interpreter mode on CPU.  Validated in interpreter mode on CPU
against the ref.py oracles; pass ``interpret=False`` to force compilation.
"""
from .lora_matmul.ops import (batched_lora_matmul,
                              batched_lora_matmul_inline, lora_dense_apply,
                              lora_matmul, lora_matmul_inline)
from .lora_matmul.ref import (batched_lora_matmul_ref,
                              batched_lora_matmul_segments, lora_matmul_ref)
from .rbla_agg.ops import axpy_fold, packed_agg, packed_robust, packed_stack
from .rbla_agg.ref import (axpy_fold_ref, packed_agg_ref, packed_robust_ref,
                           packed_robust_xla, packed_stack_ref)
from .ssd_scan.ops import ssd_scan
from .ssd_scan.ref import ssd_scan_ref

__all__ = ["lora_dense_apply", "lora_matmul", "lora_matmul_inline",
           "lora_matmul_ref", "batched_lora_matmul",
           "batched_lora_matmul_inline", "batched_lora_matmul_ref",
           "batched_lora_matmul_segments",
           "axpy_fold", "axpy_fold_ref",
           "packed_agg", "packed_agg_ref", "packed_robust",
           "packed_robust_ref", "packed_robust_xla",
           "packed_stack", "packed_stack_ref",
           "ssd_scan", "ssd_scan_ref"]
