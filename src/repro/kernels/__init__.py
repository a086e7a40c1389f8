"""Pallas TPU kernels for LCD's performance-critical paths.

  lut_matmul.py   — int4-code dequant + MXU matmul (the serving GEMM; TPU-
                    native form of the paper's §4 bucket-LUT, DESIGN.md §2),
                    including the single-pass fused smooth+quant+LUT variants
                    (lut_matmul_fused / lut_matmul_fused_gemv)
  smooth_quant.py — standalone smooth+quantize input transform (Eq. 11);
                    kept for calibration tooling — the serving path runs the
                    transform inside the fused GEMM instead
  paged_attention.py — paged attention reading the float or int8 block
                    pools in place (DESIGN.md §9, §15): int8 tiles + scales
                    dequantize in VMEM, the full-precision cache never exists
                    in HBM; per device under shard_map on a mesh (§14)
  ops.py          — padded/blocked jit wrappers, variant selection, CPU
                    fallbacks, per-device shard_map on a mesh (§14), and the
                    lut_serving dispatch context
  autotune.py     — block-shape autotuner (DESIGN.md §11): every entry
                    point's (bm, bn, bk)/(bq, bk) tile shapes come from its
                    persistent cache, else exactly the _pick_blocks
                    heuristic; the traced wrappers never measure (the
                    compiled kernel bench fills the cache on TPU)
  ref.py          — pure-jnp oracles (asserted in tests/test_kernels.py and
                    tests/test_paged_kv.py)
"""
from repro.kernels.ops import (clustered_linear, lut_gemm, lut_gemm_fused,  # noqa: F401
                               lut_gemm_int8, lut_serving)
from repro.kernels.paged_attention import (  # noqa: F401
    paged_attention_mode, paged_dequant_attention)
