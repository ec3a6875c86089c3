"""apex_tpu.ops — Pallas TPU kernels and their dispatch layer.

Kernel inventory (TPU-native equivalents of the reference csrc/ tree):
  pallas_multi_tensor — scale / axpby / l2norm over fused flat buffers
                        (csrc/multi_tensor_*.cu)
  pallas_adam         — fused Adam step with optional half write-out
                        (csrc/fused_adam_cuda_kernel.cu)
  pallas_layer_norm   — fused LayerNorm fwd/bwd row reductions
                        (csrc/layer_norm_cuda_kernel.cu)
  pallas_lamb         — LAMB stage1/stage2 (csrc/multi_tensor_lamb_stage_*.cu)
  pallas_flash_attention — fused attention fwd/bwd (no reference
                        equivalent: the 2019 snapshot predates attention)
  pallas_rope         — rotary embedding on token-major projections
  pallas_grouped_matmul — the routed experts' grouped matrix products over
                        rows sorted by group, forward and both gradients
  pallas_ssd          — the selective scan as a kernel pair, chunk by chunk
  pallas_short_conv   — the short causal convolution along the sequence with
                        its gates or SiLU, one pass forward and one backward
  row_moves           — the routed experts' rows to the row buffer and home
                        by gathers (no kernel of ours: the compiler's gather)
  pallas_common       — block arithmetic the kernels share

Each is reached from the code a chip runs under ``dispatch.pallas_enabled()``
/ ``use_pallas_for()`` (tests/test_layering.py holds that).
"""

from . import dispatch
