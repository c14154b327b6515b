"""Pallas TPU kernels for the compute hot-spots SMOL optimizes.

Each kernel package ships three files:
  <name>.py — the pl.pallas_call kernel with explicit BlockSpec VMEM tiling,
  ops.py    — the jit'd public wrapper (handles padding, grids, dtypes),
  ref.py    — a pure-jnp oracle used by the allclose test sweeps.

Kernels target TPU (MXU-aligned tiles).  The wrappers take
``interpret=None``: compiled on a TPU, interpreted on any other backend
(``kernels/mode.py``), which is how the CPU test suite runs them.
``tests/test_tpu_compile.py`` compiles them for a described v5e.

* idct            — fused dequantize + 8x8 inverse DCT over macroblock grids
                    (the device half of SMOL's split JPEG decode)
* fused_preproc   — resize-as-matmul + normalize + channel layout in one
                    VMEM pass (the DAG optimizer's fusion product, §6.2)
* vit_attention   — whole-row non-causal self-attention of a ViT block,
                    reading q, k, v from the packed qkv; scores stay in VMEM
* flash_attention — blockwise streaming attention (causal / sliding window)
* decode_attention— flash-decoding for single-token serve steps over long
                    KV caches
"""
