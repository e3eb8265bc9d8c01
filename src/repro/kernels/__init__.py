"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel subpackage has: kernel.py (pl.pallas_call + BlockSpec VMEM
tiling), ops.py (jit'd public wrapper, custom_vjp where training needs
gradients), ref.py (pure-jnp oracle used by the allclose test sweeps).

Kernels lower for TPU; on a CPU backend they run in interpret mode
(pl.pallas_call(..., interpret=True)), which is how tests/test_kernels.py
checks them against ref.py. tests/test_tpu_compile.py compiles the
edge-softmax kernel for a described v5e chip.

Kernels:
  flash_attention — causal / sliding-window / GQA online-softmax attention
  rg_lru          — Griffin RG-LRU blocked linear scan
  mlstm           — xLSTM chunkwise matrix-memory cell
  edge_softmax    — Perona GNN fused edge-softmax + neighborhood aggregation
"""
