"""Pallas TPU kernels.  On the CPU backend they run in interpret mode,
which checks their arithmetic; tests/test_tpu_compile.py compiles fused_erm
for a described v5e chip, and chip_smoke.py runs it on one:

  sampled_gather  the paper's contribution at the HBM->VMEM tier
  fused_erm       sampled gather FUSED with the ERM gradient — the epoch
                  engine's hot path; the mini-batch never lands in HBM
  sparse_erm      the CSR counterpart: per-row-segment DMA (RS) vs one
                  contiguous indptr-range DMA (CS/SS), nnz-proportional
                  bytes, rows densified only transiently in VMEM
  flash_attention online-softmax attention for the GQA archs
  ssd             Mamba2 state-space-dual chunked scan
  rglru_scan      RecurrentGemma RG-LRU linear recurrence

Each has a pure-jnp oracle (ref.py, or the ERMProblem gather path for
fused_erm) and a jit'd wrapper.  EXAMPLE.md documents the layout convention.
"""
