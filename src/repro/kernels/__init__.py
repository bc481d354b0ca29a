"""Pallas TPU kernels: compiled for the TPU by default; CPU tests ask for
interpret mode and compare with the jnp oracles.

  page_copy       — batched page gather/scatter (the pwritev/preadv analogue)
  paged_attention — GQA decode over bitmap-allocated KV pages
  ssd_scan        — Mamba2 SSD chunked scan with VMEM-resident state
"""
from repro.kernels import page_copy, paged_attention, ssd_scan

__all__ = ["page_copy", "paged_attention", "ssd_scan"]
